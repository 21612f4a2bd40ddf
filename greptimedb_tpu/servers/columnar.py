"""A result's wire values, made a column at a time.

The HTTP, MySQL and Postgres writers used to walk a result cell by cell
in Python (`isinstance`, `.item()`, `Timestamp(...).strftime`, one
`struct.pack` and one `sendall` a row). Here every column of a chunk
becomes its wire text in a few calls, and only then are the columns
interleaved into rows. What the writers frame goes out through a
`SlabWriter`: one `sendall` a slab, not one a row.

A chunk's rows take one of three routes, and the `render` span's `path`
and `greptime_render_rows_total{protocol, path}` say which:

- `compiled`: Arrow's kernels print the text and no Python object is made
  a cell. On HTTP that is every column (the integer and float casts,
  strings quoted in bulk) and the interleave into `[a, b, c]` rows
  (`json_rows_text`); on the text wires it is the float columns
  (`float_texts`), whose `repr` was most of a wide answer's time.
- `columnar`: a column at a time through Python objects (`ndarray.tolist`,
  `map(repr, ...)`, `np.datetime_as_string`, `zip`, `json.dumps`): what a
  chunk of fewer than `COMPILED_MIN_ROWS` rows takes, because a dozen Arrow
  calls a column cost more than that for a handful of rows.
- `cell`: a column none of this can do (an object array holding anything
  but `str`, a timestamp outside the years 1000-9999) takes the per-cell
  code the writers had, same text; no dtype of a table or of an aggregate
  needs it, so a fallback that engages is visible.
"""

from __future__ import annotations

import contextlib
import json
import socket
from collections import Counter
from itertools import chain
from typing import (Iterable, Iterator, List, NamedTuple, Optional, Sequence,
                    Tuple)

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from ..common.time import Timestamp, TimeUnit
from ..datatypes.record_batch import RecordBatch
from ..datatypes.vector import Vector, python_values

#: a writer sends what it has framed once it holds this much: a
#: `SELECT *` of 17M rows must not become one buffer
SLAB_BYTES = 4 << 20
#: rows turned into cells at a time (bounds the lists of a large result)
CHUNK_ROWS = 16384


class SlabWriter:
    """The sending half of a protocol's framing: bytes go to the socket
    as they come, or, inside `slab()`, gathered and sent `SLAB_BYTES` at a
    time. Without a socket they are counted and dropped (servers/render.py
    encodes an EXPLAIN ANALYZE'd result that way, through the same code)."""

    def __init__(self, sock: Optional[socket.socket]):
        self.sock = sock
        self.bytes_out = 0
        self._slab: Optional[bytearray] = None

    def write(self, data: bytes) -> None:
        if self._slab is None:
            self._send(data)
            return
        self._slab += data
        if len(self._slab) >= SLAB_BYTES:
            self._send(self._slab)
            self._slab = bytearray()

    def write_rows(self, *parts: List[bytes]) -> None:
        """Rows framed together: row i is the i-th items of `parts` (its
        header, then a prefix and a text a cell), joined."""
        self.write(b"".join(chain.from_iterable(zip(*parts))))

    @contextlib.contextmanager
    def slab(self) -> Iterator[None]:
        """Gather what is written inside; the rest goes out at the end
        (and nowhere after an exception: the connection is lost then)."""
        self._slab = bytearray()
        try:
            yield
            if self._slab:
                self._send(self._slab)
        finally:
            self._slab = None

    def _send(self, data) -> None:
        if self.sock is not None:
            self.sock.sendall(data)
        self.bytes_out += len(data)


# ---------------------------------------------------------------------------
# routes, and the floats' text
# ---------------------------------------------------------------------------

COMPILED, COLUMNAR, CELL = "compiled", "columnar", "cell"

#: rows a route rendered, by route
RouteRows = Counter

#: a chunk of fewer rows takes the `columnar` route. Arrow's kernels cost
#: microseconds a call whatever the length and a column takes a dozen of
#: them; `tolist` + `repr` cost nothing a call and a microsecond a value.
#: On the chip's host (PR 38, 12 columns of which 10 float, ms a result,
#: compiled / columnar): HTTP 0.93 / 0.24 at 12 rows, 1.15 / 1.08 at 128,
#: 1.27 / 1.54 at 192, 83.8 / 345.5 at 48,000; MySQL 1.01 / 0.40, 1.52 /
#: 1.49, 1.77 / 2.09, 216.3 / 481.7: both wires cross at about 135 rows
COMPILED_MIN_ROWS = 128

#: `float.__repr__` and Arrow's float64 -> string cast print the same
#: shortest round-trip digits; they lay them out alike for a non-integral
#: x with _CAST_LOW <= |x| < _CAST_HIGH. Outside it the cast prints `100`
#: for `100.0` and `-0` for `-0.0`, `0.00009999` for `9.999e-05` and
#: `1.5e-7` for `1.5e-07`, `1.00000000005e+10` for `10000000000.5`
#: (tests/test_render_columnar.py holds both edges)
_CAST_LOW, _CAST_HIGH = 1e-4, 1e10


def route_of(routes: Iterable[str]) -> str:
    """What a chunk or a span says of the routes its parts took: `cell`
    if any fell back, else `compiled` if compiled code printed any of it."""
    routes = set(routes)
    for route in (CELL, COMPILED):
        if route in routes:
            return route
    return COLUMNAR


def cast_prints_repr(values: np.ndarray) -> np.ndarray:
    """Where Arrow's cast of these float64 prints `repr`'s text."""
    size = np.abs(values)
    with np.errstate(invalid="ignore"):         # a signalling NaN
        whole = values == np.rint(values)
    return (size >= _CAST_LOW) & (size < _CAST_HIGH) & ~whole


def float_texts(data: np.ndarray, unread: Optional[np.ndarray] = None
                ) -> pa.StringArray:
    """`repr` of every value of a float column, as Arrow strings: the cast
    where it prints what `float.__repr__` prints, `repr` itself for the
    values outside that band (integral, tiny, huge, not finite). `unread`
    marks cells whose text nobody reads (NULLs)."""
    values = data.astype(np.float64, copy=False)
    texts = pc.cast(pa.array(values), pa.string())
    outside = ~cast_prints_repr(values)
    if unread is not None:
        outside &= ~unread
    if outside.any():
        texts = pc.replace_with_mask(
            texts, pa.array(outside),
            pa.array(list(map(repr, values[outside].tolist())),
                     type=pa.string()))
    return texts


def _chunks(batches: Sequence[RecordBatch]) -> Iterator[RecordBatch]:
    """A result as chunks of at most CHUNK_ROWS rows."""
    for b in batches:
        for lo in range(0, b.num_rows, CHUNK_ROWS):
            yield b if b.num_rows <= CHUNK_ROWS else b.slice(lo, CHUNK_ROWS)


# ---------------------------------------------------------------------------
# JSON text (HTTP)
# ---------------------------------------------------------------------------

#: bytes `json.dumps` prints as they are inside a string
_JSON_PLAIN = np.zeros(256, dtype=bool)
_JSON_PLAIN[0x20:0x7f] = True
_JSON_PLAIN[[ord('"'), ord("\\")]] = False


def json_rows_text(batches: Sequence[RecordBatch]
                   ) -> Tuple[List[bytes], RouteRows]:
    """-> (the text `json.dumps` makes of a result's rows, `[[a, b], [c,
    d]]`, in pieces to be joined; the rows each route rendered). NULL and
    NaN are `null`."""
    pieces: List[bytes] = [b"["]
    routes = RouteRows()
    for chunk in _chunks(batches):
        if not chunk.columns:
            continue
        if len(pieces) > 1:
            pieces.append(b", ")
        text, route = _json_chunk(chunk)
        pieces += text
        routes[route] += chunk.num_rows
    pieces.append(b"]")
    return pieces, routes


def _json_chunk(chunk: RecordBatch) -> Tuple[List[bytes], str]:
    """-> (pieces of `[a, b], [c, d]`, a chunk's rows; the route they
    took)."""
    if chunk.num_rows >= COMPILED_MIN_ROWS:
        text = _json_chunk_compiled(chunk)
        if text is not None:
            return [b"[", text, b"]"], COMPILED
    columns = [_json_column(v) for v in chunk.columns]
    rows = list(zip(*[values for values, _ in columns]))
    per_cell = any(fell_back for _, fell_back in columns)
    return ([json.dumps(rows)[1:-1].encode()],
            CELL if per_cell else COLUMNAR)


def _json_chunk_compiled(chunk: RecordBatch) -> Optional[pa.Buffer]:
    """`a, b], [c, d` of a chunk's rows by Arrow's kernels alone; None
    where a column has no such text, the chunk's is more than an Arrow
    string holds (2 GiB), or a string has a lone surrogate in it, which
    `json.dumps` escapes and UTF-8 has no bytes for."""
    columns = []
    try:
        for vec in chunk.columns:
            texts = _json_texts(vec)
            if texts is None:
                return None
            columns.append(texts)
        rows = pc.binary_join_element_wise(
            *columns, ", ", null_handling="replace", null_replacement="null")
        joined = pc.binary_join(
            pa.ListArray.from_arrays([0, len(rows)], rows), "], [")
    except (pa.ArrowCapacityError, UnicodeEncodeError):
        return None
    return joined[0].as_buffer()


def _json_nulls(vec: Vector) -> Optional[np.ndarray]:
    """Where a column's `null`s go: its NULLs, and a float column's NaN."""
    nulls = None if vec.validity is None else ~vec.validity
    if vec.data.dtype.kind == "f":
        nan = np.isnan(vec.data)
        nulls = nan if nulls is None else nan | nulls
    return nulls


def _json_texts(vec: Vector) -> Optional[pa.Array]:
    """A column's JSON texts as Arrow strings, null where `null` goes."""
    data = vec.data
    if not isinstance(data, np.ndarray):
        return None
    nulls = _json_nulls(vec)
    kind = data.dtype.kind
    if kind == "f":
        texts = float_texts(data, nulls)
        infinite = np.isinf(data) & ~nulls
        if infinite.any():              # what `json.dumps` prints for them
            texts = pc.replace_with_mask(
                texts, pa.array(infinite), pa.array(
                    np.where(data[infinite] > 0, "Infinity", "-Infinity"),
                    type=pa.string()))
        return pc.if_else(pa.array(nulls), None, texts) \
            if nulls.any() else texts
    if kind in "iu":
        return pc.cast(pa.array(data, mask=nulls), pa.string())
    if kind == "b":
        return pc.if_else(pa.array(data, mask=nulls), "true", "false")
    if kind == "O" and _only_str(data):
        strings = pa.array(data, type=pa.string(), mask=nulls)
        if _JSON_PLAIN[np.frombuffer(strings.buffers()[2] or b"",
                                     dtype=np.uint8)].all():
            return pc.binary_join_element_wise('"', strings, '"', "")
        return pa.array([None if s is None else json.dumps(s)
                         for s in python_values(data, nulls)],
                        type=pa.string())
    return None


def _json_column(vec: Vector) -> Tuple[list, bool]:
    """A column's values as `json.dumps` takes them (NULL and NaN are
    None), and whether the per-cell path made them."""
    data = vec.data
    if isinstance(data, np.ndarray) and (
            data.dtype.kind in "fiub"
            or data.dtype == object and _only_str(data)):
        return python_values(data, _json_nulls(vec)), False
    return [None if v != v else v for v in vec.to_pylist()], True


def _only_str(data: np.ndarray) -> bool:
    """Does this object array hold nothing but `str` (and None)?"""
    return set(map(type, data.tolist())) <= {str, type(None)}


# ---------------------------------------------------------------------------
# text cells (MySQL, Postgres)
# ---------------------------------------------------------------------------

class TextStyle(NamedTuple):
    """Where the text of a value differs between the two wires."""
    bools: Tuple[bytes, bytes]          # (false, true)
    ts_digits: int                      # of a timestamp's second: 3 or 6


MYSQL_TEXT = TextStyle((b"0", b"1"), 3)
POSTGRES_TEXT = TextStyle((b"f", b"t"), 6)

#: one column of a chunk: every cell's text (b"" where NULL), and the NULLs
TextColumn = Tuple[List[bytes], Optional[np.ndarray]]

_TS_FORMAT = "%Y-%m-%d %H:%M:%S.%f"
#: seconds of 1000-01-01 and of 10000-01-01: the years `strftime` and
#: numpy print alike, with four digits
_TS_SECONDS = (-30_610_224_000, 253_402_300_800)


def text_chunks(batches: Sequence[RecordBatch], style: TextStyle
                ) -> Iterator[Tuple[int, List[TextColumn], str]]:
    """A result as chunks of at most CHUNK_ROWS rows -> (rows, their
    columns as text cells, the route they took)."""
    for chunk in _chunks(batches):
        columns = [_text_column(v, style) for v in chunk.columns]
        yield (chunk.num_rows, [column for column, _ in columns],
               route_of(route for _, route in columns))


def literal_columns(rows: Sequence[Sequence], ncols: int
                    ) -> List[TextColumn]:
    """Text cells of a handful of literal rows (a fabricated answer)."""
    columns = []
    for i in range(ncols):
        values = [r[i] for r in rows]
        columns.append((
            [b"" if v is None else str(v).encode() for v in values],
            np.array([v is None for v in values], dtype=bool)))
    return columns


def cell_lengths(cells: List[bytes]) -> np.ndarray:
    return np.fromiter(map(len, cells), dtype=np.int64, count=len(cells))


def _text_column(vec: Vector, style: TextStyle) -> Tuple[TextColumn, str]:
    """-> (the column's cells and NULLs, the route that made them)."""
    data = vec.data
    nulls = None if vec.validity is None else ~vec.validity
    cells = None
    route = COLUMNAR
    if isinstance(data, np.ndarray):
        kind = data.dtype.kind
        if vec.dtype.is_timestamp:
            if kind == "i":
                cells = _timestamp_cells(data, vec.dtype.time_unit, style)
        elif kind == "f" and len(data) >= COMPILED_MIN_ROWS:
            route = COMPILED
            cells = float_texts(data, nulls).cast(pa.binary()).to_numpy(
                zero_copy_only=False).tolist()
        elif kind == "f":
            cells = list(map(str.encode, map(repr, data.tolist())))
        elif kind in "iu":
            cells = list(map(str.encode, map(str, data.tolist())))
        elif kind == "b":
            cells = list(map(style.bools.__getitem__, data.tolist()))
        elif kind == "O" and _only_str(data):
            none = np.equal(data, None)
            if none.any():
                nulls = none if nulls is None else nulls | none
                data = np.where(none, "", data)
            cells = list(map(str.encode, data.tolist()))
    if cells is None:
        return _cell_texts(vec, style), CELL
    if nulls is not None:
        held = np.empty(len(cells), dtype=object)
        held[:] = cells
        held[nulls] = b""
        cells = held.tolist()
    return (cells, nulls), route


def _timestamp_cells(data: np.ndarray, unit: TimeUnit, style: TextStyle
                     ) -> Optional[List[bytes]]:
    """`YYYY-MM-DD HH:MM:SS.fff[fff]` of every tick, floored to the
    style's precision (what `Timestamp.to_datetime().strftime` prints);
    None where a year lies outside 1000-9999."""
    n = len(data)
    if n == 0:
        return []
    lo, hi = (s * unit.factor for s in _TS_SECONDS)
    if int(data.min()) < lo or int(data.max()) >= hi:
        return None
    shown = {3: "ms", 6: "us"}[style.ts_digits]
    stamps = data.astype(np.int64, copy=False).view(
        f"datetime64[{unit.value}]").astype(f"datetime64[{shown}]")
    text = np.datetime_as_string(stamps)
    width = len("YYYY-MM-DDTHH:MM:SS.") + style.ts_digits
    chars = text.view(np.uint32).reshape(n, -1)[:, :width].astype(np.uint8)
    chars[:, 10] = ord(" ")
    return chars.view(f"S{width}")[:, 0].tolist()


def _cell_texts(vec: Vector, style: TextStyle) -> TextColumn:
    """The per-cell path: any value, one at a time."""
    values = vec.to_pylist()
    unit = vec.dtype.time_unit
    false, true = style.bools
    cells = []
    for v in values:
        if v is None:
            cells.append(b"")
        elif unit is not None:
            text = Timestamp(v, unit).to_datetime().strftime(_TS_FORMAT)
            cells.append(text[:len(text) - 6 + style.ts_digits].encode())
        elif isinstance(v, bool):
            cells.append(true if v else false)
        else:
            cells.append(str(v).encode())
    nulls = np.fromiter((v is None for v in values), dtype=bool,
                        count=len(values))
    return cells, nulls if nulls.any() else None
