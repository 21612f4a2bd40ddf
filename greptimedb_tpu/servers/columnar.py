"""A result's wire values, made a column at a time.

The HTTP, MySQL and Postgres writers used to walk a result cell by cell
in Python (`isinstance`, `.item()`, `Timestamp(...).strftime`, one
`struct.pack` and one `sendall` a row). Here every column of a batch
becomes its wire values in a few calls that loop in C — `ndarray.tolist`,
`map(repr, ...)`, `np.datetime_as_string`, masks for the NULLs — and only
then are the columns interleaved into rows (`zip`). What the writers frame
goes out through a `SlabWriter`: one `sendall` a slab, not one a row.

A column this cannot do (an object array holding anything but `str`, a
timestamp outside the years 1000-9999) takes `_cell_*`: the per-cell code
the writers had, same text. The `render` span then says `path="cell"` and
`greptime_render_rows_total{path="cell"}` counts the rows, so a fallback
that engages is visible; no dtype of a table or of an aggregate needs it.
"""

from __future__ import annotations

import contextlib
import socket
from itertools import chain
from typing import Iterator, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

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
# JSON values (HTTP)
# ---------------------------------------------------------------------------

def json_rows(batches: Sequence[RecordBatch]) -> Tuple[List[tuple], int]:
    """-> (the rows as `json.dumps` takes them: NULL and NaN are None,
    rows that took the per-cell path)."""
    rows: List[tuple] = []
    cell_rows = 0
    for b in batches:
        columns = [_json_column(v) for v in b.columns]
        if any(fell_back for _, fell_back in columns):
            cell_rows += b.num_rows
        rows.extend(zip(*[values for values, _ in columns]))
    return rows, cell_rows


def _json_column(vec: Vector) -> Tuple[list, bool]:
    data = vec.data
    if isinstance(data, np.ndarray) and (
            data.dtype.kind in "fiub"
            or data.dtype == object and _only_str(data)):
        nulls = None if vec.validity is None else ~vec.validity
        if data.dtype.kind == "f":
            nan = np.isnan(data)
            nulls = nan if nulls is None else nan | nulls
        return python_values(data, nulls), False
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
                ) -> Iterator[Tuple[int, List[TextColumn], bool]]:
    """A result as chunks of at most CHUNK_ROWS rows -> (rows, their
    columns as text cells, whether a column took the per-cell path)."""
    for b in batches:
        for lo in range(0, b.num_rows, CHUNK_ROWS):
            chunk = b if b.num_rows <= CHUNK_ROWS \
                else b.slice(lo, CHUNK_ROWS)
            columns = [_text_column(v, style) for v in chunk.columns]
            yield (chunk.num_rows, [column for column, _ in columns],
                   any(fell_back for _, fell_back in columns))


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


def _text_column(vec: Vector, style: TextStyle) -> Tuple[TextColumn, bool]:
    """-> (the column's cells and NULLs, whether the per-cell path made
    them)."""
    data = vec.data
    nulls = None if vec.validity is None else ~vec.validity
    cells = None
    if isinstance(data, np.ndarray):
        kind = data.dtype.kind
        if vec.dtype.is_timestamp:
            if kind == "i":
                cells = _timestamp_cells(data, vec.dtype.time_unit, style)
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
        return _cell_texts(vec, style), True
    if nulls is not None:
        held = np.empty(len(cells), dtype=object)
        held[:] = cells
        held[nulls] = b""
        cells = held.tolist()
    return (cells, nulls), False


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
