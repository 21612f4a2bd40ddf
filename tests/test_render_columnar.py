"""The column-wise writers against row-by-row references, byte for byte.

servers/columnar.py makes a result's wire values a column at a time and
the HTTP, MySQL and Postgres writers frame them in slabs. The encoders
they replaced walked a result cell by cell; their semantics live on here
as the plain references (`ref_*`): today's bytes must be the bytes a
client read before, for every dtype, NULL, slab and packet boundary.
"""

import json
import math
import struct

import numpy as np
import pytest

from greptimedb_tpu.common import telemetry
from greptimedb_tpu.common.time import Timestamp
from greptimedb_tpu.datatypes import data_type as dt
from greptimedb_tpu.datatypes.record_batch import RecordBatch
from greptimedb_tpu.datatypes.schema import ColumnSchema, Schema
from greptimedb_tpu.datatypes.vector import Vector, null_column
from greptimedb_tpu.query.output import Output
from greptimedb_tpu.servers import columnar, http, mysql, postgres

# ---------------------------------------------------------------------------
# the references: one cell at a time, as the writers did it before
# ---------------------------------------------------------------------------


def ref_pylist(vec):
    if vec.validity is None:
        if vec.dtype.is_boolean:
            return [bool(v) for v in vec.data]
        return [v.item() if isinstance(v, np.generic) else v
                for v in vec.data]
    out = []
    for v, ok in zip(vec.data, vec.validity):
        if not ok:
            out.append(None)
        elif isinstance(v, np.generic):
            out.append(v.item())
        else:
            out.append(v)
    return out


def ref_rows(batches):
    for b in batches:
        yield from zip(*[ref_pylist(c) for c in b.columns])


def ref_http_body(out):
    rows = [[None if v != v else v for v in r]
            for r in ref_rows(out.batches)]
    cols = [{"name": c.name, "data_type": c.dtype.name}
            for c in out.schema.column_schemas]
    return json.dumps({
        "code": 0,
        "output": [{"records": {"schema": {"column_schemas": cols},
                                "rows": rows}}],
        "execution_time_ms": 0}).encode()


def ref_lenenc_int(n):
    if n < 0xFB:
        return bytes([n])
    if n < 1 << 16:
        return b"\xfc" + struct.pack("<H", n)
    if n < 1 << 24:
        return b"\xfd" + struct.pack("<I", n)[:3]
    return b"\xfe" + struct.pack("<Q", n)


def ref_lenenc_str(s):
    return ref_lenenc_int(len(s)) + s


class RefPackets:
    """3-byte length + sequence number, one packet at a time."""

    def __init__(self, seq=0):
        self.seq = seq
        self.stream = b""

    def write(self, payload):
        offset = 0
        while True:
            chunk = payload[offset:offset + 0xFFFFFF]
            self.stream += (len(chunk).to_bytes(3, "little")
                            + bytes([self.seq]) + chunk)
            self.seq = (self.seq + 1) & 0xFF
            offset += len(chunk)
            if len(chunk) < 0xFFFFFF:
                break


def ref_mysql_cell(v, dtype):
    if v is None:
        return None
    if dtype.is_timestamp:
        return Timestamp(v, dtype.time_unit).to_datetime().strftime(
            "%Y-%m-%d %H:%M:%S.%f")[:-3]
    if isinstance(v, bool):
        return 1 if v else 0
    return v


def ref_mysql_stream(out, binary, seq):
    schema = out.batches[0].schema
    io = RefPackets(seq)
    io.write(ref_lenenc_int(len(schema.column_schemas)))
    for c in schema.column_schemas:
        t = mysql.T_VAR_STRING if binary else mysql._mysql_type(c.dtype)
        charset = 45 if t == mysql.T_VAR_STRING else 63
        io.write(b"".join(ref_lenenc_str(s) for s in (
            b"def", b"", b"", b"", c.name.encode(), c.name.encode()))
            + b"\x0c" + struct.pack("<HIBHB", charset, 1024, t, 0, 31)
            + b"\x00\x00")
    eof = b"\xfe" + struct.pack("<HH", 0, 2)
    io.write(eof)
    dtypes = [c.dtype for c in schema.column_schemas]
    for row in ref_rows(out.batches):
        cells = [ref_mysql_cell(v, d) for v, d in zip(row, dtypes)]
        if binary:
            bitmap = bytearray((len(cells) + 9) // 8)
            payload = b""
            for i, v in enumerate(cells):
                if v is None:
                    bitmap[(i + 2) // 8] |= 1 << ((i + 2) % 8)
                else:
                    payload += ref_lenenc_str(str(v).encode())
            io.write(b"\x00" + bytes(bitmap) + payload)
        else:
            io.write(b"".join(
                b"\xfb" if v is None else ref_lenenc_str(str(v).encode())
                for v in cells))
    io.write(eof)
    return io.stream


def ref_pg_stream(out):
    def message(tag, body):
        return tag + struct.pack("!I", len(body) + 4) + body

    schema = out.batches[0].schema
    body = struct.pack("!H", len(schema.column_schemas))
    for c in schema.column_schemas:
        body += c.name.encode() + b"\x00" + struct.pack(
            "!IHIhih", 0, 0, postgres._pg_oid(c.dtype), -1, -1, 0)
    stream = message(b"T", body)
    dtypes = [c.dtype for c in schema.column_schemas]
    n = 0
    for row in ref_rows(out.batches):
        body = struct.pack("!H", len(row))
        for v, d in zip(row, dtypes):
            if v is None:
                body += struct.pack("!i", -1)
                continue
            if d.is_timestamp:
                text = Timestamp(v, d.time_unit).to_datetime().strftime(
                    "%Y-%m-%d %H:%M:%S.%f").encode()
            elif isinstance(v, bool):
                text = b"t" if v else b"f"
            else:
                text = str(v).encode()
            body += struct.pack("!i", len(text)) + text
        stream += message(b"D", body)
        n += 1
    return stream + message(b"C", f"SELECT {n}".encode() + b"\x00")


# ---------------------------------------------------------------------------
# the cases
# ---------------------------------------------------------------------------

_FLOATS = [0.0, -0.0, 1.5, -2.25, 0.1, 1 / 3, 1e16, 1e-5, 123456789.123456789,
           5e-324, 1.7976931348623157e308, math.nan, math.inf, -math.inf,
           97.35199999999999, 1e22, 2.5e-7]
_INT_TYPES = [dt.INT8, dt.INT16, dt.INT32, dt.INT64,
              dt.UINT8, dt.UINT16, dt.UINT32, dt.UINT64]
_TS_TYPES = [dt.TIMESTAMP_SECOND, dt.TIMESTAMP_MILLISECOND,
             dt.TIMESTAMP_MICROSECOND, dt.TIMESTAMP_NANOSECOND]
#: seconds: epoch, just before it, TSBS's 2016, 1900, 1000-01-01, 9999's end
_SECONDS = [0, -1, 1451606400, -2208988800, -30610224000, 253402300799]


def _batch(*columns):
    """columns: (name, dtype, data[, validity])."""
    schema = Schema([ColumnSchema(c[0], c[1]) for c in columns])
    return RecordBatch(schema, [Vector(c[1], *c[2:]) for c in columns])


def _objects(values):
    arr = np.empty(len(values), dtype=object)
    arr[:] = values
    return arr


def _alternate(n):
    return np.arange(n) % 2 == 0


def _case_integers():
    cols = []
    for t in _INT_TYPES:
        info = np.iinfo(t.np_dtype)
        cols.append((t.name, t, np.array([info.min, info.max, 0, 1, 7],
                                         dtype=t.np_dtype)))
    return [_batch(*cols)]


def _case_floats():
    f64 = np.array(_FLOATS, dtype=np.float64)
    with np.errstate(over="ignore", under="ignore"):
        f32 = f64.astype(np.float32)
    return [_batch(("f64", dt.FLOAT64, f64), ("f32", dt.FLOAT32, f32))]


def _case_bool():
    return [_batch(("b", dt.BOOLEAN, np.array([True, False, True])),
                   ("n", dt.BOOLEAN, np.array([True, False, True]),
                    np.array([False, True, True])))]


def _case_strings():
    return [_batch(("s", dt.STRING, _objects(
        ["", "host_0", "héllo wörld ☃ 日本語", "q'uo\"te\\ \n\t\x00", "x" * 250,
         "y" * 251, "ü" * 200, "z" * 65535, "w" * 65536, "é" * 40000])))]


def _case_timestamps():
    cols = []
    for t in _TS_TYPES:
        factor = t.time_unit.factor
        ticks = [s * factor for s in _SECONDS]
        # the last tick of a second, fractions that floor below zero, and
        # every digit the unit has
        ticks += [s * factor + factor - 1 for s in _SECONDS[:4]]
        ticks += [-1, -999, -1001,
                  1451606400 * factor + 123456789 * factor // 10 ** 9]
        # nanoseconds of int64 reach from 1677 to 2262 only
        ticks = [x if -2 ** 63 <= x < 2 ** 63 else x % 10 ** 18
                 for x in ticks]
        cols.append((t.name, t, np.array(ticks, dtype=np.int64)))
    return [_batch(*cols)]


def _case_nulls():
    n = 9
    keep = _alternate(n)
    return [_batch(
        ("i", dt.INT64, np.arange(n, dtype=np.int64) - 4, keep),
        ("u", dt.UINT8, np.arange(n, dtype=np.uint8), ~keep),
        ("f", dt.FLOAT64, np.array(_FLOATS[:n]), keep),
        ("nan", dt.FLOAT64, np.full(n, math.nan), ~keep),
        ("b", dt.BOOLEAN, keep.copy(), ~keep),
        ("s", dt.STRING, _objects([f"s{i}" for i in range(n)]), keep),
        ("long", dt.STRING, _objects(["k" * 300] * n), ~keep),
        ("t", dt.TIMESTAMP_MILLISECOND,
         np.arange(n, dtype=np.int64) * 86_400_123 - 10 ** 12, keep),
        ("d", dt.DATE, np.arange(n, dtype=np.int32) - 3, ~keep))]


def _case_all_null():
    n = 5
    cols = []
    for t in (dt.INT32, dt.FLOAT64, dt.BOOLEAN, dt.STRING,
              dt.TIMESTAMP_NANOSECOND):
        cols.append((f"nulls_{t.name}", t, *null_column(t, n)))
        v = Vector.nulls(n, t)
        cols.append((f"filled_{t.name}", t, v.data, v.validity))
    # None in the data of a column that says it has no NULL
    cols.append(("none", dt.STRING, _objects(["a", None, "", None, "b"])))
    return [_batch(*cols)]


def _case_empty():
    return [_batch(("i", dt.INT64, np.array([], dtype=np.int64)),
                   ("f", dt.FLOAT64, np.array([], dtype=np.float64)),
                   ("s", dt.STRING, _objects([])),
                   ("t", dt.TIMESTAMP_SECOND, np.array([], dtype=np.int64)))]


def _cpu_like(n, first=0, fields=3):
    """Rows shaped like a TSBS group-by answer: tag, hour, averages."""
    i = np.arange(first, first + n)
    rng = np.random.default_rng(first + n)
    cols = [("hostname", dt.STRING, _objects([f"host_{k}" for k in i])),
            ("hour", dt.TIMESTAMP_MILLISECOND,
             1451606400000 + 3600000 * (i % 12).astype(np.int64))]
    for k in range(fields):
        cols.append((f"avg{k}", dt.FLOAT64, rng.random(n) * 100))
    return _batch(*cols)


def _case_several_batches():
    empty = _cpu_like(0)
    return [_cpu_like(3), empty, _cpu_like(300, 3), _cpu_like(1, 303), empty]


def _case_sequence_wrap():
    return [_cpu_like(700, fields=1)]


def _case_cell_path():
    """What no column-wise path takes: the per-cell code, same text."""
    return [_batch(
        ("mixed", dt.STRING, _objects(["a", np.str_("b"), "c"])),
        ("year500", dt.TIMESTAMP_SECOND,
         np.array([-46388678400, 0, 1], dtype=np.int64)),
        ("year999", dt.TIMESTAMP_MILLISECOND,
         np.array([-30610224000001, 0, -1], dtype=np.int64),
         np.array([True, True, False])),
        ("i", dt.INT64, np.array([1, 2, 3])))]


def _case_binary():
    # str(bytes) on the text wires; no JSON at all
    return [_batch(("bin", dt.BINARY, _objects([b"ab", b"", b"\x00\xff"])),
                   ("i", dt.INT64, np.array([1, 2, 3])))]


def _case_object_numbers():
    # JSON only holds these; the text wires print str() of each
    return [_batch(("o", dt.FLOAT64, _objects([1.5, math.nan, 3])),
                   ("p", dt.BOOLEAN, _objects([True, False, True])))]


def _step(x, towards):
    return float(np.nextafter(x, towards))


#: what Arrow's cast prints otherwise than `repr` (integral values, the
#: signed zeros, under 1e-4, from 1e10 up to 1e16), the neighbours of
#: both edges of the band in which they print alike, and the ends of the
#: doubles
_FLOAT_EDGES = [
    100.0, -100.0, 1.0, 7.0, 0.0, -0.0, 2.0 ** 53, 2.0 ** 53 + 2, 1e15,
    -1e15, 1e16, -1e16, 999999999999999.9, 1e15 + 0.5, 1e22, 1e23, 1e100,
    1e-4, _step(1e-4, 0), _step(1e-4, 1), 9.999e-05, 1.5e-07, 1e-5, 1e-10,
    1e10, _step(1e10, 0), _step(1e10, 1e11), 15000000000.5, 9999999999.5,
    123456789012.25, 5e-324, -5e-324, 2.2250738585072014e-308,
    1.7976931348623157e308, -1.7976931348623157e308, math.inf, -math.inf,
    math.nan, 0.1, 0.30000000000000004, 1 / 3, 2 / 3, 97.35199999999999]


def _case_float_edges():
    f64 = np.array(_FLOAT_EDGES + [-v for v in _FLOAT_EDGES])
    return [_batch(("f64", dt.FLOAT64, f64))]


def _case_float_edges_nulls():
    """NULLs beside them, over data of every kind (a NULL's slot holds
    whatever the engine left there)."""
    f64 = np.array(_FLOAT_EDGES)
    n = len(f64)
    return [_batch(("even", dt.FLOAT64, f64, _alternate(n)),
                   ("odd", dt.FLOAT64, f64, ~_alternate(n)),
                   ("third", dt.FLOAT64, f64[::-1].copy(),
                    np.arange(n) % 3 != 0),
                   ("no_nan", dt.FLOAT64, np.where(f64 != f64, 1.5, f64),
                    np.arange(n) % 5 != 0))]


def _case_f32_born():
    """Aggregates come off the device as f32 and are widened: 16-17
    digits each, which is most of what a dashboard reads. And a FLOAT32
    column, whose text is its double's."""
    rng = np.random.default_rng(38)
    with np.errstate(over="ignore"):
        f32 = np.concatenate([
            rng.random(300) * 100, rng.random(100), rng.random(100) * 1e6,
            10.0 ** rng.uniform(-7, 12, 200), [0.1, 100.0, 1e-4, 1e10, 3e38,
                                               1e-45, math.inf, math.nan],
        ]).astype(np.float32)
    return [_batch(("widened", dt.FLOAT64, f32.astype(np.float64)),
                   ("f32", dt.FLOAT32, f32),
                   ("f32_nulls", dt.FLOAT32, f32, _alternate(len(f32))))]


#: strings `json.dumps` prints as they are between two quotes
_PLAIN_STRINGS = ["", "host_0", "a b", "~!#$%&'()*+,-./:;<=>?@[]^_`{|}", "x" * 300]
#: and strings it escapes: a quote, a backslash, control bytes, DEL, and
#: what lies beyond ASCII
_ESCAPED_STRINGS = ['"', "\\", "\n", "\x00", "\t\r\x1f", "\x7f", "é", "日本語", "☃",
                    "\U0001f600", 'say "hi"', "C:\\dir\\file", "a\"b\\c\nd\x00"]


def _case_strings_plain():
    values = _PLAIN_STRINGS * 3
    n = len(values)
    return [_batch(("s", dt.STRING, _objects(values)),
                   ("nulls", dt.STRING, _objects(values), _alternate(n)),
                   ("none", dt.STRING, _objects(
                       [None if i % 4 == 0 else v
                        for i, v in enumerate(values)])))]


def _case_strings_escaped():
    values = _ESCAPED_STRINGS + _PLAIN_STRINGS
    n = len(values)
    return [_batch(("s", dt.STRING, _objects(values)),
                   ("nulls", dt.STRING, _objects(values), ~_alternate(n)),
                   ("none", dt.STRING, _objects(
                       [None if i % 3 == 0 else v
                        for i, v in enumerate(values)])),
                   # one escaped string among plain ones: the whole
                   # column goes through `json.dumps`
                   ("one", dt.STRING, _objects(["plain"] * (n - 1) + ['"'])),
                   ("i", dt.INT64, np.arange(n, dtype=np.int64)))]


def _case_dashboard():
    """A fleet panel's answer past the crossover, with NULLs and a NaN in
    it, in two batches."""
    first, second = _cpu_like(200, fields=10), _cpu_like(137, 200, fields=10)
    second.columns[3].validity = _alternate(137)
    second.columns[4].data[::7] = math.nan
    return [first, second]


CASES = {
    "integers": _case_integers,
    "floats": _case_floats,
    "bool": _case_bool,
    "strings": _case_strings,
    "timestamps": _case_timestamps,
    "nulls": _case_nulls,
    "all_null": _case_all_null,
    "empty": _case_empty,
    "several_batches": _case_several_batches,
    "sequence_wrap": _case_sequence_wrap,
    "cell_path": _case_cell_path,
    "binary": _case_binary,
    "object_numbers": _case_object_numbers,
    "float_edges": _case_float_edges,
    "float_edges_nulls": _case_float_edges_nulls,
    "f32_born": _case_f32_born,
    "strings_plain": _case_strings_plain,
    "strings_escaped": _case_strings_escaped,
    "dashboard": _case_dashboard,
}
#: cases of which some column takes the per-cell path
CELL_PATH_CASES = {"cell_path", "binary", "object_numbers"}
#: those of which a column takes it in JSON: a year is a number there
JSON_CELL_PATH_CASES = {"cell_path", "object_numbers"}


class RecordingSocket:
    def __init__(self):
        self.sends = []

    def sendall(self, data):
        self.sends.append(bytes(data))

    @property
    def stream(self):
        return b"".join(self.sends)


@pytest.fixture(params=[(columnar.SLAB_BYTES, columnar.CHUNK_ROWS),
                        (512, 7)], ids=["slab4MiB", "slab512B"])
def slabs(request, monkeypatch):
    """The real slab and chunk, and ones so small that every case
    crosses many of both."""
    slab, chunk = request.param
    monkeypatch.setattr(columnar, "SLAB_BYTES", slab)
    monkeypatch.setattr(columnar, "CHUNK_ROWS", chunk)
    return slab


@pytest.fixture(params=[1, 1 << 62], ids=["compiled", "columnar"])
def route(request, monkeypatch):
    """Every chunk on the compiled route whatever its length, then none:
    the cases are a few rows each, and both routes print them."""
    monkeypatch.setattr(columnar, "COMPILED_MIN_ROWS", request.param)
    return columnar.COMPILED if request.param == 1 else columnar.COLUMNAR


def _output(case):
    return Output.record_batches(CASES[case]())


# ---------------------------------------------------------------------------
# byte for byte
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", CASES)
def test_http_body_is_the_row_by_row_body(case, route, monkeypatch):
    monkeypatch.setattr(http.time, "perf_counter", lambda: 0.0)
    out = _output(case)
    if case == "binary":
        # bytes are no JSON, on either side of this PR
        for body in (ref_http_body, lambda o: http.sql_response([o], 0.0)):
            with pytest.raises(TypeError):
                body(out)
        return
    response = http.sql_response([out], 0.0)
    assert response.body == ref_http_body(out)
    pieces, routes = columnar.json_rows_text(out.batches)
    assert b"".join(pieces) == json.dumps(
        [[None if v != v else v for v in r]
         for r in ref_rows(out.batches)]).encode()
    assert sum(routes.values()) == out.num_rows
    if case in JSON_CELL_PATH_CASES:
        assert set(routes) == {columnar.CELL}
    elif out.num_rows:
        assert set(routes) == {route}


@pytest.mark.parametrize("binary", [False, True], ids=["text", "binary"])
@pytest.mark.parametrize("case", CASES)
def test_mysql_stream_is_the_row_by_row_stream(case, binary, slabs, route):
    out = _output(case)
    sock = RecordingSocket()
    conn = mysql._Connection(None, sock, 1)
    conn.io.seq = 1                      # a COM_QUERY's answer starts at 1
    routes = conn._send_output(out, binary, conn.io)
    want = ref_mysql_stream(out, binary, 1)
    assert sock.stream == want
    assert conn.io.bytes_out == len(want)
    assert (columnar.CELL in routes) == (case in CELL_PATH_CASES)
    assert sum(routes.values()) == out.num_rows
    # the discarded encoding of an EXPLAIN ANALYZE'd result: same count
    dropped = mysql.PacketIO(None)
    dropped.seq = 1
    conn._send_output(out, binary, dropped)
    assert dropped.bytes_out == len(want)
    assert dropped.seq == conn.io.seq
    assert len(sock.sends) <= len(want) // slabs + 1


@pytest.mark.parametrize("case", CASES)
def test_postgres_stream_is_the_row_by_row_stream(case, slabs, route):
    out = _output(case)
    sock = RecordingSocket()
    conn = postgres._PgConnection(None, sock, 1)
    conn.send_result("SELECT 1", out)
    want = ref_pg_stream(out)
    assert sock.stream == want
    assert conn.io.bytes_out == len(want)
    dropped = postgres._MessageIO(None)
    with dropped.slab():
        conn.send_row_description(out.batches[0].schema, dropped)
        conn.send_rows(out.batches, dropped)
        conn.send_complete("SELECT 1", out, dropped)
    assert dropped.bytes_out == len(want)
    assert len(sock.sends) <= len(want) // slabs + 1


@pytest.mark.parametrize("length", [
    0xFFFFFF - 4,       # payload of exactly 0xFFFFFF: an empty last packet
    0xFFFFFF,           # split in two
    (1 << 24) + 3,      # the 9-byte lenenc prefix
], ids=["exact", "split", "lenenc8"])
@pytest.mark.parametrize("binary", [False, True], ids=["text", "binary"])
def test_mysql_payload_over_one_packet(length, binary):
    """A row of 16 MiB is split at 0xFFFFFF with its own sequence
    numbers, between ordinary rows."""
    if binary:
        length -= 2                      # the 0x00 and the NULL bitmap
    out = Output.record_batches([_batch(
        ("s", dt.STRING, _objects(["before", "x" * length, "after"])))])
    sock = RecordingSocket()
    conn = mysql._Connection(None, sock, 1)
    conn.io.seq = 1
    conn._send_output(out, binary, conn.io)
    want = ref_mysql_stream(out, binary, 1)
    assert sock.stream == want
    dropped = mysql.PacketIO(None)
    conn._send_output(out, binary, dropped)
    assert dropped.bytes_out == len(want)


def test_federated_answers_keep_their_bytes():
    """`SELECT @@var` and friends: literal rows through the same framing."""
    for sql, binary in (("select @@version_comment", False),
                        ("select @@no_such_variable", False),
                        ("select version()", True),
                        ("show variables", False)):
        sock = RecordingSocket()
        conn = mysql._Connection(None, sock, 1)
        conn.io.seq = 1
        conn.handle_query(sql, binary=binary)
        names, rows = mysql.federated_answer(sql, conn.ctx)
        schema = Schema([ColumnSchema(n, dt.STRING) for n in names])
        batch = RecordBatch(schema, [
            Vector.from_pylist([r[i] for r in rows], dt.STRING)
            for i in range(len(names))])
        assert sock.stream == ref_mysql_stream(
            Output.record_batches([batch]), binary, 1)


# ---------------------------------------------------------------------------
# counts, not times
# ---------------------------------------------------------------------------

class _Instance:
    def __init__(self, out):
        self.out = out

    def do_query(self, sql, ctx):
        return [self.out]


class _Server:
    def __init__(self, out):
        self.instance = _Instance(out)


class _SpanSink:
    def __init__(self):
        self.spans = []

    def on_span_end(self, span, elapsed_ms, status):
        self.spans.append(span)


@pytest.fixture
def render_spans():
    sink = _SpanSink()
    before = telemetry._SPAN_SINK[0]
    telemetry.set_span_sink(sink)
    yield lambda: [s for s in sink.spans if s["name"] == "render"]
    telemetry.set_span_sink(before)


def _render_rows_total(protocol, path):
    for family in telemetry.collect_families():
        for s in family.samples:
            if s.name == "greptime_render_rows_total" and s.labels == {
                    "protocol": protocol, "path": path}:
                return s.value
    return 0.0


@pytest.mark.parametrize("wire", ["mysql", "postgres"])
def test_one_send_a_slab_and_the_span_counts_what_was_sent(
        wire, render_spans):
    out = Output.record_batches([_cpu_like(10_000, fields=10)])
    sock = RecordingSocket()
    before = _render_rows_total(wire, "compiled")
    if wire == "mysql":
        mysql._Connection(_Server(out), sock, 1).handle_query("SELECT 1")
    else:
        postgres._PgConnection(_Server(out), sock, 1).send_result(
            "SELECT 1", out)
    sent = len(sock.stream)
    assert sent > 10_000 * 12 * 4
    assert len(sock.sends) <= math.ceil(sent / columnar.SLAB_BYTES) + 1
    (span,) = render_spans()
    assert span["attrs"]["bytes"] == sent
    assert span["attrs"]["rows"] == 10_000
    # its ten float columns were printed by Arrow's cast
    assert span["attrs"]["path"] == "compiled"
    assert _render_rows_total(wire, "compiled") - before == 10_000


def test_a_fallback_shows_on_the_span_and_the_counter(render_spans):
    out = _output("object_numbers")
    before = _render_rows_total("http", "cell")
    http.sql_response([out], 0.0)
    span = render_spans()[-1]
    assert span["attrs"]["path"] == "cell"
    assert _render_rows_total("http", "cell") - before == out.num_rows


def _http_routes(out):
    """(body, the `render` span's path, rows `greptime_render_rows_total`
    gained by path) of one answer over HTTP."""
    sink = _SpanSink()
    held = telemetry._SPAN_SINK[0]
    telemetry.set_span_sink(sink)
    paths = (columnar.COMPILED, columnar.COLUMNAR, columnar.CELL)
    before = {p: _render_rows_total("http", p) for p in paths}
    try:
        body = http.sql_response([out], 0.0).body
    finally:
        telemetry.set_span_sink(held)
    gained = {p: _render_rows_total("http", p) - before[p] for p in paths}
    spans = [s for s in sink.spans if s["name"] == "render"]
    return body, [s["attrs"]["path"] for s in spans], {
        p: n for p, n in gained.items() if n}


def test_a_fleet_panel_takes_the_compiled_route_and_says_so(monkeypatch):
    """48,000 x 12, double-groupby-all's answer: on the span, on the
    counter, and on the `render` row under an EXPLAIN ANALYZE of it."""
    monkeypatch.setattr(http.time, "perf_counter", lambda: 0.0)
    out = Output.record_batches([_cpu_like(48_000, fields=10)])
    body, paths, gained = _http_routes(out)
    assert paths == ["compiled"]
    assert gained == {"compiled": 48_000}
    assert body == ref_http_body(out)

    from greptimedb_tpu.common.exec_stats import ExecStats
    from greptimedb_tpu.query.engine import stage_rows_output
    explained = stage_rows_output(ExecStats(), ["plan"], out)
    body, paths, gained = _http_routes(explained)
    assert paths[0] == "compiled"          # the analysed result, discarded
    rows = json.loads(body)["output"][0]["records"]["rows"]
    stage, nrows, _, _, detail = rows[-1]
    assert (stage, nrows) == ("render", 48_000)
    assert "protocol=http" in detail and "path=compiled" in detail
    assert gained["compiled"] == 48_000


def test_a_small_answer_keeps_the_columnar_route():
    """A host's panel: 12 rows. A dozen Arrow calls a column cost more
    than `tolist` and `json.dumps` do for them."""
    out = Output.record_batches([_cpu_like(12, fields=10)])
    _, paths, gained = _http_routes(out)
    assert paths == ["columnar"] and gained == {"columnar": 12}
    assert 12 < columnar.COMPILED_MIN_ROWS <= 1000
    at = columnar.COMPILED_MIN_ROWS
    for n, want in ((at - 1, "columnar"), (at, "compiled")):
        _, paths, _ = _http_routes(
            Output.record_batches([_cpu_like(n, fields=2)]))
        assert paths == [want]
    for send in ("mysql", "postgres"):
        for n, want in ((at - 1, "columnar"), (at, "compiled")):
            chunks = columnar.text_chunks(
                [_cpu_like(n, fields=2)],
                {"mysql": columnar.MYSQL_TEXT,
                 "postgres": columnar.POSTGRES_TEXT}[send])
            assert [route for _, _, route in chunks] == [want]


def test_a_chunk_boundary_leaves_the_body_as_it_was(monkeypatch):
    """CHUNK_ROWS + 1 rows in two batches against the same rows in one:
    three chunks there, two here, one text."""
    monkeypatch.setattr(http.time, "perf_counter", lambda: 0.0)
    n = columnar.CHUNK_ROWS + 1
    whole = _cpu_like(n, fields=3)
    cut = 5_000
    halves = [whole.slice(0, cut), whole.slice(cut, n - cut)]
    one = http.sql_response([Output.record_batches([whole])], 0.0).body
    two = http.sql_response([Output.record_batches(halves)], 0.0).body
    assert one == two == ref_http_body(Output.record_batches([whole]))
    pieces, routes = columnar.json_rows_text([whole])
    # "[", a chunk between its own "[" and "]", ", ", the row that is
    # left, "]"
    assert len(pieces) == 7 and routes == {"compiled": n - 1, "columnar": 1}


def test_several_results_in_one_envelope(monkeypatch):
    """`rows` are spliced where each result's belong, a column named
    `"rows": null` or not."""
    monkeypatch.setattr(http.time, "perf_counter", lambda: 0.0)
    tricky = _batch(('"rows": null', dt.STRING, _objects(['"rows": null'])),
                    ("rows", dt.INT64, np.array([1])))
    outs = [Output.record_batches([tricky]), Output.rows(3),
            Output.record_batches([_cpu_like(100)]),
            Output.record_batches([])]
    want = json.loads(ref_http_body(outs[0]))
    want["output"] += [{"affectedrows": 3}]
    want["output"] += json.loads(ref_http_body(outs[2]))["output"]
    want["output"] += [{"records": {"schema": {"column_schemas": []},
                                    "rows": []}}]
    assert http.sql_response(outs, 0.0).body == json.dumps(want).encode()


def test_a_chunk_too_long_for_arrow_takes_the_columnar_route(monkeypatch):
    """Past 2 GiB of text in one chunk Arrow's join refuses (its strings
    have 32-bit offsets); the rows then go through `json.dumps`."""
    def refuse(*args, **kwargs):
        raise columnar.pa.ArrowCapacityError(
            "array cannot contain more than 2147483646 bytes")
    monkeypatch.setattr(http.time, "perf_counter", lambda: 0.0)
    monkeypatch.setattr(columnar.pc, "binary_join_element_wise", refuse)
    out = Output.record_batches([_cpu_like(300, fields=2)])
    assert out.num_rows >= columnar.COMPILED_MIN_ROWS
    body, paths, gained = _http_routes(out)
    assert body == ref_http_body(out)
    assert paths == ["columnar"] and gained == {"columnar": 300}


def test_the_process_is_busy_while_a_result_is_encoded(monkeypatch):
    """The encoders give the interpreter lock up at every Arrow call, as
    the engine's numpy calls do: a parser beside them has to give way
    (`admission.give_way` asks `busy()`) until the body is whole."""
    from greptimedb_tpu.common import process_list
    seen = []
    whole = columnar.json_rows_text

    def watched(batches):
        seen.append(process_list.REGISTRY.busy())
        return whole(batches)
    monkeypatch.setattr(http, "json_rows_text", watched)
    assert not process_list.REGISTRY.busy()
    http.sql_response([Output.record_batches([_cpu_like(300)])], 0.0)
    assert seen == [True] and not process_list.REGISTRY.busy()
    with pytest.raises(TypeError):
        http.sql_response([_output("binary")], 0.0)
    assert not process_list.REGISTRY.busy()


def test_a_lone_surrogate_takes_the_columnar_route(monkeypatch):
    """`json.dumps` escapes it; Arrow's strings are UTF-8, which has no
    bytes for it."""
    monkeypatch.setattr(http.time, "perf_counter", lambda: 0.0)
    out = Output.record_batches([_batch(
        ("s", dt.STRING, _objects(["ok", "\ud800x"] * 150)),
        ("f", dt.FLOAT64, np.arange(300) / 7))])
    assert out.num_rows >= columnar.COMPILED_MIN_ROWS
    body, paths, gained = _http_routes(out)
    assert body == ref_http_body(out)
    assert paths == ["columnar"] and gained == {"columnar": 300}


# ---------------------------------------------------------------------------
# the floats' text: Arrow's cast inside the band, `repr` outside it
# ---------------------------------------------------------------------------

def _random_doubles(seed):
    """Every exponent a double has (random bit patterns, NaNs and
    infinities among them), magnitudes of every decade from 1e-6 to 1e18,
    and integral values."""
    rng = np.random.default_rng(seed)
    bits = rng.integers(0, 2 ** 64, 20_000, dtype=np.uint64).view(np.float64)
    decades = 10.0 ** rng.uniform(-6, 18, 24_000) \
        * rng.choice([-1.0, 1.0], 24_000)
    whole = np.rint(10.0 ** rng.uniform(0, 17, 4_000))
    return np.concatenate([bits, decades, whole])


@pytest.mark.parametrize("wire", ["http", "mysql", "postgres"])
def test_random_doubles_print_as_repr_prints_them(wire, monkeypatch):
    values = _random_doubles({"http": 381, "mysql": 382, "postgres": 383}[wire])
    n = 6_000
    out = Output.record_batches([_batch(*[
        (f"f{k}", dt.FLOAT64, values[k * n:(k + 1) * n].copy())
        for k in range(len(values) // n)])])
    if wire == "http":
        monkeypatch.setattr(http.time, "perf_counter", lambda: 0.0)
        _, paths, _ = _http_routes(out)
        assert paths == ["compiled"]
        assert http.sql_response([out], 0.0).body == ref_http_body(out)
    elif wire == "mysql":
        sock = RecordingSocket()
        conn = mysql._Connection(None, sock, 1)
        conn.io.seq = 1
        assert conn._send_output(out, False, conn.io) == {"compiled": n}
        assert sock.stream == ref_mysql_stream(out, False, 1)
    else:
        sock = RecordingSocket()
        postgres._PgConnection(None, sock, 1).send_result("SELECT 1", out)
        assert sock.stream == ref_pg_stream(out)


def test_the_band_in_which_the_cast_prints_what_repr_prints():
    """`float_texts` is `repr` everywhere; inside the band the cast alone
    makes it, and each edge is where the two part."""
    values = _random_doubles(384)
    assert columnar.float_texts(values).to_pylist() == list(
        map(repr, values.tolist()))
    cast = columnar.pc.cast(columnar.pa.array(values),
                            columnar.pa.string()).to_pylist()
    inside = columnar.cast_prints_repr(values)
    assert 10_000 < inside.sum() < len(values) - 10_000
    assert [t for t, i in zip(cast, inside) if i] == [
        repr(v) for v, i in zip(values.tolist(), inside) if i]

    low, high = columnar._CAST_LOW, columnar._CAST_HIGH
    just_inside = np.array([low, _step(low, 1), _step(high, 0), high - 0.5,
                            -low, -_step(high, 0), 0.5, 99.99])
    just_outside = np.array([_step(low, 0), high + 0.5, _step(high, 2 * high),
                             -_step(low, 0), -(high + 0.5), 100.0, -0.0,
                             math.nan, math.inf])
    assert columnar.cast_prints_repr(just_inside).all()
    assert not columnar.cast_prints_repr(just_outside).any()
    # and the band could be no wider: just outside it the cast prints
    # something else
    cast = columnar.pc.cast(columnar.pa.array(just_outside[:7]),
                            columnar.pa.string()).to_pylist()
    assert all(t != repr(v) for t, v in zip(cast, just_outside.tolist()))

    # a NULL's slot is nobody's to read: whatever is there, the others
    # print as they would
    texts = columnar.float_texts(
        np.array([100.0, 2.5, 3.0, math.nan]),
        unread=np.array([True, False, False, True])).to_pylist()
    assert texts[1:3] == ["2.5", "3.0"]


def test_to_pylist_keeps_its_types():
    """`Vector.to_pylist` loops in C for every non-object dtype and hands
    out what the per-value loop did."""
    for case in CASES:
        for batch in CASES[case]():
            for vec in batch.columns:
                got, want = vec.to_pylist(), ref_pylist(vec)
                assert [type(v) for v in got] == [type(v) for v in want]
                assert all(a == b or (a != a and b != b)
                           for a, b in zip(got, want))
