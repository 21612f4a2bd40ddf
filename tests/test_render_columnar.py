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
}
#: cases of which some column takes the per-cell path
CELL_PATH_CASES = {"cell_path", "binary", "object_numbers"}


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


def _output(case):
    return Output.record_batches(CASES[case]())


# ---------------------------------------------------------------------------
# byte for byte
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", CASES)
def test_http_body_is_the_row_by_row_body(case, monkeypatch):
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
    rows, cell_rows = columnar.json_rows(out.batches)
    assert (cell_rows > 0) == (case in CELL_PATH_CASES)
    for row in rows:
        for v in row:
            assert type(v) in (int, float, bool, str, bytes, type(None))


@pytest.mark.parametrize("binary", [False, True], ids=["text", "binary"])
@pytest.mark.parametrize("case", CASES)
def test_mysql_stream_is_the_row_by_row_stream(case, binary, slabs):
    out = _output(case)
    sock = RecordingSocket()
    conn = mysql._Connection(None, sock, 1)
    conn.io.seq = 1                      # a COM_QUERY's answer starts at 1
    cell_rows = conn._send_output(out, binary, conn.io)
    want = ref_mysql_stream(out, binary, 1)
    assert sock.stream == want
    assert conn.io.bytes_out == len(want)
    assert (cell_rows > 0) == (case in CELL_PATH_CASES)
    # the discarded encoding of an EXPLAIN ANALYZE'd result: same count
    dropped = mysql.PacketIO(None)
    dropped.seq = 1
    conn._send_output(out, binary, dropped)
    assert dropped.bytes_out == len(want)
    assert dropped.seq == conn.io.seq
    assert len(sock.sends) <= len(want) // slabs + 1


@pytest.mark.parametrize("case", CASES)
def test_postgres_stream_is_the_row_by_row_stream(case, slabs):
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
    before = _render_rows_total(wire, "columnar")
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
    assert span["attrs"]["path"] == "columnar"
    assert _render_rows_total(wire, "columnar") - before == 10_000


def test_a_fallback_shows_on_the_span_and_the_counter(render_spans):
    out = _output("object_numbers")
    before = _render_rows_total("http", "cell")
    http.sql_response([out], 0.0)
    span = render_spans()[-1]
    assert span["attrs"]["path"] == "cell"
    assert _render_rows_total("http", "cell") - before == out.num_rows


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
