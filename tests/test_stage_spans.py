"""Every millisecond of a statement under a span of the program's own
(ISSUE 24): EXPLAIN ANALYZE rows with wall-clock starts over HTTP and
MySQL, the `render` row, the profiler bridge, the row-insert timers.
And what a request does outside its rows (ISSUE 39): the HTTP server's
hand-offs as the rows `request.read` / `.queue` / `.resume`, the thread's
CPU time on every row.
"""

import json
import re
import subprocess
import sys
import time
import urllib.parse
import urllib.request

import pytest

from greptimedb_tpu.datanode.instance import DatanodeInstance, DatanodeOptions
from greptimedb_tpu.frontend.instance import FrontendInstance
from greptimedb_tpu.servers.http import HttpServer
from greptimedb_tpu.servers.mysql import MysqlServer

from test_mysql import MiniMysqlClient
from test_postgres import MiniPgClient

QUERY = ("SELECT host, date_bin(INTERVAL '1 minute', ts) AS minute, "
         "avg(usage), max(usage) FROM span_cpu GROUP BY host, minute "
         "ORDER BY host, minute")
T0 = re.compile(r"t0_ns=(\d+)$")
CPU = re.compile(r"(?:^|, )cpu_ms=(\d+\.\d{3})(?:, |$)")
#: the request's frame over HTTP, outside `total`
BEFORE, AFTER = ["request.read", "request.queue", "parse"], \
    ["total", "request.resume", "render"]
#: timed before the statement was known to be analysed: no CPU clock read
NO_CPU = {"request.read", "request.queue", "request.resume", "parse"}
#: rows that may carry a time without being a span of this statement's
#: thread of execution: the dispatch decision, the whole
UNTIMED = {"dispatch", "total"}
OUTSIDE_TOTAL = {"parse", "render"}


class CountingSocket:
    """A client socket that counts what it receives."""

    def __init__(self, sock):
        self.sock, self.received = sock, 0

    def recv(self, n):
        data = self.sock.recv(n)
        self.received += len(data)
        return data

    def __getattr__(self, name):
        return getattr(self.sock, name)


class Wires:
    """One frontend behind an HTTP and a MySQL server."""

    def __init__(self, data_home: str):
        self.dn = DatanodeInstance(DatanodeOptions(
            data_home=data_home, register_numbers_table=False))
        self.dn.start()
        self.fe = FrontendInstance(self.dn)
        self.fe.start()
        self.http = HttpServer(self.fe, addr="127.0.0.1:0")
        self.http.start()
        self.mysql = MysqlServer(self.fe)
        self.mysql.serve_in_background()
        self.mysql_client = MiniMysqlClient(self.mysql.port)
        self.mysql_sock = self.mysql_client.io.sock = CountingSocket(
            self.mysql_client.sock)

    def close(self):
        self.mysql_client.close()
        self.mysql.shutdown()
        self.http.shutdown()
        self.fe.shutdown()

    def http_raw(self, path: str, params=None, body=None) -> bytes:
        url = f"http://127.0.0.1:{self.http.port}{path}"
        if params:
            url += "?" + urllib.parse.urlencode(params)
        req = urllib.request.Request(
            url, data=body, method="POST" if body is not None else "GET")
        with urllib.request.urlopen(req, timeout=60) as r:
            return r.read()

    def sql_raw(self, via: str, sql: str):
        """-> (rows, bytes of the answer as the wire carried it)."""
        # the latency-adaptive dispatch floor is process-global: pin it
        # before every dispatch-sensitive statement
        self.fe.do_query("SET tpu_dispatch_min_rows = 1")
        if via == "http":
            raw = self.http_raw("/v1/sql", {"sql": sql})
            return json.loads(raw)["output"][-1]["records"]["rows"], len(raw)
        before = self.mysql_sock.received
        _names, rows = self.mysql_client.query(sql)
        return rows, self.mysql_sock.received - before

    def stages(self, via: str, sql: str = QUERY) -> dict:
        """EXPLAIN ANALYZE rows -> {stage: (rows, elapsed_ms, detail)} in
        order."""
        rows, _ = self.sql_raw(via, "EXPLAIN ANALYZE " + sql)
        assert all(len(r) == 5 for r in rows), "the table keeps five columns"
        return {r[0]: (int(r[1]), float(r[3]), r[4] or "") for r in rows}


@pytest.fixture(scope="module")
def wires(tmp_path_factory):
    w = Wires(str(tmp_path_factory.mktemp("spans")))
    w.fe.do_query(
        "CREATE TABLE span_cpu (host STRING, ts TIMESTAMP TIME INDEX, "
        "usage DOUBLE, PRIMARY KEY(host))")
    values = ", ".join(f"('h{h}', {1000 * t}, {h + t / 10})"
                       for h in range(8) for t in range(240))
    w.fe.do_query(f"INSERT INTO span_cpu VALUES {values}")
    for via in ("http", "mysql"):      # compile and build the scan cache
        w.sql_raw(via, QUERY)
    yield w
    # the floor is process-global: later test files rely on the default
    w.fe.do_query("SET tpu_dispatch_min_rows = 131072")
    w.close()


def interval(stages: dict, name: str):
    found = T0.search(stages[name][2])
    assert found, f"row {name!r} has no t0_ns: {stages[name][2]!r}"
    start = int(found.group(1))
    return start, start + int(stages[name][1] * 1e6)


VIAS = pytest.mark.parametrize("via", ["http", "mysql"])


@VIAS
def test_every_timed_row_is_a_span(wires, via):
    stages = wires.stages(via)
    assert "device-resident" in stages["dispatch"][2]
    for want in ("parse", "plan", "scan_prep", "reduce", "reduce.runs",
                 "reduce.mask", "reduce.upload", "reduce.launch",
                 "reduce.fetch", "reduce.collect", "finalize", "project",
                 "project.sort", "project.to_batches", "total", "render"):
        assert want in stages, f"no {want} row in {list(stages)}"
    for name, (_rows, ms, _detail) in stages.items():
        if name not in UNTIMED:
            start, end = interval(stages, name)
            assert end >= start > 1_600_000_000 * 10**9, name
    names = list(stages)
    assert names.index("parse") < names.index("plan") < names.index("total")
    assert names[-1] == "render", "render lies after total"
    assert stages["plan"][1] > 0.0, "the plan row shows its time"
    assert stages["plan"][2].startswith("TpuAggregateExec")


@VIAS
def test_parts_lie_inside_their_parent(wires, via):
    stages = wires.stages(via)
    # the request's rows are parts of no row: their whole is the client's
    parts = [n for n in stages if "." in n and not n.startswith("request.")]
    assert len(parts) >= 8
    slack = 50_000      # two clocks: wall start, monotonic length
    for part in parts:
        lo, hi = interval(stages, part)
        plo, phi = interval(stages, part.split(".", 1)[0])
        assert plo - slack <= lo and hi <= phi + slack, (part, lo - plo,
                                                         phi - hi)


@VIAS
def test_top_level_rows_are_disjoint_and_fit_total(wires, via):
    stages = wires.stages(via)
    top = sorted(
        (interval(stages, n) + (n,) for n in stages
         if "." not in n and n not in UNTIMED | OUTSIDE_TOTAL))
    assert [n for _, _, n in top] == ["plan", "scan_prep", "reduce",
                                      "finalize", "project"]
    slack = 50_000
    for (_, end, a), (start, _, b) in zip(top, top[1:]):
        assert end <= start + slack, f"{a} runs into {b}"
    timed_ms = sum(stages[n][1] for _, _, n in top)
    assert timed_ms <= stages["total"][1]
    # parse before the rest, render after it
    assert interval(stages, "parse")[1] <= top[0][0] + slack
    assert interval(stages, "render")[0] + slack >= top[-1][1]


@VIAS
def test_render_reports_the_plain_statement(wires, via):
    rows, sent = wires.sql_raw(via, QUERY)
    render = wires.stages(via)["render"]
    assert render[0] == len(rows) == 8 * 4
    detail = dict(kv.split("=") for kv in render[2].split(", "))
    assert detail["protocol"] == via
    # HTTP's body carries execution_time_ms, whose digits may differ
    assert abs(int(detail["bytes"]) - sent) <= (4 if via == "http" else 0)


@VIAS
def test_total_names_the_statements_trace(wires, via):
    total = wires.stages(via)["total"]
    assert re.fullmatch(r"trace_id=[0-9a-f]{32}, cpu_ms=\d+\.\d{3}",
                        total[2]), total[2]


def test_a_request_over_http_is_framed_by_its_handoffs(wires):
    stages = wires.stages("http")
    names = list(stages)
    assert names[:3] == BEFORE and names[-3:] == AFTER, names
    chain = BEFORE + [n for n in names[3:-3] if "." not in n
                      and n not in UNTIMED] + AFTER[1:]
    assert chain[3:-2] == ["plan", "scan_prep", "reduce", "finalize",
                           "project"]
    slack = 1_000_000       # 1 ms: the wall clock against the monotonic
    for a, b in zip(chain, chain[1:]):
        assert interval(stages, a)[1] <= interval(stages, b)[0] + slack, \
            f"{a} runs into {b}"
    # nothing long lies between them: the frame is the whole request
    whole = interval(stages, "render")[1] - interval(stages, BEFORE[0])[0]
    covered = sum(stages[n][1] for n in BEFORE + AFTER) * 1e6
    assert whole - covered < 20e6, (whole, covered)


@pytest.mark.parametrize("via", ["mysql", "postgres"])
def test_a_connections_own_thread_has_no_handoff_rows(wires, via):
    """MySQL and Postgres run a statement on the connection's thread."""
    if via == "mysql":
        names = list(wires.stages(via))
    else:
        from greptimedb_tpu.servers.postgres import PostgresServer
        server = PostgresServer(wires.fe)
        server.serve_in_background()
        try:
            wires.fe.do_query("SET tpu_dispatch_min_rows = 1")
            client = MiniPgClient(server.port)
            names = [r[0] for r in client.query("EXPLAIN ANALYZE "
                                                + QUERY)[1]]
            client.close()
        finally:
            server.shutdown()
    assert names[0] == "parse" and names[-2:] == ["total", "render"]
    assert not [n for n in names if n.startswith("request.")]


@pytest.mark.parametrize("via", ["http", "mysql"])
def test_every_span_reads_its_threads_cpu_time(wires, via):
    stages = wires.stages(via)
    spans = [n for n in stages if T0.search(stages[n][2])]
    assert len(spans) >= 16
    for name in spans + ["total"]:
        _rows, ms, detail = stages[name]
        found = CPU.search(detail)
        if name in NO_CPU:
            assert found is None, f"{name} reads no CPU clock: {detail}"
            continue
        assert found, f"row {name!r} has no cpu_ms: {detail!r}"
        # two clocks, and the CPU clock ticks coarsely on some kernels
        assert 0.0 <= float(found.group(1)) <= ms + 1.0, (name, detail)
        if name != "total":
            assert re.search(r"cpu_ms=[0-9.]+, t0_ns=\d+$", detail), detail
    assert "cpu_ms=" not in stages["dispatch"][2]


def test_a_datanodes_rows_keep_their_own_start():
    from greptimedb_tpu.common.exec_stats import ExecStats
    remote = ExecStats()
    with remote.stage("reduce"):
        pass
    start = remote.stages["reduce"].t0_ns
    local = ExecStats()
    local.absorb(json.loads(json.dumps(remote.to_dict())))
    assert local.stages["reduce"].t0_ns == start
    assert local.stages["reduce"].detail_str() == f"t0_ns={start}"


def _burn(seconds):
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


@pytest.mark.parametrize("work, low, high", [
    (_burn, 0.3, 1.05), (time.sleep, 0.0, 0.2)],
    ids=["busy-loop", "sleep"])
def test_cpu_time_tells_work_from_waiting(work, low, high):
    from greptimedb_tpu.common.exec_stats import ExecStats
    stats = ExecStats(cpu=True)
    with stats.stage("row", n=1):
        work(0.08)
    st = stats.stages["row"]
    assert st.elapsed_s >= 0.08
    assert low * st.elapsed_s <= st.cpu_s <= high * st.elapsed_s + 2e-3
    assert st.detail_str().startswith(f"n=1, cpu_ms={st.cpu_s * 1e3:.3f}, ")


def test_a_row_recorded_without_a_timed_interval_has_no_cpu_ms():
    """A pool worker's slices: counted and summed, never clocked here."""
    from greptimedb_tpu.common.exec_stats import ExecStats
    stats = ExecStats()
    stats.record("decode", rows=10, elapsed_s=0.004, t0_ns=123)
    stats.record("decode", rows=5, elapsed_s=0.001)
    assert stats.stages["decode"].cpu_s is None
    assert stats.stages["decode"].detail_str() == "t0_ns=123"
    assert stats.to_dict()["stages"][0]["cpu_ms"] is None
    local = ExecStats()
    local.absorb(json.loads(json.dumps(stats.to_dict())))
    assert local.stages["decode"].cpu_s is None


def test_cpu_time_adds_up_over_a_rows_entries_and_crosses_the_wire():
    from greptimedb_tpu.common.exec_stats import ExecStats
    remote = ExecStats(cpu=True)
    for _ in range(2):
        with remote.stage("reduce"):
            _burn(0.01)
    # two entries of 10 ms each, less what a busy machine took away
    assert 0.004 <= remote.stages["reduce"].cpu_s <= \
        remote.stages["reduce"].elapsed_s + 2e-3
    local = ExecStats()
    local.absorb(json.loads(json.dumps(remote.to_dict())))
    assert local.stages["reduce"].cpu_s == pytest.approx(
        remote.stages["reduce"].cpu_s, abs=1e-6)
    assert re.fullmatch(r"cpu_ms=\d+\.\d{3}, t0_ns=\d+",
                        local.stages["reduce"].detail_str())


def test_only_an_analysed_statement_reads_the_cpu_clock(wires, monkeypatch):
    """A read of the thread's CPU clock is a system call (5.5 us on the
    chip's host): the collector of a plain statement, whose rows nobody
    reads, makes none; `telemetry.timer`'s are `time.thread_time`."""
    from greptimedb_tpu.common.exec_stats import ExecStats, Timed
    reads = []
    clock = time.thread_time_ns
    monkeypatch.setattr(time, "thread_time_ns",
                        lambda: reads.append(1) or clock())
    for via in ("http", "mysql"):
        wires.sql_raw(via, QUERY)
    assert reads == []
    wires.stages("http")
    assert len(reads) >= 2 * 16
    del reads[:]
    with Timed("parse") as plain, ExecStats().stage("row"):
        pass
    assert reads == [] and plain.cpu_s is None and plain.elapsed_s > 0
    with Timed("row", cpu=True) as timed:
        pass
    assert len(reads) == 2 and 0.0 <= timed.cpu_s <= timed.elapsed_s + 2e-3


def test_telemetry_alone_does_not_import_jax():
    code = ("import sys; import greptimedb_tpu.common.telemetry as t; "
            "import greptimedb_tpu.common.exec_stats as e\n"
            "with t.span('s'), t.timer('x'), e.Timed('y'): pass\n"
            "assert 'jax' not in sys.modules, 'jax was imported'")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_stages_show_on_the_profilers_host_plane(wires, tmp_path):
    import glob

    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        wires.stages("http")
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    assert found
    data = jax.profiler.ProfileData.from_file(found[-1])
    names = {ev.name for plane in data.planes
             if not plane.name.startswith("/device:")
             for line in plane.lines for ev in line.events}
    for want in ("execute_stmt", "plan", "reduce", "reduce.fetch",
                 "finalize", "project", "render"):
        assert want in names, f"no {want} event among the host events"


def test_row_insert_timers_and_no_per_span_histogram(wires):
    wires.http_raw("/v1/influxdb/write", {"precision": "ms"},
                   body=b"span_lp,host=a usage=1.5 1000\n"
                        b"span_lp,host=b usage=2.5 1000\n")
    wires.sql_raw("http", QUERY)
    text = wires.http_raw("/metrics").decode()
    samples = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            name, _, value = line.rpartition(" ")
            samples[name] = float(value)
    assert samples["greptime_ingest_parse_seconds_count"] >= 1
    assert samples['greptime_render_seconds_count{protocol="http"}'] >= 1
    assert not [n for n in samples if n.startswith("greptime_span_")]
