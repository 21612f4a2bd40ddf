"""Durable trace store tests (ISSUE 15).

Spans persist into the database they describe: the TraceSink buffers
completed spans per trace, the tail verdict fires at the root span's
exit (slow / error / KILLed / balancer / head-sample), and retained
spans flush through the self-monitor ingest path into
greptime_private.trace_spans. Datanodes buffer blind until the
frontend's verdict piggybacks on a later RPC; a TTL evicts the rest.
"""

import json
import logging
import time

import pytest

from greptimedb_tpu.common import trace_store
from greptimedb_tpu.common.telemetry import (
    root_span, set_slow_query_threshold_ms, span)
from greptimedb_tpu.common.trace_store import (
    PRIVATE_SCHEMA, TRACE_SPANS_TABLE, TraceSink)
from greptimedb_tpu.datanode.instance import DatanodeInstance, DatanodeOptions
from greptimedb_tpu.frontend.instance import FrontendInstance


@pytest.fixture(autouse=True)
def _restore_knobs():
    saved_ratio = trace_store.sample_ratio()
    saved_ret = trace_store.retention_ms()
    saved_sink = trace_store.sink()
    yield
    trace_store.configure(sample_ratio=saved_ratio,
                          retention_ms=saved_ret, buffer_ttl_s=300)
    trace_store.install(saved_sink)
    set_slow_query_threshold_ms(None)


@pytest.fixture()
def fe(tmp_path):
    dn = DatanodeInstance(DatanodeOptions(data_home=str(tmp_path)))
    frontend = FrontendInstance(dn)
    frontend.start()
    frontend.do_query(
        "CREATE TABLE cpu (host STRING, ts TIMESTAMP TIME INDEX, "
        "v DOUBLE, PRIMARY KEY(host))")
    frontend.do_query("INSERT INTO cpu VALUES ('a', 1000, 1.5), "
                      "('b', 2000, 2.5)")
    yield frontend
    frontend.shutdown()


def _pydict(fe, sql):
    out = fe.do_query(sql)[-1]
    return out.batches[0].to_pydict()


def _stored_names(fe, trace_id):
    rows = trace_store.fetch_trace(fe.catalog, trace_id)
    return sorted(str(r["span_name"]) for r in rows)


class TestTailSampling:
    def test_ratio_one_retains_and_stores(self, fe):
        trace_store.configure(sample_ratio=1.0)
        fe.do_query("SELECT host FROM cpu")
        sink = trace_store.sink()
        tid = sink.last_retained
        assert tid is not None
        assert sink.flush() > 0
        names = _stored_names(fe, tid)
        assert "execute_stmt" in names

    def test_ratio_zero_fast_query_leaves_no_spans(self, fe):
        trace_store.configure(sample_ratio=0.0)
        sink = trace_store.sink()
        # held by the statement's own trace, not by the sink's totals: the
        # sink is the process's, and a thread an earlier test of this
        # worker left running may finish a retained trace meanwhile
        tid = fe.do_query("SELECT host FROM cpu")[-1].trace[0]
        assert sink.stored_verdict(tid) == "sampled-out"
        assert sink.stats["traces_sampled_out"] > 0
        sink.flush()
        assert _stored_names(fe, tid) == []

    def test_slow_query_retained_at_ratio_zero(self, fe):
        trace_store.configure(sample_ratio=0.0)
        set_slow_query_threshold_ms(1)      # everything is "slow"
        fe.do_query("SELECT host, v FROM cpu ORDER BY host")
        sink = trace_store.sink()
        tid = sink.last_retained
        assert tid is not None
        assert sink.flush() > 0
        assert "execute_stmt" in _stored_names(fe, tid)

    def test_error_retained_at_ratio_zero(self, fe):
        trace_store.configure(sample_ratio=0.0)
        sink = trace_store.sink()
        before = sink.stats["traces_retained"]
        from greptimedb_tpu.errors import GreptimeError
        with pytest.raises(GreptimeError):
            fe.do_query("SELECT host FROM no_such_table_xyz")
        assert sink.stats["traces_retained"] == before + 1
        tid = sink.last_retained
        sink.flush()
        rows = trace_store.fetch_trace(fe.catalog, tid)
        assert any(r["status"] == "error" for r in rows)

    def test_killed_query_always_retained(self, fe):
        """A KILLed statement reads as status=cancelled and retains at
        ratio 0 — the operator's first question after a KILL is 'what
        was it doing'."""
        trace_store.configure(sample_ratio=0.0)
        import threading

        import numpy as np
        from greptimedb_tpu.errors import QueryCancelledError
        n = 400_000
        fe.catalog.table("greptime", "public", "cpu").bulk_load({
            "host": np.array([f"h{i % 50}" for i in range(n)],
                             dtype=object),
            "ts": np.arange(n, dtype=np.int64) * 100,
            "v": np.random.default_rng(7).random(n)})
        fe.do_query("SET stream_threshold_rows = 1000")
        try:
            from greptimedb_tpu.common import process_list
            started = threading.Event()
            seen = {}
            orig = process_list.REGISTRY.register

            def spy(*a, **k):
                e = orig(*a, **k)
                seen["id"] = e.id
                started.set()
                return e
            process_list.REGISTRY.register = spy
            try:
                t = threading.Thread(
                    target=lambda: seen.setdefault("err", _run(fe)))

                def _run(fe):
                    try:
                        fe.do_query("SELECT host, avg(v) FROM cpu "
                                    "GROUP BY host")
                        return None
                    except QueryCancelledError as e:
                        return e
                t = threading.Thread(
                    target=lambda: seen.setdefault("err", _run(fe)))
                t.start()
                assert started.wait(10)
                # kill as soon as the statement registers; the scan
                # checks cancellation at slice boundaries
                process_list.REGISTRY.kill(seen["id"])
                t.join(30)
            finally:
                process_list.REGISTRY.register = orig
            sink = trace_store.sink()
            if isinstance(seen.get("err"), QueryCancelledError):
                tid = sink.last_retained
                assert tid is not None
                sink.flush()
                rows = trace_store.fetch_trace(fe.catalog, tid)
                assert any(r["status"] == "cancelled" for r in rows)
            else:
                # raced to completion before the kill landed: the
                # cancelled-retention path is still covered by the unit
                # test below
                pass
        finally:
            fe.do_query("SET stream_threshold_rows = 2000000")

    def test_cancelled_status_unit(self):
        """Sink-level: a QueryCancelledError crossing the root span
        retains the trace at ratio 0."""
        trace_store.configure(sample_ratio=0.0)
        sink = TraceSink(node_label="t", role="root", writer=None)
        trace_store.install(sink)
        from greptimedb_tpu.errors import QueryCancelledError
        with pytest.raises(QueryCancelledError):
            with span("execute_stmt"):
                raise QueryCancelledError("killed")
        assert sink.stats["traces_retained"] == 1

    def test_balancer_span_retained_at_ratio_zero(self):
        trace_store.configure(sample_ratio=0.0)
        sink = TraceSink(node_label="t", role="root", writer=None)
        trace_store.install(sink)
        with root_span("job_balancer_op", op_id="x"):
            pass
        assert sink.stats["traces_retained"] == 1

    def test_head_sample_deterministic(self):
        trace_store.configure(sample_ratio=0.5)
        tid = "deadbeef" * 4
        assert trace_store.head_sampled(tid) == \
            trace_store.head_sampled(tid)
        trace_store.configure(sample_ratio=0.0)
        assert not trace_store.head_sampled(tid)
        trace_store.configure(sample_ratio=1.0)
        assert trace_store.head_sampled(tid)


class TestSlowLogAnnotation:
    def test_slow_log_carries_trace_stored(self, fe, caplog):
        trace_store.configure(sample_ratio=0.0)
        set_slow_query_threshold_ms(1)
        with caplog.at_level(logging.WARNING,
                             logger="greptimedb_tpu.slow_query"):
            fe.do_query("SELECT host FROM cpu")
        msgs = [r.getMessage() for r in caplog.records
                if "slow query" in r.getMessage()]
        assert msgs and "trace_stored=yes" in msgs[-1]

    def test_fast_statement_reports_sampled_out(self, fe, caplog):
        """Threshold high enough that nothing is slow, but force the
        log by lowering it only for the check: instead, verify the
        sink's verdict function directly for a sampled-out trace."""
        trace_store.configure(sample_ratio=0.0)
        sink = trace_store.sink()
        fe.do_query("SELECT host FROM cpu")
        # the last trace was sampled out; its verdict reads accordingly
        with sink._lock:
            tid = next(reversed(sink._verdicts))
        assert sink.stored_verdict(tid) == "sampled-out"


class TestWaterfallSurfaces:
    def test_admin_show_trace_renders_tree(self, fe):
        trace_store.configure(sample_ratio=1.0)
        fe.do_query("SELECT host, v FROM cpu ORDER BY host")
        out = fe.do_query("ADMIN SHOW TRACE 'last'")[-1]
        d = out.batches[0].to_pydict()
        assert "execute_stmt" in d["span"][0]
        assert d["node"][0] == "standalone"
        assert d["status"][0] == "ok"
        # children render indented under the root
        for s in d["span"][1:]:
            assert s.startswith("  ")

    def test_admin_show_trace_unknown_id_clean_error(self, fe):
        from greptimedb_tpu.errors import InvalidArgumentsError
        with pytest.raises(InvalidArgumentsError, match="not found"):
            fe.do_query("ADMIN SHOW TRACE 'ffffffffffffffff'")

    def test_information_schema_trace_spans_view(self, fe):
        trace_store.configure(sample_ratio=1.0)
        fe.do_query("SELECT host FROM cpu")
        d = _pydict(fe, "SELECT span_name, node, status, trace_id FROM "
                        "information_schema.trace_spans")
        assert "execute_stmt" in d["span_name"]
        assert all(s in ("ok", "error", "cancelled")
                   for s in d["status"])

    def test_waterfall_network_split_for_dist_rpc(self):
        rows = [
            {"span_id": "a", "parent_span_id": "", "span_name":
             "execute_stmt", "node": "frontend", "ts": 0,
             "duration_ms": 10.0, "status": "ok", "attrs": ""},
            {"span_id": "b", "parent_span_id": "a", "span_name":
             "dist_rpc", "node": "frontend", "ts": 1,
             "duration_ms": 8.0, "status": "ok", "attrs": ""},
            {"span_id": "c", "parent_span_id": "b", "span_name":
             "dn_scan", "node": "dn1", "ts": 2, "duration_ms": 5.0,
             "status": "ok", "attrs": ""},
        ]
        wf = trace_store.waterfall_rows(rows)
        assert [r["span"].strip().lstrip("└─ ") for r in wf] == \
            ["execute_stmt", "dist_rpc", "dn_scan"]
        rpc = wf[1]
        assert rpc["self_ms"] == pytest.approx(3.0)
        assert "network_ms=3.0" in rpc["detail"]
        assert wf[2]["node"] == "dn1"


class TestBackgroundJobs:
    def test_flush_job_registered_with_region(self, fe):
        from greptimedb_tpu.common import background_jobs
        background_jobs.reset()
        fe.do_query("ADMIN FLUSH TABLE cpu")
        rows = background_jobs.rows()
        flushes = [r for r in rows if r["kind"] == "flush"]
        assert flushes
        assert flushes[0]["state"] == "done"
        assert flushes[0]["region"]
        assert flushes[0]["trace_id"]
        assert flushes[0]["duration_ms"] is not None

    def test_background_jobs_view_serves_rows(self, fe):
        fe.do_query("ADMIN FLUSH TABLE cpu")
        d = _pydict(fe, "SELECT kind, state, node FROM "
                        "information_schema.background_jobs")
        assert "flush" in d["kind"]

    def test_live_job_shows_running(self):
        from greptimedb_tpu.common import background_jobs
        background_jobs.reset()
        with background_jobs.job("compaction", region="r1"):
            rows = background_jobs.rows()
            live = [r for r in rows if r["kind"] == "compaction"]
            assert live and live[0]["state"] == "running"
            assert live[0]["duration_ms"] is not None
        rows = background_jobs.rows()
        assert [r for r in rows if r["kind"] == "compaction"][0][
            "state"] == "done"

    def test_failed_job_records_error(self):
        from greptimedb_tpu.common import background_jobs
        background_jobs.reset()
        with pytest.raises(RuntimeError):
            with background_jobs.job("ttl_sweep", region="r9"):
                raise RuntimeError("boom")
        row = [r for r in background_jobs.rows()
               if r["kind"] == "ttl_sweep"][0]
        assert row["state"] == "failed"
        assert "boom" in row["error"]

    def test_background_job_trace_retained_on_failure(self):
        """A failed background job is an errored trace: retained at
        ratio 0, so the postmortem has its spans."""
        trace_store.configure(sample_ratio=0.0)
        sink = TraceSink(node_label="t", role="root", writer=None)
        trace_store.install(sink)
        from greptimedb_tpu.common import background_jobs
        with pytest.raises(RuntimeError):
            with background_jobs.job("compaction", region="r1"):
                raise RuntimeError("disk full")
        assert sink.stats["traces_retained"] == 1

    def test_root_span_restores_ambient_trace(self):
        with span("outer") as outer:
            with root_span("job_flush") as job_sp:
                assert job_sp["trace_id"] != outer["trace_id"]
                assert job_sp["parent_id"] is None
            with span("inner") as inner:
                assert inner["trace_id"] == outer["trace_id"]


class TestRecursionGuard:
    def test_storing_traces_never_retains_its_own_writes(self, fe):
        """The flush writes run under suppress_metrics: the spans they
        open are invisible to the sink, so the trace store can never
        feed itself (satellite: recursion test)."""
        trace_store.configure(sample_ratio=1.0)
        fe.do_query("SELECT host FROM cpu")
        sink = trace_store.sink()
        sink.flush()
        retained_after_flush = sink.stats["traces_retained"]
        spans_after_flush = sink.stats["spans_recorded"]
        # repeated flushes with nothing pending record nothing
        for _ in range(3):
            sink.flush()
        assert sink.stats["traces_retained"] == retained_after_flush
        assert sink.stats["spans_recorded"] == spans_after_flush

    def test_monitor_tick_converges_with_trace_store_on(self, fe):
        """Scraper ticks (which now also flush traces) stay suppressed
        end to end — their own root span must not grow the registry."""
        trace_store.configure(sample_ratio=1.0)
        from greptimedb_tpu.common.telemetry import registry_snapshot

        def greptime_counters():
            # greptime_* only: process/python_gc counters tick on their
            # own regardless of the scraper
            return {(n, l): v for n, l, v, _ in registry_snapshot()
                    if n.startswith("greptime_")}
        fe.self_monitor.tick()
        before = greptime_counters()
        fe.self_monitor.tick()
        after = greptime_counters()
        assert before == after


class TestRetention:
    def test_trace_retention_sweep(self, fe):
        """Aged trace rows sweep on the monitor tick under the
        trace-specific knob (separate from self_monitor_retention_ms)."""
        trace_store.configure(sample_ratio=1.0)
        fe.do_query("SELECT host FROM cpu")
        sink = trace_store.sink()
        sink.flush()
        n0 = _pydict(fe, f"SELECT count(*) FROM {PRIVATE_SCHEMA}."
                         f"{TRACE_SPANS_TABLE}")["count(*)"][0]
        assert n0 > 0
        trace_store.configure(sample_ratio=0.0)  # no new retains
        fe.do_query("SET trace_retention_ms = 1")
        time.sleep(0.01)
        fe.self_monitor.tick()
        n1 = _pydict(fe, f"SELECT count(*) FROM {PRIVATE_SCHEMA}."
                         f"{TRACE_SPANS_TABLE}")["count(*)"][0]
        assert n1 == 0

    def test_set_trace_sample_ratio_validation(self, fe):
        from greptimedb_tpu.errors import InvalidArgumentsError
        with pytest.raises(InvalidArgumentsError):
            fe.do_query("SET trace_sample_ratio = 'banana'")
        with pytest.raises(InvalidArgumentsError):
            fe.do_query("SET trace_sample_ratio = 7")


class TestDatanodeBuffering:
    """Buffer-role sinks: the datanode half of tail sampling."""

    def _remote_span(self, sink, trace_id, name="dn_scan"):
        trace_store.install(sink)
        from greptimedb_tpu.common.telemetry import remote_context
        header = f"00-{trace_id}-00f067aa0ba902b7-01"
        with remote_context(header):
            with span(name, node=3):
                pass

    def test_buffer_role_holds_until_verdict(self):
        sink = TraceSink(node_label="dn3", service="datanode",
                         role="buffer")
        tid = "a" * 32
        self._remote_span(sink, tid)
        assert sink.take_export() == []          # nothing released
        sink.apply_verdicts({tid: True})
        rows = sink.take_export()
        assert len(rows) == 1
        assert rows[0]["trace_id"] == tid
        assert rows[0]["node"] == "dn3"

    def test_buffer_role_discards_on_negative_verdict(self):
        sink = TraceSink(node_label="dn3", service="datanode",
                         role="buffer")
        tid = "b" * 32
        self._remote_span(sink, tid)
        sink.apply_verdicts({tid: False})
        assert sink.take_export() == []
        assert sink.stats["traces_sampled_out"] == 1

    def test_ttl_evicts_verdictless_traces(self):
        trace_store.configure(buffer_ttl_s=1)
        sink = TraceSink(node_label="dn3", service="datanode",
                         role="buffer")
        tid = "c" * 32
        self._remote_span(sink, tid)
        assert sink.evict_expired(now=time.monotonic() + 5) == 1
        # a verdict arriving after eviction finds nothing to release
        sink.apply_verdicts({tid: True})
        assert sink.take_export() == []

    def test_late_span_follows_verdict(self):
        """A span completing after its trace's verdict (pool worker
        straggler) applies the verdict directly."""
        trace_store.configure(sample_ratio=0.0)
        sink = TraceSink(node_label="t", role="root", writer=None)
        trace_store.install(sink)
        set_slow_query_threshold_ms(1)
        import time as _t
        with span("execute_stmt") as sp:
            tid = sp["trace_id"]
            _t.sleep(0.005)
        # trace decided (slow → retained); a straggler span of the
        # same trace now completes
        sink.on_span_end({"name": "straggler", "trace_id": tid,
                          "span_id": "feedfeedfeedfeed",
                          "parent_id": sp["span_id"],
                          "attrs": {}, "start_unix_ns": 0}, 1.0, "ok")
        rows = sink.take_export()
        assert {r["span_name"] for r in rows} == \
            {"execute_stmt", "straggler"}

    def test_push_verdict_resurfaces_aged_out_verdicts(self):
        """A verdict older than the youngest-PIGGYBACK_MAX window never
        rides an RPC again on its own; the render path re-announces it
        (push_verdict) so SHOW TRACE can still release a datanode's
        buffer minutes later. A known sampled-out trace is not
        resurrected."""
        sink = TraceSink(node_label="fe", role="root")
        tid_old = "a" * 32
        with sink._lock:
            sink._verdicts[tid_old] = (True, time.monotonic())
            for i in range(sink.PIGGYBACK_MAX + 8):
                sink._verdicts[f"{i:032x}"] = (False, time.monotonic())
        assert tid_old not in sink.recent_verdicts()
        assert sink.push_verdict(tid_old)
        assert sink.recent_verdicts().get(tid_old) is True
        # sampled-out stays sampled-out (probe one still in-window)
        dropped = f"{sink.PIGGYBACK_MAX + 7:032x}"
        assert not sink.push_verdict(dropped)
        assert sink.recent_verdicts().get(dropped) is False

    def test_root_role_decides_for_remote_parent(self):
        """A frontend joining an external client's trace still decides
        the verdict (role=root), it does not buffer forever."""
        trace_store.configure(sample_ratio=1.0)
        sink = TraceSink(node_label="fe", role="root", writer=None)
        self._remote_span(sink, "d" * 32, name="execute_stmt")
        assert sink.stats["traces_retained"] == 1


class TestVerdictPiggybackWire:
    """Real Flight sockets: verdicts ride RPC bodies out, released
    spans ride responses home."""

    @pytest.fixture()
    def wire(self, tmp_path):
        from greptimedb_tpu.client.flight import FlightDatanodeClient
        from greptimedb_tpu.servers.flight import FlightDatanodeServer
        dn = DatanodeInstance(DatanodeOptions(
            data_home=str(tmp_path / "dn"), node_id=7,
            register_numbers_table=False))
        dn.start()
        server = FlightDatanodeServer(dn)
        server.serve_in_background()
        client = FlightDatanodeClient(server.address, 7)
        client.ping()                       # wait for serving
        yield dn, server, client
        client.close()
        server.shutdown()
        dn.shutdown()

    def test_verdict_piggyback_releases_datanode_spans(self, wire):
        dn, server, client = wire
        # datanode-side sink buffers a remote-rooted span
        dn_sink = TraceSink(node_label="dn7", service="datanode",
                            role="buffer")
        tid = "e" * 32
        trace_store.install(dn_sink)
        from greptimedb_tpu.common.telemetry import remote_context
        with remote_context(f"00-{tid}-00f067aa0ba902b7-01"):
            with span("dn_scan", node=7):
                pass
        assert dn_sink.buffered_trace_count() == 1
        # frontend-side root sink carries a fresh verdict; the ping
        # piggybacks it and the released span rides the response. Both
        # sinks live in this process, so install the ROOT sink around
        # the client call (the server thread reads the same global:
        # single-process test of a two-process protocol — the wire
        # format is what's under test)
        root_sink = TraceSink(node_label="fe", role="root", writer=None)
        with root_sink._lock:
            root_sink._verdicts[tid] = (True, time.monotonic())
        # hand-deliver: apply verdicts on the dn sink via the server
        # path by sending an action whose body carries them
        import pyarrow.flight as flight
        body = json.dumps({trace_store.TRACE_VERDICTS_BODY_KEY:
                           {tid: True}}).encode()
        results = list(client.conn.do_action(flight.Action("ping",
                                                           body)))
        resp = json.loads(results[0].body.to_pybytes())
        assert resp["ok"]
        spans = resp.get("trace_spans")
        assert spans and spans[0]["trace_id"] == tid
        assert spans[0]["span_name"] == "dn_scan"

    def test_client_traced_attaches_verdicts(self, wire):
        """_traced() on a root sink attaches recent verdicts to every
        outbound body; the datanode drops the negatively-verdicted
        buffer."""
        dn, server, client = wire
        sink = TraceSink(node_label="fe", role="root", writer=None)
        trace_store.install(sink)
        tid = "f" * 32
        # buffer a trace on the (shared in-process) sink as if it were
        # the datanode's, then record a DROP verdict and ping
        from greptimedb_tpu.common.telemetry import remote_context
        dn_sink = TraceSink(node_label="dn7", service="datanode",
                            role="buffer")
        with sink._lock:
            sink._verdicts[tid] = (False, time.monotonic())
        trace_store.install(dn_sink)         # server side sees this
        with remote_context(f"00-{tid}-00f067aa0ba902b7-01"):
            with span("dn_scan", node=7):
                pass
        trace_store.install(sink)            # client side sees this
        assert sink.recent_verdicts() == {tid: False}
        trace_store.install(dn_sink)
        from greptimedb_tpu.client import flight as cflight
        body = cflight._traced({})
        # simulate what a root-sink client attaches
        trace_store.install(sink)
        body = cflight._traced({})
        assert body[trace_store.TRACE_VERDICTS_BODY_KEY] == {tid: False}


class TestDropAccounting:
    def _counter_value(self, name):
        from greptimedb_tpu.common.telemetry import registry_snapshot
        for n, _l, v, _k in registry_snapshot():
            if n == name:
                return v
        return 0.0

    def test_otlp_full_queue_drops_are_counted(self):
        """Satellite: beyond the one-shot log, a shedding OTLP exporter
        shows up in greptime_trace_export_dropped_total (and therefore
        in runtime_metrics / the scraped history)."""
        from greptimedb_tpu.common.telemetry import OtlpExporter
        exp = OtlpExporter("http://127.0.0.1:1", flush_interval=3600,
                           max_queue=2)
        try:
            before = self._counter_value(
                "greptime_trace_export_dropped_total")
            s = {"trace_id": "a" * 32, "span_id": "b" * 16,
                 "name": "x", "attrs": {}, "start_unix_ns": 1}
            for _ in range(5):
                exp.enqueue(dict(s), 1000)
            assert exp.dropped == 3
            after = self._counter_value(
                "greptime_trace_export_dropped_total")
            assert after - before == 3
        finally:
            exp.shutdown()

    def test_sink_overflow_drops_are_counted(self):
        """The new sink's drop counter surfaces the same way."""
        trace_store.configure(sample_ratio=0.0)
        sink = TraceSink(node_label="t", role="buffer")
        trace_store.install(sink)
        before = self._counter_value("greptime_trace_sink_dropped_total")
        from greptimedb_tpu.common.telemetry import remote_context
        for i in range(1, sink.MAX_TRACES + 6):
            # from 1: an all-zero trace id is invalid per W3C and the
            # remote_context would be a no-op for it
            tid = f"{i:032x}"
            with remote_context(f"00-{tid}-00f067aa0ba902b7-01"):
                with span("dn_scan"):
                    pass
        assert sink.stats["spans_dropped"] == 5
        after = self._counter_value("greptime_trace_sink_dropped_total")
        assert after - before == 5


class TestHttpTraceEndpoint:
    @pytest.fixture()
    def server(self, fe):
        from greptimedb_tpu.servers.http import HttpServer
        srv = HttpServer(fe, addr="127.0.0.1:0")
        srv.start()
        yield srv
        srv.shutdown()

    def _get(self, srv, path):
        import urllib.error
        import urllib.request
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}{path}",
                    timeout=10) as resp:
                return resp.status, json.loads(resp.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    def test_get_trace_waterfall(self, fe, server):
        trace_store.configure(sample_ratio=1.0)
        fe.do_query("SELECT host FROM cpu")
        sink = trace_store.sink()
        tid = sink.last_retained
        status, doc = self._get(server, f"/v1/trace/{tid}")
        assert status == 200
        assert doc["trace_id"] == tid
        assert doc["span_count"] >= 1
        assert any(s["span_name"] == "execute_stmt"
                   for s in doc["spans"])
        assert doc["waterfall"][0]["span"] == "execute_stmt"
        # 'last' resolves to the most recently retained trace... which
        # by now is the /v1/trace request's own statementless flush-free
        # trace or the SELECT — either way it renders, not 404s
        status, doc = self._get(server, "/v1/trace/last")
        assert status == 200

    def test_get_unknown_trace_404(self, fe, server):
        trace_store.configure(sample_ratio=0.0)
        status, doc = self._get(server, "/v1/trace/abcdef0123456789")
        assert status == 404
        assert "not found" in doc["error"]


class TestDistributedDifferential:
    """Satellite: a distributed query's stored spans reassemble into
    the same per-node tree EXPLAIN ANALYZE renders (structure match,
    modulo timing)."""

    @pytest.fixture()
    def cluster(self, tmp_path):
        from greptimedb_tpu.client import LocalDatanodeClient
        from greptimedb_tpu.frontend.distributed import DistInstance
        from greptimedb_tpu.meta import MetaClient, Peer
        from greptimedb_tpu.meta.kv import MemKv
        from greptimedb_tpu.meta.service import MetaSrv
        datanodes, clients = {}, {}
        srv = MetaSrv(MemKv(), datanode_lease_secs=3600)
        meta = MetaClient(srv)
        for i in (1, 2):
            dn = DatanodeInstance(DatanodeOptions(
                data_home=str(tmp_path / f"dn{i}"), node_id=i,
                register_numbers_table=False))
            dn.start()
            datanodes[i] = dn
            clients[i] = LocalDatanodeClient(dn)
            srv.register_datanode(Peer(i, f"dn{i}"))
            srv.handle_heartbeat(i)
        fe = DistInstance(meta, clients)
        yield fe
        for dn in datanodes.values():
            dn.shutdown()

    def test_stored_trace_matches_explain_analyze_nodes(self, cluster):
        fe = cluster
        fe.do_query(
            "CREATE TABLE m (host STRING, ts TIMESTAMP TIME INDEX, "
            "v DOUBLE, PRIMARY KEY(host)) "
            "PARTITION BY HASH (host) PARTITIONS 4")
        values = ", ".join(f"('h{i}', {1000 + i}, {float(i)})"
                           for i in range(32))
        fe.do_query(f"INSERT INTO m VALUES {values}")
        trace_store.configure(sample_ratio=1.0)
        sql = "SELECT host, avg(v) FROM m GROUP BY host"
        fe.do_query(sql)
        sink = trace_store.sink()
        tid = sink.last_retained
        assert tid is not None
        sink.flush()
        rows = trace_store.fetch_trace(fe.catalog, tid)
        # EXPLAIN ANALYZE's per-node blocks name the same datanodes the
        # stored dist_rpc spans recorded
        out = fe.do_query(f"EXPLAIN ANALYZE {sql}")[-1]
        d = out.batches[0].to_pydict()
        ea_text = json.dumps(d)
        ea_nodes = {n for n in ("dn1", "dn2") if n in ea_text}
        assert ea_nodes == {"dn1", "dn2"}
        rpc_spans = [r for r in rows if r["span_name"] == "dist_rpc"]
        span_peers = {json.loads(r["attrs"])["peer"] for r in rpc_spans}
        assert span_peers == ea_nodes
        # structure: every dist_rpc span hangs (possibly through
        # intermediate exec spans) under the one execute_stmt root —
        # the same tree shape the ANALYZE node blocks render
        root = [r for r in rows if r["span_name"] == "execute_stmt"]
        assert len(root) == 1
        by_id = {r["span_id"]: r for r in rows}

        def reaches_root(r, hops=10):
            while hops:
                pid = r.get("parent_span_id")
                if pid == root[0]["span_id"]:
                    return True
                r = by_id.get(pid)
                if r is None:
                    return False
                hops -= 1
            return False
        assert all(reaches_root(r) for r in rpc_spans)
        wf = trace_store.waterfall_rows(rows)
        assert wf[0]["span"] == "execute_stmt"
        indented = [r for r in wf if r["span"].lstrip().startswith("└─")]
        assert len(indented) >= len(rpc_spans)
