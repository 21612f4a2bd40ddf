"""High-QPS front-door tests (ISSUE 12): admission control over real
wires, WAL group commit, ingest coalescing, and concurrent scan fusion.

The admission gate is load-shedding, not queueing: past the configured
in-flight limit new statements are REJECTED with a typed, retryable
error (HTTP 429 + Retry-After, MySQL 1040 server-busy, PG 53300) while
work already in flight — including work holding WAL group-commit cohort
slots — runs to completion. KILL and SET stay admitted (the operator's
way out), and the self-monitor's own greptime_private writes are
exempt (shedding the observer would blind the operator exactly when
they need the data).
"""

import json
import threading
import time
import urllib.error
import urllib.parse
import urllib.request

import numpy as np
import pytest

from greptimedb_tpu.common import process_list
from greptimedb_tpu.common.admission import GATE, exempt
from greptimedb_tpu.datanode.instance import DatanodeInstance, DatanodeOptions
from greptimedb_tpu.errors import GreptimeError, OverloadedError
from greptimedb_tpu.frontend.instance import FrontendInstance
from greptimedb_tpu.servers.coalesce import (
    COALESCER, configure_coalescer, coalescer_settings)
from greptimedb_tpu.storage.wal import (
    Wal, configure_group_commit, group_commit_settings)


@pytest.fixture(autouse=True)
def _reset_front_door_knobs():
    """Admission/coalescer/group-commit state is process-global — every
    test leaves it as it found it."""
    gate_snap = GATE.snapshot()
    gc_snap = group_commit_settings()
    co_snap = coalescer_settings()
    yield
    GATE.configure(max_inflight=gate_snap["max_inflight"],
                   max_queued_bytes=gate_snap["max_queued_bytes"],
                   retry_after_s=gate_snap["retry_after_s"])
    configure_group_commit(enabled=gc_snap[0], max_wait_us=gc_snap[1],
                           max_batch=gc_snap[2])
    configure_coalescer(window_ms=co_snap)


@pytest.fixture()
def frontend(tmp_path):
    dn = DatanodeInstance(DatanodeOptions(data_home=str(tmp_path / "d"),
                                          register_numbers_table=False))
    dn.start()
    fe = FrontendInstance(dn)
    fe.start()
    yield fe
    fe.shutdown()


def _scalar(out):
    """First column of the first row of an Output (rows() yields
    iterators)."""
    return list(list(out.batches[0].rows())[0])[0]


def _fill_registry(n):
    """Occupy n in-flight statement slots with live registry entries."""
    return [process_list.REGISTRY.register(f"SELECT {i}", "test", "", "",
                                           None) for i in range(n)]


def _drain(entries):
    for e in entries:
        process_list.REGISTRY.deregister(e)


# ---------------------------------------------------------------------------
# gate semantics (unit level)
# ---------------------------------------------------------------------------

class TestGateUnit:
    def test_disabled_by_default(self):
        assert GATE.snapshot()["max_inflight"] == 0
        GATE.admit_statement("Query")          # no limit: never raises

    def test_rejects_at_limit_and_recovers(self):
        GATE.configure(max_inflight=2)
        entries = _fill_registry(2)
        try:
            with pytest.raises(OverloadedError) as ei:
                GATE.admit_statement("Query")
            assert ei.value.retry_after_s >= 1
            assert ei.value.to_http_status() == 429
        finally:
            _drain(entries)
        GATE.admit_statement("Query")          # slots free: admitted

    def test_kill_and_set_always_admitted(self):
        GATE.configure(max_inflight=1)
        entries = _fill_registry(3)
        try:
            GATE.admit_statement("Kill")
            GATE.admit_statement("SetVariable")
            with pytest.raises(OverloadedError):
                GATE.admit_statement("Query")
        finally:
            _drain(entries)

    def test_exempt_context(self):
        GATE.configure(max_inflight=1)
        entries = _fill_registry(2)
        try:
            with exempt():
                GATE.admit_statement("Query")
                with GATE.admit_ingest(1 << 30):
                    pass
        finally:
            _drain(entries)

    def test_ingest_bytes_reject_and_release(self):
        GATE.configure(max_queued_bytes=100)
        with GATE.admit_ingest(80):
            with pytest.raises(OverloadedError):
                with GATE.admit_ingest(40):
                    pass
        # the 80-byte body drained: the 40-byte one is admitted now
        with GATE.admit_ingest(40):
            pass

    def test_single_oversized_body_admitted_when_idle(self):
        GATE.configure(max_queued_bytes=100)
        with GATE.admit_ingest(500):           # one body IS the queue
            pass


# ---------------------------------------------------------------------------
# over real HTTP: 429 + Retry-After, in-flight work completes
# ---------------------------------------------------------------------------

def _http_sql(port, stmt):
    url = f"http://127.0.0.1:{port}/v1/sql"
    body = urllib.parse.urlencode({"sql": stmt}).encode()
    r = urllib.request.Request(
        url, data=body, method="POST",
        headers={"Content-Type": "application/x-www-form-urlencoded"})
    try:
        with urllib.request.urlopen(r, timeout=15) as resp:
            return resp.status, dict(resp.headers), resp.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


class TestHttpOverload:
    @pytest.fixture()
    def http(self, frontend):
        from greptimedb_tpu.servers.http import HttpServer
        srv = HttpServer(frontend, addr="127.0.0.1:0")
        srv.start()
        yield srv
        srv.shutdown()

    def test_reject_with_429_and_retry_after_under_2x_load(self, http,
                                                           frontend):
        """2x the configured limit concurrently: the overflow rejects
        cleanly with Retry-After while every admitted statement
        completes — no collapse, no deadlock."""
        frontend.do_query(
            "CREATE TABLE adm (host STRING, ts TIMESTAMP TIME INDEX, "
            "v DOUBLE, PRIMARY KEY(host))")
        frontend.do_query("INSERT INTO adm VALUES ('a', 1000, 1.0)")
        limit = 2
        GATE.configure(max_inflight=limit, retry_after_s=3)
        entries = _fill_registry(limit)        # the "in-flight" load
        results = []
        try:
            def one():
                results.append(_http_sql(http.port,
                                         "SELECT * FROM adm"))
            threads = [threading.Thread(target=one)
                       for _ in range(2 * limit)]
            [t.start() for t in threads]
            [t.join(timeout=30) for t in threads]
        finally:
            _drain(entries)
        assert len(results) == 2 * limit
        rejected = [r for r in results if r[0] == 429]
        assert rejected, results
        for status, headers, body in rejected:
            assert headers.get("Retry-After") == "3"
            payload = json.loads(body)
            assert payload["code"] == 6001      # RATE_LIMITED
            assert "overloaded" in payload["error"]
        # the gate cleared: the same statement is admitted now and the
        # process did not collapse
        status, _h, _b = _http_sql(http.port, "SELECT * FROM adm")
        assert status == 200

    def test_inflight_work_completes_and_kill_releases_slots(
            self, http, frontend):
        """A slow admitted statement finishes; KILLing it frees its
        admission slot for the next arrival (KILL itself is never
        gated)."""
        frontend.do_query(
            "CREATE TABLE slowt (host STRING, ts TIMESTAMP TIME INDEX, "
            "v DOUBLE, PRIMARY KEY(host))")
        frontend.do_query(
            "INSERT INTO slowt VALUES " + ",".join(
                f"('h{i % 8}', {i * 1000}, {float(i)})"
                for i in range(64)))
        GATE.configure(max_inflight=1)
        release = threading.Event()
        from greptimedb_tpu.query import tpu_exec
        orig = tpu_exec.cached_table_frame

        def gated(table):
            if getattr(table, "name", "") == "slowt":
                release.wait(timeout=20)
            return orig(table)

        tpu_exec.cached_table_frame = gated
        outcome = {}

        def slow_query():
            try:
                outcome["out"] = frontend.do_query(
                    "SELECT host, v FROM slowt WHERE host = 'h1'")
            except GreptimeError as e:
                outcome["err"] = e

        t = threading.Thread(target=slow_query)
        t.start()
        try:
            deadline = time.monotonic() + 10
            while len(process_list.REGISTRY) < 1:
                assert time.monotonic() < deadline, "query never started"
                time.sleep(0.01)
            # the slot is taken: HTTP rejects with 429
            status, headers, _ = _http_sql(http.port,
                                           "SELECT 1 FROM slowt")
            assert status == 429 and "Retry-After" in headers
            # KILL goes THROUGH the full wire path despite the gate
            rows = process_list.REGISTRY.rows()
            assert len(rows) == 1
            status, _h, body = _http_sql(http.port,
                                         f"KILL {rows[0]['id']}")
            assert status == 200, body
            release.set()
            t.join(timeout=20)
            assert not t.is_alive()
            # in-flight work completed (ran to its end or was killed —
            # either way the slot is RELEASED and new work is admitted)
            deadline = time.monotonic() + 10
            while len(process_list.REGISTRY) > 0:
                assert time.monotonic() < deadline
                time.sleep(0.01)
            status, _h, _b = _http_sql(http.port, "SELECT 1 FROM slowt")
            assert status == 200
        finally:
            release.set()
            tpu_exec.cached_table_frame = orig
            t.join(timeout=5)

    def test_ingest_body_gate_rejects_prometheus_write(self, http):
        from greptimedb_tpu.servers import prometheus as prom_mod
        GATE.configure(max_queued_bytes=64)
        series = [prom_mod.TimeSeries(
            labels={"__name__": "m1", "host": "a"},
            samples=[(1.0, 1000)])]
        body = prom_mod.encode_write_request(series)
        blocker = threading.Event()
        inner = threading.Event()

        # hold one admitted body in flight, then push a second
        def hold():
            with GATE.admit_ingest(60):
                inner.set()
                blocker.wait(timeout=10)

        t = threading.Thread(target=hold)
        t.start()
        assert inner.wait(timeout=5)
        try:
            r = urllib.request.Request(
                f"http://127.0.0.1:{http.port}/v1/prometheus/write",
                data=body, method="POST")
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(r, timeout=10)
            assert ei.value.code == 429
            assert "Retry-After" in dict(ei.value.headers)
        finally:
            blocker.set()
            t.join(timeout=5)
        # drained: the same body is admitted
        r = urllib.request.Request(
            f"http://127.0.0.1:{http.port}/v1/prometheus/write",
            data=body, method="POST")
        with urllib.request.urlopen(r, timeout=10) as resp:
            assert resp.status == 204


# ---------------------------------------------------------------------------
# over the MySQL wire: clean server-busy error
# ---------------------------------------------------------------------------

class TestMysqlOverload:
    def test_clean_server_busy_error(self, frontend):
        from greptimedb_tpu.servers.mysql import MysqlServer
        from test_mysql import MiniMysqlClient
        srv = MysqlServer(frontend)
        srv.serve_in_background()
        try:
            GATE.configure(max_inflight=1)
            entries = _fill_registry(1)
            try:
                client = MiniMysqlClient(srv.port)
                with pytest.raises(RuntimeError) as ei:
                    client.query("SELECT 1")
                assert "overloaded" in str(ei.value)
                # the connection SURVIVES the rejection (clean error
                # packet, not a dropped socket)
                assert client.ping()
            finally:
                _drain(entries)
            # and recovers once slots free up
            assert client.query("SELECT 1")[1] == [["1"]]
        finally:
            srv.shutdown()


# ---------------------------------------------------------------------------
# monitor exemption
# ---------------------------------------------------------------------------

class TestMonitorExemption:
    def test_self_monitor_writes_pass_a_full_gate(self, frontend):
        """The scraper's greptime_private writes are never shed: a tick
        under a saturated gate still lands rows."""
        GATE.configure(max_inflight=1, max_queued_bytes=16)
        entries = _fill_registry(4)            # far past the limit
        try:
            written = frontend.self_monitor.tick()
            assert written > 0
            assert frontend.self_monitor.stats["last_error"] is None
        finally:
            _drain(entries)
        t = frontend.catalog.table("greptime", "greptime_private",
                                   "node_metrics")
        assert t is not None


# ---------------------------------------------------------------------------
# WAL group commit
# ---------------------------------------------------------------------------

class TestGroupCommit:
    def _concurrent_appends(self, tmp_path, n_threads=6, per=20):
        w = Wal(str(tmp_path), sync_on_write=True)
        errs = []

        def writer(i):
            try:
                for j in range(per):
                    w.append(i * 1000 + j, b"payload-%d-%d" % (i, j))
            except Exception as e:  # noqa: BLE001 — collected for assert
                errs.append(e)

        threads = [threading.Thread(target=writer, args=(i,))
                   for i in range(n_threads)]
        [t.start() for t in threads]
        [t.join(timeout=30) for t in threads]
        assert not errs, errs
        return w, n_threads * per

    def test_cohort_shares_fsyncs_and_loses_nothing(self, tmp_path):
        configure_group_commit(enabled=True)
        from greptimedb_tpu.common.telemetry import registry_snapshot
        before = {s[0]: s[2] for s in registry_snapshot()}
        w, n = self._concurrent_appends(tmp_path / "gc")
        after = {s[0]: s[2] for s in registry_snapshot()}
        # every record replays after the concurrent cohort storm
        assert len(list(w.read_from(0))) == n
        w.close()
        fsyncs = after.get("greptime_wal_group_commit_fsyncs_total", 0) \
            - before.get("greptime_wal_group_commit_fsyncs_total", 0)
        records = after.get("greptime_wal_group_commit_records_total", 0) \
            - before.get("greptime_wal_group_commit_records_total", 0)
        assert records == n
        # the whole point: strictly fewer shared fsyncs than records
        assert 0 < fsyncs < n

    def test_off_mode_preserves_per_append_fsync(self, tmp_path):
        configure_group_commit(enabled=False)
        w, n = self._concurrent_appends(tmp_path / "off")
        assert len(list(w.read_from(0))) == n
        w.close()

    def test_failed_group_fsync_fails_every_cohort_member(self, tmp_path):
        """An injected wal_fsync fault during the SHARED fsync must
        surface to every writer whose record it covered — acks must
        never outrun durability."""
        from greptimedb_tpu.common import failpoint as fp
        configure_group_commit(enabled=True, max_wait_us=2000)
        w = Wal(str(tmp_path / "fail"), sync_on_write=True)
        start = threading.Barrier(3)
        errs, oks = [], []

        def writer(i):
            start.wait(timeout=10)
            try:
                w.append(i, b"x" * 16)
                oks.append(i)
            except GreptimeError as e:
                errs.append(e)

        with fp.cfg("wal_fsync", "err"):
            threads = [threading.Thread(target=writer, args=(i,))
                       for i in range(3)]
            [t.start() for t in threads]
            [t.join(timeout=30) for t in threads]
        # with the failpoint armed for the whole storm, nobody acks
        assert not oks and len(errs) == 3, (oks, errs)
        # the WAL recovers: next append + sync succeed
        w.append(99, b"recovered")
        w.sync()
        assert [r[0] for r in w.read_from(99)] == [99]
        w.close()

    def test_knobs_validate(self, frontend):
        with pytest.raises(GreptimeError):
            frontend.do_query("SET wal_group_max_batch = 0")
        frontend.do_query("SET wal_group_commit = 0")
        assert group_commit_settings()[0] is False
        frontend.do_query("SET wal_group_commit = 1")
        frontend.do_query("SET wal_group_max_wait_us = 250")
        frontend.do_query("SET wal_group_max_batch = 64")
        assert group_commit_settings()[1:] == (250, 64)

    def test_region_write_overlaps_group_wait(self, tmp_path):
        """Region-level: concurrent sync_on_write writers through
        Region.write land every row exactly once with group commit on."""
        from torture import TortureRig, make_batch
        configure_group_commit(enabled=True)
        rig = TortureRig(str(tmp_path / "rig"), sync_wal=True)
        rig.create()
        batches = [make_batch(i) for i in range(8)]
        errs = []

        def writer(b):
            try:
                rig.write(b)
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=writer, args=(b,))
                   for b in batches]
        [t.start() for t in threads]
        [t.join(timeout=30) for t in threads]
        assert not errs, errs
        got = rig.region.snapshot().read_merged()
        want = {}
        for b in batches:
            want.update(b)
        assert got.num_rows == len(want)
        rig.region.close()


# ---------------------------------------------------------------------------
# ingest coalescing
# ---------------------------------------------------------------------------

class TestCoalescer:
    def test_concurrent_same_shape_requests_merge(self, frontend):
        configure_coalescer(window_ms=25)
        from greptimedb_tpu.session import QueryContext
        ctx = QueryContext()
        start = threading.Barrier(5)
        acks, errs = [], []

        def one(i):
            start.wait(timeout=10)
            try:
                n = COALESCER.ingest(
                    frontend, "co_metric",
                    {"ts": [1000 + i], "host": [f"h{i}"], "v": [float(i)]},
                    tag_columns=("host",), timestamp_column="ts", ctx=ctx)
                acks.append(n)
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(5)]
        [t.start() for t in threads]
        [t.join(timeout=30) for t in threads]
        assert not errs, errs
        assert acks == [1] * 5                 # per-request acks
        out = frontend.do_query("SELECT count(*) FROM co_metric")[0]
        assert _scalar(out) == 5
        from greptimedb_tpu.common.telemetry import registry_snapshot
        snap = {s[0]: s[2] for s in registry_snapshot()}
        assert snap.get(
            "greptime_ingest_coalesce_merged_requests_total", 0) >= 1

    def test_shared_error_reaches_every_member(self, frontend):
        """A cohort whose shared insert fails errors EVERY member —
        none of their rows are durable, none may be acked."""
        configure_coalescer(window_ms=25)
        from greptimedb_tpu.session import QueryContext
        frontend.do_query(
            "CREATE TABLE co_err (host STRING, ts TIMESTAMP TIME INDEX, "
            "v DOUBLE, PRIMARY KEY(host))")
        ctx = QueryContext()
        start = threading.Barrier(3)
        errs = []

        def one(i):
            start.wait(timeout=10)
            try:
                # 'newtag' does not exist and tags cannot be added after
                # create: the shared insert raises for the whole cohort
                COALESCER.ingest(
                    frontend, "co_err",
                    {"ts": [1000 + i], "host": ["a"], "v": [1.0],
                     "newtag": ["x"]},
                    tag_columns=("host", "newtag"),
                    timestamp_column="ts", ctx=ctx)
            except GreptimeError as e:
                errs.append(e)

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(3)]
        [t.start() for t in threads]
        [t.join(timeout=30) for t in threads]
        assert len(errs) == 3, errs
        out = frontend.do_query("SELECT count(*) FROM co_err")[0]
        assert _scalar(out) == 0

    def test_different_shapes_never_share_a_batch(self, frontend):
        """Requests whose column signatures differ stay separate, so a
        request needing a different auto-create shape cannot poison a
        stranger's ack."""
        configure_coalescer(window_ms=25)
        from greptimedb_tpu.session import QueryContext
        ctx = QueryContext()
        start = threading.Barrier(2)
        results = {}

        def narrow():
            start.wait(timeout=10)
            results["narrow"] = COALESCER.ingest(
                frontend, "co_shape",
                {"ts": [1000], "host": ["a"], "v": [1.0]},
                tag_columns=("host",), timestamp_column="ts", ctx=ctx)

        def wide():
            start.wait(timeout=10)
            try:
                results["wide"] = COALESCER.ingest(
                    frontend, "co_shape",
                    {"ts": [2000], "host": ["b"], "v": [2.0],
                     "extra": [7.0]},
                    tag_columns=("host",), timestamp_column="ts", ctx=ctx)
            except GreptimeError as e:
                results["wide_err"] = e

        t1, t2 = threading.Thread(target=narrow), \
            threading.Thread(target=wide)
        t1.start(); t2.start()
        t1.join(timeout=30); t2.join(timeout=30)
        assert results.get("narrow") == 1

    def test_disabled_coalescer_is_passthrough(self, frontend):
        configure_coalescer(window_ms=0)
        from greptimedb_tpu.session import QueryContext
        n = COALESCER.ingest(
            frontend, "co_direct", {"ts": [1], "v": [1.0]},
            tag_columns=(), timestamp_column="ts", ctx=QueryContext())
        assert n == 1
        assert COALESCER.pending_batches() == 0

    def test_http_influx_concurrent_writes_coalesce(self, frontend):
        """End to end over HTTP: concurrent line-protocol bodies for one
        measurement still ack 204 each and land every row."""
        from greptimedb_tpu.servers.http import HttpServer
        configure_coalescer(window_ms=25)
        srv = HttpServer(frontend, addr="127.0.0.1:0")
        srv.start()
        try:
            codes = []

            def write(i):
                body = (f"co_http,host=h{i} v={float(i)} "
                        f"{(1000 + i) * 1_000_000}").encode()
                r = urllib.request.Request(
                    f"http://127.0.0.1:{srv.port}/v1/influxdb/write",
                    data=body, method="POST")
                with urllib.request.urlopen(r, timeout=15) as resp:
                    codes.append(resp.status)

            threads = [threading.Thread(target=write, args=(i,))
                       for i in range(6)]
            [t.start() for t in threads]
            [t.join(timeout=30) for t in threads]
            assert codes == [204] * 6
            out = frontend.do_query("SELECT count(*) FROM co_http")[0]
            assert _scalar(out) == 6
        finally:
            srv.shutdown()


# ---------------------------------------------------------------------------
# concurrent scan fusion
# ---------------------------------------------------------------------------

class TestScanFusion:
    def _setup(self, frontend):
        frontend.do_query(
            "CREATE TABLE fuse (host STRING, ts TIMESTAMP TIME INDEX, "
            "v DOUBLE, PRIMARY KEY(host))")
        frontend.do_query(
            "INSERT INTO fuse VALUES " + ",".join(
                f"('h{i % 4}', {i * 1000}, {i * 0.5})"
                for i in range(200)))
        from greptimedb_tpu.query import tpu_exec
        # pin the device dispatch so the small table takes the resident
        # region path (the fusion site), not the CPU columnar fallback
        self._orig_note = tpu_exec._note_device_query_time
        tpu_exec._note_device_query_time = lambda dt: None
        frontend.do_query("SET tpu_dispatch_min_rows = 1")
        return tpu_exec

    def _teardown(self, tpu_exec):
        tpu_exec._note_device_query_time = self._orig_note
        tpu_exec.TPU_DISPATCH_MIN_ROWS = 131072
        tpu_exec._observed_min_dt[0] = None

    def test_fused_follower_equals_solo_scan(self, frontend):
        """The fusion differential: N concurrent identical scans all
        return exactly the solo answer, with followers adopting the
        leader's pass (counter-asserted), and EXPLAIN ANALYZE naming
        fused-follower."""
        tpu_exec = self._setup(frontend)
        try:
            q = "SELECT host, avg(v) FROM fuse GROUP BY host"
            solo = frontend.do_query(q)[0]
            solo_rows = sorted(
                map(tuple, (r for b in solo.batches for r in b.rows())))
            orig = tpu_exec._moment_frame_for_scan

            def slow(*a, **kw):
                time.sleep(0.2)        # overlap window for the cohort
                return orig(*a, **kw)

            tpu_exec._moment_frame_for_scan = slow
            from greptimedb_tpu.common.telemetry import registry_snapshot
            before = {s[0]: s[2] for s in registry_snapshot()}
            results, errs = [], []

            def one():
                try:
                    out = frontend.do_query(q)[0]
                    results.append(sorted(map(
                        tuple,
                        (r for b in out.batches for r in b.rows()))))
                except Exception as e:  # noqa: BLE001
                    errs.append(e)

            threads = [threading.Thread(target=one) for _ in range(6)]
            [t.start() for t in threads]
            [t.join(timeout=60) for t in threads]
            tpu_exec._moment_frame_for_scan = orig
            assert not errs, errs
            assert all(r == solo_rows for r in results)
            after = {s[0]: s[2] for s in registry_snapshot()}
            followers = after.get(
                "greptime_scan_fusion_follower_total", 0) - before.get(
                "greptime_scan_fusion_follower_total", 0)
            assert followers >= 1
            # EXPLAIN ANALYZE renders the adopted pass
            tpu_exec._moment_frame_for_scan = slow
            ea_rows = []

            def explain():
                out = frontend.do_query(f"EXPLAIN ANALYZE {q}")[0]
                ea_rows.append(
                    [r for b in out.batches for r in b.rows()])

            threads = [threading.Thread(target=explain)
                       for _ in range(3)]
            [t.start() for t in threads]
            [t.join(timeout=60) for t in threads]
            tpu_exec._moment_frame_for_scan = orig
            fused = [r for rows in ea_rows for r in rows
                     if "fused-follower" in str(r[0])]
            assert fused, ea_rows
        finally:
            self._teardown(tpu_exec)

    def test_write_between_scans_defeats_fusion(self, frontend):
        """Read-your-writes: a scan that starts after a write is acked
        carries a different data-state key and cannot adopt a stale
        pass."""
        tpu_exec = self._setup(frontend)
        try:
            q = "SELECT count(*) FROM fuse"
            out1 = frontend.do_query(q)[0]
            n1 = _scalar(out1)
            frontend.do_query(
                "INSERT INTO fuse VALUES ('h9', 999000, 9.9)")
            out2 = frontend.do_query(q)[0]
            assert _scalar(out2) == n1 + 1
        finally:
            self._teardown(tpu_exec)

