"""Live process list + cooperative KILL tests (ISSUE 8).

Covers the active-statement registry (common/process_list.py), its SQL
surfaces (SHOW PROCESSLIST, information_schema.processes, KILL), live
resource totals off the running statement's ExecStats collector, and
the cancellation contract: a killed streamed scan or dist scatter
terminates at the next batch boundary AND releases its pool slots (no
orphan futures), while killing an unknown/finished id is a clean user
error.
"""

import threading
import time

import numpy as np
import pytest

from greptimedb_tpu.common import failpoint, process_list
from greptimedb_tpu.common.process_list import ProcessRegistry
from greptimedb_tpu.datanode.instance import DatanodeInstance, DatanodeOptions
from greptimedb_tpu.errors import InvalidArgumentsError, QueryCancelledError
from greptimedb_tpu.frontend.instance import FrontendInstance
from greptimedb_tpu.query.stream_exec import (configure_streaming,
                                              stream_threshold_rows)


@pytest.fixture(autouse=True)
def _clean_failpoints():
    failpoint.reset()
    yield
    failpoint.reset()


@pytest.fixture()
def fe(tmp_path):
    dn = DatanodeInstance(DatanodeOptions(data_home=str(tmp_path)))
    frontend = FrontendInstance(dn)
    frontend.start()
    yield frontend
    frontend.shutdown()


def _pydict(fe, sql):
    out = fe.do_query(sql)[-1]
    return out.batches[0].to_pydict()


# ---------------------------------------------------------------------------
# registry unit behavior
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_track_register_deregister(self):
        reg = ProcessRegistry(node="test")
        with process_list.track("SELECT 1", protocol="mysql",
                                trace_id="abc") as entry:
            assert process_list.current() is entry
            # the global registry is separate from this local one; check
            # the entry row shape off the entry itself
            row = entry.row()
            assert row["query"] == "SELECT 1"
            assert row["protocol"] == "mysql"
            assert row["state"] == "running"
            assert row["trace_id"] == "abc"
        assert process_list.current() is None
        assert len(reg) == 0

    def test_kill_unknown_id_clean_error(self):
        reg = ProcessRegistry()
        with pytest.raises(InvalidArgumentsError, match="no such running"):
            reg.kill(424242)

    def test_kill_trips_check_cancelled(self):
        reg = ProcessRegistry()
        entry = reg.register("SELECT slow", "http", "", "", None)
        with process_list.install(entry):
            process_list.check_cancelled()          # not yet
            reg.kill(entry.id)
            assert entry.state() == "cancelling"
            with pytest.raises(QueryCancelledError):
                process_list.check_cancelled()
        reg.deregister(entry)
        # killing it AGAIN after it finished: clean error, not a crash
        with pytest.raises(InvalidArgumentsError):
            reg.kill(entry.id)

    def test_check_cancelled_noop_outside_statement(self):
        process_list.check_cancelled()              # no tracked statement

    def test_propagate_carries_entry_into_workers(self):
        """telemetry.propagate must carry the process entry, so a KILL
        is observable from pool workers too."""
        from greptimedb_tpu.common.runtime import parallel_map
        reg = ProcessRegistry()
        entry = reg.register("SELECT fanout", "http", "", "", None)
        reg.kill(entry.id)
        with process_list.install(entry):
            with pytest.raises(QueryCancelledError):
                list(parallel_map(
                    lambda _: process_list.check_cancelled(), [1, 2],
                    max_workers=2))
        reg.deregister(entry)


# ---------------------------------------------------------------------------
# SQL surfaces
# ---------------------------------------------------------------------------

class TestSqlSurfaces:
    def test_show_processlist_shows_itself(self, fe):
        d = _pydict(fe, "SHOW PROCESSLIST")
        assert "SHOW PROCESSLIST" in d["Info"]
        i = d["Info"].index("SHOW PROCESSLIST")
        assert d["State"][i] == "running"
        assert d["Protocol"][i] == "http"
        assert d["Trace_id"][i]

    def test_show_full_processlist_truncation(self, fe):
        filler = ", ".join(["1"] * 200)
        d = _pydict(fe, f"SHOW PROCESSLIST -- {filler}")
        row = next(q for q in d["Info"] if q.startswith("SHOW"))
        assert len(row) == 100                      # truncated
        d = _pydict(fe, f"SHOW FULL PROCESSLIST -- {filler}")
        row = next(q for q in d["Info"] if q.startswith("SHOW"))
        assert len(row) > 100                       # full text

    def test_information_schema_processes(self, fe):
        d = _pydict(fe, "SELECT id, node, query, protocol, state, "
                        "elapsed_ms, rows_scanned, bytes_read, rpcs "
                        "FROM information_schema.processes")
        assert len(d["id"]) == 1
        assert "information_schema.processes" in d["query"][0]
        assert d["state"] == ["running"]
        assert d["elapsed_ms"][0] >= 0.0

    def test_kill_unknown_id_via_sql(self, fe):
        with pytest.raises(InvalidArgumentsError, match="KILL 424242"):
            fe.do_query("KILL 424242")
        with pytest.raises(InvalidArgumentsError):
            fe.do_query("KILL QUERY 424242")        # MySQL spelling

    def test_kill_parse_errors(self, fe):
        from greptimedb_tpu.sql.parser import ParserError
        with pytest.raises(ParserError):
            fe.do_query("KILL abc")


# ---------------------------------------------------------------------------
# cooperative cancellation: streamed cold scan
# ---------------------------------------------------------------------------

class TestKillStreamedScan:
    @pytest.fixture()
    def slow_scan_fe(self, fe):
        fe.do_query(
            "CREATE TABLE cpu (host STRING, ts TIMESTAMP TIME INDEX, "
            "v DOUBLE, PRIMARY KEY(host))")
        table = fe.catalog.table("greptime", "public", "cpu")
        per = 20_000
        for chunk in range(10):   # 10 SSTs → many streamed slices
            ts = np.arange(per, dtype=np.int64) * 1000 \
                + chunk * per * 1000
            host = np.repeat(
                np.array([f"h{i}" for i in range(20)]),
                per // 20).astype(object)
            table.bulk_load({"host": host, "ts": ts,
                             "v": np.random.default_rng(chunk).random(per)})
        from greptimedb_tpu.query import stream_exec
        saved = stream_threshold_rows()
        saved_slice = stream_exec._SLICE_ROWS[0]
        # small slices: the scan must cross MANY batch boundaries so the
        # cooperative cancellation check has somewhere to fire
        configure_streaming(threshold_rows=1000, slice_rows=5000)
        yield fe
        configure_streaming(threshold_rows=saved, slice_rows=saved_slice)

    def test_kill_terminates_within_one_slice(self, slow_scan_fe):
        fe = slow_scan_fe
        fe.do_query("SET failpoint_stream_slice = 'delay(150)'")
        outcome = []

        def run():
            try:
                fe.do_query("SELECT host, max(v) FROM cpu GROUP BY host")
                outcome.append("completed")
            except QueryCancelledError:
                outcome.append("cancelled")

        t = threading.Thread(target=run)
        t.start()
        pid = live = None
        for _ in range(400):                 # await live progress facts
            rows = [r for r in process_list.REGISTRY.rows()
                    if "GROUP BY" in r["query"]]
            if rows and rows[0]["bytes_read"] > 0:
                pid, live = rows[0]["id"], rows[0]
                break
            time.sleep(0.01)
        assert pid is not None, "query never appeared in the registry"
        assert live["state"] == "running"
        t0 = time.perf_counter()
        fe.do_query(f"KILL {pid}")
        t.join(timeout=15)
        elapsed_ms = (time.perf_counter() - t0) * 1e3
        assert outcome == ["cancelled"], outcome
        # one slice boundary = one 150ms delay (+ slack for a slow box)
        assert elapsed_ms < 5000, f"took {elapsed_ms:.0f}ms after KILL"
        # gone from the view, and the id is now a clean error
        assert not any(r["id"] == pid
                       for r in process_list.REGISTRY.rows())
        with pytest.raises(InvalidArgumentsError):
            fe.do_query(f"KILL {pid}")

    def test_live_rows_scanned_progress(self, slow_scan_fe):
        """Acceptance: a slow query shows LIVE rows-scanned counts in
        the processes view while it runs, not only at the end."""
        fe = slow_scan_fe
        fe.do_query("SET failpoint_stream_slice = 'delay(100)'")
        seen = []

        def run():
            try:
                fe.do_query("SELECT host, max(v) FROM cpu GROUP BY host")
            except QueryCancelledError:
                pass

        t = threading.Thread(target=run)
        t.start()
        pid = None
        try:
            for _ in range(600):
                rows = [r for r in process_list.REGISTRY.rows()
                        if "GROUP BY" in r["query"]]
                if rows:
                    pid = rows[0]["id"]
                    if rows[0]["rows_scanned"] > 0:
                        seen.append(rows[0]["rows_scanned"])
                        break
                time.sleep(0.01)
        finally:
            if pid is not None:
                try:
                    process_list.REGISTRY.kill(pid)
                except InvalidArgumentsError:
                    pass
            t.join(timeout=15)
        assert seen and seen[0] > 0

    def test_killed_scan_releases_stream_workers(self, slow_scan_fe):
        """After a kill, the per-scan transient pool must wind down (the
        prefetched slice futures are cancelled in the loop's finally) —
        the scan thread joins promptly instead of draining every
        remaining prefetched slice."""
        fe = slow_scan_fe
        fe.do_query("SET failpoint_stream_slice = 'delay(200)'")
        t = threading.Thread(
            target=lambda: pytest.raises(
                QueryCancelledError,
                fe.do_query, "SELECT host, max(v) FROM cpu GROUP BY host"))
        t.start()
        for _ in range(400):
            rows = [r for r in process_list.REGISTRY.rows()
                    if "GROUP BY" in r["query"]]
            if rows and rows[0]["bytes_read"] > 0:
                process_list.REGISTRY.kill(rows[0]["id"])
                break
            time.sleep(0.01)
        t0 = time.perf_counter()
        t.join(timeout=20)
        assert not t.is_alive()
        # 10 SSTs × 200ms ≈ 2s serial drain; a prompt exit proves the
        # queued prefetches were cancelled, not awaited
        assert (time.perf_counter() - t0) < 2.0


# ---------------------------------------------------------------------------
# cooperative cancellation: distributed scatter-gather
# ---------------------------------------------------------------------------

class TestKillDistScatter:
    @pytest.fixture()
    def cluster(self, tmp_path):
        from greptimedb_tpu.client import LocalDatanodeClient
        from greptimedb_tpu.frontend.distributed import DistInstance
        from greptimedb_tpu.meta import MemKv, MetaClient, MetaSrv, Peer
        srv = MetaSrv(MemKv(), datanode_lease_secs=3600)
        meta = MetaClient(srv)
        datanodes, clients = {}, {}
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
        fe.do_query(
            "CREATE TABLE hashed (host STRING, ts TIMESTAMP TIME INDEX, "
            "v DOUBLE, PRIMARY KEY(host)) "
            "PARTITION BY HASH (host) PARTITIONS 8")
        fe.do_query("INSERT INTO hashed VALUES " + ", ".join(
            f"('h{i}', {1000 + i}, 1.0)" for i in range(64)))
        yield fe
        for dn in datanodes.values():
            dn.shutdown()

    def test_kill_in_flight_scatter_releases_pool(self, cluster):
        from greptimedb_tpu.common.runtime import (configure_dist_fanout,
                                                   dist_fanout,
                                                   dist_runtime)
        fe = cluster
        saved = dist_fanout()
        # serial fan-out: the second datanode's RPC sits QUEUED in the
        # shared dist pool while the first one crawls — exactly the
        # orphan-future shape the gather's finally must cancel
        configure_dist_fanout(1)
        failpoint.configure("dist_rpc", "delay(400)")
        outcome = []

        def run():
            try:
                fe.do_query("SELECT host, max(v) FROM hashed "
                            "GROUP BY host")
                outcome.append("completed")
            except QueryCancelledError:
                outcome.append("cancelled")

        t = threading.Thread(target=run)
        t.start()
        try:
            pid = None
            for _ in range(400):
                rows = [r for r in process_list.REGISTRY.rows()
                        if "GROUP BY" in r["query"]]
                if rows:
                    pid = rows[0]["id"]
                    break
                time.sleep(0.01)
            assert pid is not None
            time.sleep(0.1)            # first RPC in flight, second queued
            fe.do_query(f"KILL {pid}")
            t.join(timeout=15)
        finally:
            failpoint.configure("dist_rpc", None)
            configure_dist_fanout(saved)
        assert outcome == ["cancelled"], outcome
        # no orphan futures left occupying the shared dist pool: the
        # queue drains and fresh work gets a slot immediately
        pool = dist_runtime()
        deadline = time.time() + 5
        while pool._work_queue.qsize() and time.time() < deadline:
            time.sleep(0.02)
        assert pool._work_queue.qsize() == 0
        t0 = time.perf_counter()
        pool.submit(lambda: None).result(timeout=5)
        assert (time.perf_counter() - t0) < 1.0

    def test_dist_processes_view_counts_rpcs(self, cluster):
        fe = cluster
        fe.do_query("SELECT host, max(v) FROM hashed GROUP BY host")
        st = fe.query_engine.last_exec_stats
        assert st is not None and st.totals()["rpcs"] >= 1

    def test_dist_frontend_names_the_node(self, cluster):
        """A cluster frontend labels its processes rows 'frontend', so a
        multi-frontend operator can tell which process owns a statement
        (KILL is per-process) — and a standalone built later relabels."""
        d = cluster.do_query(
            "SELECT node FROM information_schema.processes"
        )[-1].batches[0].to_pydict()
        assert d["node"] == ["frontend"]


# ---------------------------------------------------------------------------
# satellite: SET unification across frontends
# ---------------------------------------------------------------------------

class TestSetVariableUnified:
    """`SET` of an unknown variable must behave IDENTICALLY on the
    standalone and distributed frontends: both route through
    apply_set_variable, so both raise the same InvalidArgumentsError,
    and both silently accept the wire-client compat boilerplate."""

    @pytest.fixture()
    def dist_fe(self, tmp_path):
        from greptimedb_tpu.client import LocalDatanodeClient
        from greptimedb_tpu.frontend.distributed import DistInstance
        from greptimedb_tpu.meta import MemKv, MetaClient, MetaSrv, Peer
        srv = MetaSrv(MemKv(), datanode_lease_secs=3600)
        meta = MetaClient(srv)
        dn = DatanodeInstance(DatanodeOptions(
            data_home=str(tmp_path / "dn1"), node_id=1,
            register_numbers_table=False))
        dn.start()
        srv.register_datanode(Peer(1, "dn1"))
        srv.handle_heartbeat(1)
        frontend = DistInstance(meta, {1: LocalDatanodeClient(dn)})
        yield frontend
        dn.shutdown()

    @pytest.mark.parametrize("which", ["standalone", "distributed"])
    @pytest.mark.parametrize("name", [
        "slow_query_treshold_ms",                # typo'd
        "scan_fusion", "ingest_coalesce"])       # removed switches
    def test_unknown_variable_errors_identically(self, name, which, fe,
                                                 dist_fe):
        target = fe if which == "standalone" else dist_fe
        with pytest.raises(InvalidArgumentsError,
                           match="unknown session variable"):
            target.do_query(f"SET {name} = 0")

    @pytest.mark.parametrize("which", ["standalone", "distributed"])
    def test_compat_and_known_knobs_accepted(self, which, fe, dist_fe):
        target = fe if which == "standalone" else dist_fe
        target.do_query("SET autocommit = 1")            # client compat
        target.do_query("SET extra_float_digits = 3")    # pg compat
        target.do_query("SET slow_query_threshold_ms = 0")   # real knob
        target.do_query("SET self_monitor_retention_ms = 3600000")
        from greptimedb_tpu.monitor.scraper import (configure_retention,
                                                    retention_ms)
        assert retention_ms() == 3600000
        configure_retention(7 * 24 * 3600 * 1000)
