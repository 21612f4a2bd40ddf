"""Multi-host cluster topology tests.

Two levels, mirroring the reference's distributed coverage:
- in-process, real sockets: FlightMetaServer/Client + Flight datanodes +
  PeerClientRegistry (tests-integration style)
- true multi-process: metasrv + 2 datanodes + frontend spawned via the
  CLI role subcommands, driven over HTTP (the greptime cluster quick
  start flow).
"""

import json
import os
import signal
import subprocess
import sys
import time
import urllib.parse
import urllib.request

import pytest

from greptimedb_tpu import DEFAULT_CATALOG_NAME as CAT
from greptimedb_tpu import DEFAULT_SCHEMA_NAME as SCH
from greptimedb_tpu.datanode.instance import DatanodeInstance, DatanodeOptions
from greptimedb_tpu.frontend.distributed import DistInstance
from greptimedb_tpu.meta import MetaSrv, Peer
from greptimedb_tpu.meta.flight import (
    FlightMetaClient, FlightMetaServer, PeerClientRegistry)
from greptimedb_tpu.meta.kv import FileKv, MemKv
from greptimedb_tpu.servers.flight import FlightDatanodeServer

DDL = """
CREATE TABLE dist (host STRING, ts TIMESTAMP TIME INDEX, cpu DOUBLE,
                   PRIMARY KEY(host))
PARTITION BY RANGE COLUMNS (host) (
  PARTITION r0 VALUES LESS THAN ('h5'),
  PARTITION r1 VALUES LESS THAN (MAXVALUE))
"""


def _wait_port(server, timeout=10.0):
    t0 = time.time()
    while server.port == 0 and time.time() - t0 < timeout:
        time.sleep(0.01)
    assert server.port != 0


class TestFileKv:
    def test_snapshot_roundtrip(self, tmp_path):
        path = str(tmp_path / "kv.json")
        kv = FileKv(path)
        kv.put("a", b"1")
        kv.incr("seq")
        assert FileKv(path).get("a") == b"1"
        assert FileKv(path).incr("seq") == 2

    def test_cas_persists(self, tmp_path):
        path = str(tmp_path / "kv.json")
        kv = FileKv(path)
        assert kv.compare_and_put("k", None, b"v")
        assert not FileKv(path).compare_and_put("k", None, b"w")


class TestWireMetaCluster:
    @pytest.fixture()
    def cluster(self, tmp_path):
        meta_srv = MetaSrv(MemKv())
        meta_server = FlightMetaServer(meta_srv)
        meta_server.serve_in_background()
        _wait_port(meta_server)
        meta = FlightMetaClient(meta_server.address)

        datanodes, servers = {}, {}
        for i in (1, 2):
            dn = DatanodeInstance(DatanodeOptions(
                data_home=str(tmp_path / f"dn{i}"), node_id=i,
                register_numbers_table=False))
            dn.start()
            srv = FlightDatanodeServer(dn)
            srv.serve_in_background()
            _wait_port(srv)
            meta.register(Peer(i, srv.address))
            dn.start_heartbeat(meta, interval_s=3600)
            datanodes[i] = dn
            servers[i] = srv
        fe = DistInstance(meta, PeerClientRegistry(meta))
        yield fe, datanodes
        for s in servers.values():
            s.shutdown()
        for dn in datanodes.values():
            dn.shutdown()
        meta.close()
        meta_server.shutdown()

    def test_ddl_insert_query_over_wire_meta(self, cluster):
        fe, datanodes = cluster
        fe.do_query(DDL)
        rows = ", ".join(f"('h{i}', {1000+i}, {float(i)})"
                         for i in range(10))
        n = fe.do_query(f"INSERT INTO dist VALUES {rows}")[-1]
        assert n.affected_rows == 10
        counts = sorted(
            sum(b.num_rows for b in
                dn.catalog.table(CAT, SCH, "dist").scan_batches())
            for dn in datanodes.values())
        assert counts == [5, 5]
        out = fe.do_query("SELECT count(*) AS c FROM dist")[-1]
        assert next(out.batches[0].rows())[0] == 10

    def test_registry_resolves_lazily(self, cluster):
        fe, _ = cluster
        fe.do_query(DDL)
        fe.do_query("INSERT INTO dist VALUES ('h1', 1, 1.0)")
        # a fresh frontend with an EMPTY registry must dial peers on
        # demand from meta state alone
        fe2 = DistInstance(fe.meta, PeerClientRegistry(fe.meta))
        out = fe2.do_query("SELECT sum(cpu) AS s FROM dist")[-1]
        assert next(out.batches[0].rows())[0] == 1.0


HASH_DDL = """
CREATE TABLE obs (host STRING, ts TIMESTAMP TIME INDEX, cpu DOUBLE,
                  PRIMARY KEY(host))
PARTITION BY HASH (host) PARTITIONS 8
"""


class TestClusterObservability:
    """ISSUE 6: one trace id per statement across processes, per-node
    EXPLAIN ANALYZE over the wire, and the cluster_info health view."""

    @pytest.fixture()
    def wire_cluster(self, tmp_path):
        # a lease no loaded box outlives between the set-up's one
        # heartbeat and a test's first look; expiry is probed with `now`
        meta_srv = MetaSrv(MemKv(), datanode_lease_secs=3600)
        meta_server = FlightMetaServer(meta_srv)
        meta_server.serve_in_background()
        _wait_port(meta_server)
        meta = FlightMetaClient(meta_server.address)
        datanodes, servers = {}, {}
        for i in (1, 2):
            dn = DatanodeInstance(DatanodeOptions(
                data_home=str(tmp_path / f"dn{i}"), node_id=i,
                register_numbers_table=False))
            dn.start()
            srv = FlightDatanodeServer(dn)
            srv.serve_in_background()
            _wait_port(srv)
            meta.register(Peer(i, srv.address))
            dn.start_heartbeat(meta, interval_s=3600)
            datanodes[i] = dn
            servers[i] = srv
        fe = DistInstance(meta, PeerClientRegistry(meta))
        fe.do_query(HASH_DDL)
        rows = ", ".join(f"('h{i % 4}', {1000 + i}, {float(i)})"
                         for i in range(24))
        fe.do_query(f"INSERT INTO obs VALUES {rows}")
        yield fe, meta_srv, datanodes
        for s in servers.values():
            s.shutdown()
        for dn in datanodes.values():
            dn.shutdown()
        meta.close()
        meta_server.shutdown()

    def test_one_trace_id_across_frontend_and_datanodes(
            self, wire_cluster, caplog):
        """Satellite 1: after wire propagation, a slow distributed
        statement logs the SAME trace id on the frontend and on every
        datanode it touched (datanodes used to mint their own)."""
        import logging

        from greptimedb_tpu.common import failpoint
        from greptimedb_tpu.common.telemetry import (
            set_slow_query_threshold_ms)
        fe, _, _ = wire_cluster
        sql = "SELECT host, count(*) AS c FROM obs GROUP BY host"
        # slow on purpose, not by the box's mood: a datanode that holds a
        # scan of the table and then takes new rows refreshes that scan
        # for the next statement, and the refresh sleeps past the threshold
        fe.do_query(sql)
        fe.do_query("INSERT INTO obs VALUES " + ", ".join(
            f"('h{i}', 9000, 1.0)" for i in range(8)))
        set_slow_query_threshold_ms(20)
        try:
            with caplog.at_level(logging.WARNING,
                                 logger="greptimedb_tpu.slow_query"), \
                    failpoint.cfg("scan_cache_incremental", "delay(30)"):
                fe.do_query(sql)
        finally:
            set_slow_query_threshold_ms(None)
        import re

        # this statement's lines only: the logger is the process's
        def traces(needle, mine):
            return [re.search(r"trace=(\S+)", r.getMessage()).group(1)
                    for r in caplog.records
                    if needle in r.getMessage() and mine in r.getMessage()]
        fe_traces = traces("slow query:", repr(sql))
        dn_traces = traces("slow datanode op:", "table=obs")
        assert len(fe_traces) == 1, caplog.text
        assert len(dn_traces) == 2, caplog.text    # both datanodes refresh
        assert set(dn_traces) == set(fe_traces), \
            f"trace ids diverged: fe={fe_traces} dn={dn_traces}"
        # a bare 32-hex trace id, not a whole traceparent header
        assert "-" not in fe_traces[0]

    def _analyze_rows(self, fe, sql):
        out = fe.do_query("EXPLAIN ANALYZE " + sql)[-1]
        return [r for b in out.batches for r in b.to_pylist()]

    def test_per_node_tree_sums_to_standalone(self, wire_cluster,
                                              tmp_path):
        """Satellite 3 (wire-level differential): the per-node stage
        rows of a distributed EXPLAIN ANALYZE sum — rows scanned across
        nodes — to the standalone run of the same query on the same
        data."""
        from greptimedb_tpu.frontend.instance import FrontendInstance
        fe, _, _ = wire_cluster
        sql = "SELECT host, count(*) AS c FROM obs GROUP BY host"
        rows = self._analyze_rows(fe, sql)
        node_rows = [r for r in rows
                     if r["stage"].startswith("  dn")
                     and not r["stage"].startswith("    ")]
        assert len(node_rows) == 2, [r["stage"] for r in rows]
        for r in node_rows:
            assert "dispatch=" in r["detail"]
            assert "network_ms=" in r["detail"]
        scan_rows = [r for r in rows if r["stage"] == "    scan_prep"]
        assert scan_rows, "per-node scan stages must cross the wire"
        dist_scanned = sum(r["rows"] for r in scan_rows)

        # standalone twin on identical data
        dn = DatanodeInstance(DatanodeOptions(
            data_home=str(tmp_path / "solo"),
            register_numbers_table=False))
        dn.start()
        solo = FrontendInstance(dn)
        solo.start()
        try:
            solo.do_query(
                "CREATE TABLE obs (host STRING, ts TIMESTAMP TIME INDEX,"
                " cpu DOUBLE, PRIMARY KEY(host))")
            vals = ", ".join(f"('h{i % 4}', {1000 + i}, {float(i)})"
                             for i in range(24))
            solo.do_query(f"INSERT INTO obs VALUES {vals}")
            solo_rows = self._analyze_rows(solo, sql)
        finally:
            solo.shutdown()
        solo_scanned = next(
            r["rows"] for r in solo_rows
            if r["stage"] in ("scan_prep", "scan", "decode_reduce"))
        assert dist_scanned == solo_scanned == 24

    def test_cluster_info_lease_flip_on_dead_datanode(self, wire_cluster):
        """Acceptance: all nodes alive with region counts; a datanode
        that stops heartbeating flips to expired within the lease
        window (probed with an explicit `now` — no wall-clock sleeps)."""
        fe, meta_srv, _ = wire_cluster
        out = fe.do_query(
            "SELECT peer_type, lease_state, region_count FROM "
            "information_schema.cluster_info ORDER BY peer_id")[-1]
        got = [tuple(r) for b in out.batches for r in b.rows()]
        assert got[0][:2] == ("metasrv", "leader")
        assert [g[:2] for g in got[1:]] == [("datanode", "alive")] * 2
        assert sum(g[2] for g in got[1:]) == 8     # all routed regions
        # dn2 ingests hot right up to its death...
        import time as _time
        from greptimedb_tpu.meta import DatanodeStat
        t0 = _time.time()
        meta_srv.handle_heartbeat(
            2, DatanodeStat(approximate_rows=1000), now=t0)
        meta_srv.handle_heartbeat(
            2, DatanodeStat(approximate_rows=3000), now=t0 + 2)
        hot = {n["peer_id"]: n for n in meta_srv.cluster_info(now=t0 + 2)}
        assert hot[2]["ingest_rate_rps"] > 0
        # ...then goes silent: one lease window later the view says
        # expired
        later = t0 + 2 + meta_srv.datanode_lease_secs + 1
        meta_srv.handle_heartbeat(1, now=later)    # dn1 keeps beating
        info = {n["peer_id"]: n for n in meta_srv.cluster_info(now=later)}
        assert info[1]["lease_state"] == "alive"
        assert info[2]["lease_state"] == "expired"
        assert info[2]["region_count"] == 4        # placement unchanged
        # a dead node is not ingesting: its last-known rate must not
        # read as cluster heat forever (rows stay — they are cumulative)
        assert info[2]["ingest_rate_rps"] == 0.0
        assert info[2]["approximate_rows"] == 3000

    def test_heartbeat_stats_feed_cluster_info(self, wire_cluster):
        """A stat-bearing heartbeat surfaces rows + per-region stats in
        the view, and consecutive reports yield an ingest rate."""
        fe, meta_srv, datanodes = wire_cluster
        import json as _json
        import time as _time
        from greptimedb_tpu.meta import DatanodeStat
        t0 = _time.time()
        meta_srv.handle_heartbeat(1, DatanodeStat(
            region_count=4, approximate_rows=1000,
            region_stats=[{"region": "r", "rows": 1000}]), now=t0)
        meta_srv.handle_heartbeat(1, DatanodeStat(
            region_count=4, approximate_rows=3000,
            region_stats=[{"region": "r", "rows": 3000}]), now=t0 + 2)
        info = {n["peer_id"]: n
                for n in meta_srv.cluster_info(now=t0 + 2)}
        assert info[1]["approximate_rows"] == 3000
        assert info[1]["ingest_rate_rps"] == pytest.approx(1000.0)
        assert _json.loads(info[1]["region_stats"]) == [
            {"region": "r", "rows": 3000}]


@pytest.mark.slow
class TestMultiProcessCluster:
    def _spawn(self, *argv, env):
        return subprocess.Popen(
            [sys.executable, "-m", "greptimedb_tpu.cmd.main", *argv],
            env=env, cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT)

    def _http(self, port, sql, timeout=60):
        data = urllib.parse.urlencode({"sql": sql}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/sql", data=data)
        return json.load(urllib.request.urlopen(req, timeout=timeout))

    def _wait_tcp(self, port, proc, timeout=90):
        import socket
        t0 = time.time()
        while time.time() - t0 < timeout:
            if proc.poll() is not None:
                out = proc.stdout.read().decode(errors="replace")
                raise AssertionError(f"process died:\n{out[-3000:]}")
            try:
                socket.create_connection(("127.0.0.1", port),
                                         timeout=1).close()
                return
            except OSError:
                time.sleep(0.3)
        raise AssertionError(f"port {port} never came up")

    def test_cluster_quickstart(self, tmp_path):
        import socket

        def free_port():
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            p = s.getsockname()[1]
            s.close()
            return p

        meta_p, dn1_p, dn2_p, http_p = (free_port() for _ in range(4))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        procs = []
        try:
            procs.append(self._spawn(
                "metasrv", "start", "--bind-addr", f"127.0.0.1:{meta_p}",
                "--store", str(tmp_path / "kv.json"), env=env))
            self._wait_tcp(meta_p, procs[0])
            for i, port in ((1, dn1_p), (2, dn2_p)):
                procs.append(self._spawn(
                    "datanode", "start", "--node-id", str(i),
                    "--rpc-addr", f"127.0.0.1:{port}",
                    "--metasrv-addr", f"127.0.0.1:{meta_p}",
                    "--data-home", str(tmp_path / f"dn{i}"), env=env))
            self._wait_tcp(dn1_p, procs[1])
            self._wait_tcp(dn2_p, procs[2])
            procs.append(self._spawn(
                "frontend", "start",
                "--metasrv-addr", f"127.0.0.1:{meta_p}",
                "--http-addr", f"127.0.0.1:{http_p}", env=env))
            self._wait_tcp(http_p, procs[3])

            resp = self._http(http_p, DDL)
            assert resp["code"] == 0, resp
            rows = ", ".join(f"('h{i}', {1000+i}, {float(i)})"
                             for i in range(10))
            resp = self._http(http_p, f"INSERT INTO dist VALUES {rows}")
            assert resp["code"] == 0, resp
            assert resp["output"][0]["affectedrows"] == 10
            resp = self._http(
                http_p, "SELECT host, cpu FROM dist ORDER BY host")
            assert resp["code"] == 0, resp
            got = resp["output"][0]["records"]["rows"]
            assert len(got) == 10
            assert got[0][0] == "h0"
            resp = self._http(http_p, "SELECT sum(cpu) FROM dist")
            assert resp["output"][0]["records"]["rows"] == [[45.0]]
        finally:
            for p in procs:
                p.send_signal(signal.SIGTERM)
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()


@pytest.mark.slow
class TestDurableTraceCluster:
    """ISSUE 15 acceptance drive: a REAL 4-datanode cluster (separate
    processes). A deliberately slow distributed query finishes; long
    after, ADMIN SHOW TRACE reassembles its full cross-node waterfall
    from greptime_private.trace_spans — frontend AND all touched
    datanodes under one trace id. A fast query leaves no spans, a
    KILLed query is always retained, and background_jobs shows
    datanode-side flush/compaction work with its region."""

    _spawn = TestMultiProcessCluster._spawn
    _http = TestMultiProcessCluster._http
    _wait_tcp = TestMultiProcessCluster._wait_tcp

    def _sql(self, port, sql, timeout=60):
        resp = self._http(port, sql, timeout=timeout)
        assert resp["code"] == 0, resp
        return resp

    def _rows(self, port, sql):
        out = self._sql(port, sql)["output"][0]
        return out.get("records", {}).get("rows", [])

    def test_cross_node_waterfall_survives_the_query(self, tmp_path):
        import socket
        import threading

        def free_port():
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            p = s.getsockname()[1]
            s.close()
            return p

        meta_p, http_p = free_port(), free_port()
        dn_ports = {i: free_port() for i in (1, 2, 3, 4)}
        # tail-sampling pinned for determinism: ONLY slow/error/killed/
        # balancer traces retain (no head-sample noise). 300ms keeps
        # ordinary statements fast; the "deliberately slow" query gets
        # its slowness injected via the dist_rpc delay failpoint
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   GREPTIME_TRACE_SAMPLE_RATIO="0",
                   GREPTIME_SLOW_QUERY_MS="300")
        procs = []
        try:
            procs.append(self._spawn(
                "metasrv", "start", "--bind-addr", f"127.0.0.1:{meta_p}",
                "--store", str(tmp_path / "kv.json"), env=env))
            self._wait_tcp(meta_p, procs[0])
            for i, port in dn_ports.items():
                procs.append(self._spawn(
                    "datanode", "start", "--node-id", str(i),
                    "--rpc-addr", f"127.0.0.1:{port}",
                    "--metasrv-addr", f"127.0.0.1:{meta_p}",
                    # one shared data home (the elastic deployment
                    # shape) so the migrate half of the drive can hand
                    # a region between nodes; WAL/fence state is
                    # node-scoped inside it
                    "--data-home", str(tmp_path / "shared"), env=env))
            for i, port in dn_ports.items():
                self._wait_tcp(port, procs[i])
            procs.append(self._spawn(
                "frontend", "start",
                "--metasrv-addr", f"127.0.0.1:{meta_p}",
                "--http-addr", f"127.0.0.1:{http_p}", env=env))
            self._wait_tcp(http_p, procs[-1])

            self._sql(http_p, """
CREATE TABLE tr (host STRING, ts TIMESTAMP TIME INDEX, v DOUBLE,
                 PRIMARY KEY(host))
PARTITION BY HASH (host) PARTITIONS 8""")
            for b in range(4):
                vals = ", ".join(
                    f"('h{j % 40}', {100_000 + b * 1000 + j}, {float(j)})"
                    for j in range(500))
                self._sql(http_p, f"INSERT INTO tr VALUES {vals}")

            # --- the deliberately slow distributed query: every dist
            # RPC pays an injected 400ms hop, so the statement clears
            # the 300ms slow threshold deterministically ---
            self._sql(http_p, "SET failpoint_dist_rpc = 'delay(400)'")
            rows = self._rows(http_p, "SELECT host, avg(v), count(*) "
                                      "FROM tr GROUP BY host")
            assert len(rows) == 40
            self._sql(http_p, "SET failpoint_dist_rpc = 'off'")

            # the query is DONE. Reassemble its waterfall from the
            # durable store: the SHOW TRACE ping piggybacks verdicts to
            # every datanode and collects their buffered spans
            wf = self._rows(http_p, "ADMIN SHOW TRACE 'last'")
            spans = [r[0].strip() for r in wf]
            nodes = {r[1] for r in wf}
            assert any("execute_stmt" in s for s in spans)
            assert "frontend" in nodes
            touched = {n for n in nodes if n.startswith("dn")}
            assert touched == {"dn1", "dn2", "dn3", "dn4"}, nodes
            # one trace id across every process: the stored rows agree
            tid_rows = self._rows(
                http_p, "SELECT DISTINCT trace_id FROM "
                        "information_schema.trace_spans WHERE "
                        "span_name IN ('dn_region_moments', 'dn_scan')")
            assert len(tid_rows) == 1
            tid = tid_rows[0][0]
            node_rows = self._rows(
                http_p, f"SELECT DISTINCT node FROM information_schema"
                        f".trace_spans WHERE trace_id = '{tid}'")
            got_nodes = {r[0] for r in node_rows}
            assert {"frontend", "dn1", "dn2", "dn3", "dn4"} <= got_nodes

            # --- a fast query leaves no spans ---
            before = self._rows(http_p, "SELECT count(*) FROM "
                                        "information_schema.trace_spans"
                                        )[0][0]
            self._sql(http_p, "SELECT 1")
            time.sleep(0.2)
            after = self._rows(http_p, "SELECT count(*) FROM "
                                       "information_schema.trace_spans"
                                       )[0][0]
            assert after == before   # nothing new from SELECT 1

            # --- a KILLed query is always retained ---
            self._sql(http_p, "SET failpoint_dist_rpc = 'delay(2000)'")
            killed = {}

            def victim():
                try:
                    self._http(http_p,
                               "SELECT host, sum(v) FROM tr "
                               "GROUP BY host", timeout=120)
                except Exception as e:  # noqa: BLE001
                    killed["err"] = e
            t = threading.Thread(target=victim)
            t.start()
            pid = None
            t0 = time.time()
            while pid is None and time.time() - t0 < 30:
                for r in self._rows(http_p,
                                    "SELECT id, query FROM "
                                    "information_schema.processes"):
                    if "sum(v)" in r[1]:
                        pid = r[0]
                time.sleep(0.1)
            assert pid is not None, "victim never registered"
            self._sql(http_p, f"KILL {pid}")
            t.join(60)
            self._sql(http_p, "SET failpoint_dist_rpc = 'off'")
            cancelled = self._rows(
                http_p, "SELECT count(*) FROM information_schema."
                        "trace_spans WHERE status = 'cancelled'")
            assert cancelled[0][0] >= 1

            # --- background_jobs shows datanode work with regions ---
            self._sql(http_p, "ADMIN FLUSH TABLE tr")
            jobs = self._rows(
                http_p, "SELECT kind, region, node, state FROM "
                        "information_schema.background_jobs "
                        "WHERE kind = 'flush'")
            assert jobs, "no flush jobs visible cluster-wide"
            assert any(r[2].startswith("dn") and r[1] for r in jobs)

            # --- balancer op steps: jobs on the METASRV process are
            # merged into the view, and the op's trace (always
            # retained) lands in trace_spans via the meta-RPC export ---
            owner = self._rows(
                http_p, "SELECT peer_id FROM information_schema."
                        "region_peers WHERE region_number = 0")[0][0]
            target = next(i for i in (1, 2, 3, 4) if i != owner)
            self._sql(http_p,
                      f"ADMIN MIGRATE REGION tr 0 TO {target}")
            t0 = time.time()
            bal = []
            while time.time() - t0 < 60:
                bal = self._rows(
                    http_p, "SELECT kind, node, state FROM "
                            "information_schema.background_jobs "
                            "WHERE kind = 'balancer_op'")
                if any(r[1] == "metasrv" for r in bal):
                    break
                time.sleep(0.5)
            assert any(r[1] == "metasrv" for r in bal), bal
            t0 = time.time()
            stored = []
            while time.time() - t0 < 60 and not stored:
                stored = self._rows(
                    http_p, "SELECT count(*) FROM information_schema."
                            "trace_spans WHERE node = 'metasrv' AND "
                            "span_name = 'job_balancer_op'")
                if stored and stored[0][0] > 0:
                    break
                stored = []
                time.sleep(0.5)
            assert stored and stored[0][0] > 0, \
                "metasrv balancer trace never reached trace_spans"
        finally:
            for p in procs:
                p.send_signal(signal.SIGTERM)
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()


@pytest.mark.slow
class TestElasticCluster:
    """ISSUE 9 acceptance drive: a REAL 4-datanode cluster (separate
    processes over a shared object store) under sustained ingest —
    ADMIN MIGRATE REGION completes with zero acked-row loss/duplication,
    kill -9 of a datanode triggers automatic re-placement while queries
    keep answering, and region_peers/cluster_info reflect it all."""

    _spawn = TestMultiProcessCluster._spawn
    _http = TestMultiProcessCluster._http
    _wait_tcp = TestMultiProcessCluster._wait_tcp

    def _sql(self, port, sql, timeout=60):
        resp = self._http(port, sql, timeout=timeout)
        assert resp["code"] == 0, resp
        return resp

    def _rows(self, port, sql):
        return self._sql(port, sql)["output"][0]["records"]["rows"]

    def _wait_until(self, fn, timeout=60, what="condition"):
        t0 = time.time()
        last = None
        while time.time() - t0 < timeout:
            try:
                last = fn()
                if last:
                    return last
            except Exception as e:  # noqa: BLE001 — polled condition
                last = e            # may race server restarts
            time.sleep(0.5)
        raise AssertionError(f"{what} never held (last={last!r})")

    def test_migrate_and_kill_under_ingest(self, tmp_path):
        import socket
        import threading

        def free_port():
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            p = s.getsockname()[1]
            s.close()
            return p

        meta_p, http_p = free_port(), free_port()
        dn_ports = {i: free_port() for i in (1, 2, 3, 4)}
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        shared_home = str(tmp_path / "shared")
        procs, dn_procs = [], {}
        try:
            procs.append(self._spawn(
                "metasrv", "start", "--bind-addr", f"127.0.0.1:{meta_p}",
                "--store", str(tmp_path / "kv.json"),
                "--failover-interval", "0.5",
                "--datanode-lease-secs", "2", env=env))
            self._wait_tcp(meta_p, procs[0])
            for i, port in dn_ports.items():
                p = self._spawn(
                    "datanode", "start", "--node-id", str(i),
                    "--rpc-addr", f"127.0.0.1:{port}",
                    "--metasrv-addr", f"127.0.0.1:{meta_p}",
                    "--heartbeat-interval", "0.5",
                    # ONE shared data home = shared object store; WAL +
                    # control state are node-scoped inside it
                    "--data-home", shared_home, env=env)
                procs.append(p)
                dn_procs[i] = p
            for i, port in dn_ports.items():
                self._wait_tcp(port, dn_procs[i])
            procs.append(self._spawn(
                "frontend", "start",
                "--metasrv-addr", f"127.0.0.1:{meta_p}",
                "--http-addr", f"127.0.0.1:{http_p}", env=env))
            self._wait_tcp(http_p, procs[-1])

            self._sql(http_p, """
CREATE TABLE el (host STRING, ts TIMESTAMP TIME INDEX, v DOUBLE,
                 PRIMARY KEY(host))
PARTITION BY RANGE COLUMNS (host) (
  PARTITION r0 VALUES LESS THAN ('h3'),
  PARTITION r1 VALUES LESS THAN ('h6'),
  PARTITION r2 VALUES LESS THAN ('h9'),
  PARTITION r3 VALUES LESS THAN (MAXVALUE))""")

            acked = set()
            acked_lock = threading.Lock()
            stop = threading.Event()

            def ingest():
                n = 0
                while not stop.is_set():
                    n += 1
                    batch = [(f"h{j}", 10_000 + n * 10 + j)
                             for j in range(10)]
                    vals = ", ".join(f"('{h}', {ts}, 1.0)"
                                     for h, ts in batch)
                    try:
                        self._sql(http_p,
                                  f"INSERT INTO el VALUES {vals}",
                                  timeout=30)
                        with acked_lock:
                            acked.update(batch)
                    except Exception:  # noqa: BLE001 — unacked writes
                        pass           # are legal during the fault
                    time.sleep(0.05)

            t = threading.Thread(target=ingest, daemon=True)
            t.start()
            try:
                # --- ADMIN MIGRATE under sustained ingest ---
                peers = self._rows(
                    http_p,
                    "SELECT region_number, peer_id FROM "
                    "information_schema.region_peers")
                assert len(peers) == 4
                src = next(p for r, p in peers if r == 0)
                dst = next(i for i in (1, 2, 3, 4) if i != src)
                out = self._rows(
                    http_p, f"ADMIN MIGRATE REGION el 0 TO {dst}")
                assert out[0][1] == "migrate"
                self._wait_until(
                    lambda: [r for r in self._rows(
                        http_p,
                        "SELECT region_number, peer_id, operation FROM "
                        "information_schema.region_peers")
                        if r[0] == 0][0][1] == dst and
                    [r for r in self._rows(
                        http_p,
                        "SELECT region_number, operation FROM "
                        "information_schema.region_peers")
                        if r[0] == 0][0][1] is None,
                    what="migration commit")

                # --- kill -9 a datanode hosting region 3 ---
                placement = {r[0]: r[1] for r in self._rows(
                    http_p,
                    "SELECT region_number, peer_id FROM "
                    "information_schema.region_peers")}
                victim = placement[3]
                victim_regions = [rn for rn, p in placement.items()
                                  if p == victim]
                dn_procs[victim].kill()      # SIGKILL, no shutdown
                self._wait_until(
                    lambda: all(
                        r[1] != victim for r in self._rows(
                            http_p,
                            "SELECT region_number, peer_id FROM "
                            "information_schema.region_peers")),
                    timeout=90, what="automatic re-placement")
                # cluster_info marks the victim non-alive
                states = {r[0]: r[1] for r in self._rows(
                    http_p,
                    "SELECT peer_id, lease_state FROM "
                    "information_schema.cluster_info")}
                assert states[victim] in ("expired", "suspect",
                                          "unknown")
                # queries answer on the re-placed layout
                assert self._rows(
                    http_p, "SELECT count(*) FROM el")[0][0] > 0
            finally:
                stop.set()
                t.join(timeout=60)

            # --- integrity: every acked row exactly once ---
            # Rows that ACKED on the victim but lived only in its WAL
            # are the documented failover loss domain (RFC region-fault-
            # tolerance: re-adoption is at last-flushed state), so the
            # check excludes the victim-hosted ranges; every OTHER
            # region's acked rows must be present exactly once.
            RANGES = {0: (None, "h3"), 1: ("h3", "h6"),
                      2: ("h6", "h9"), 3: ("h9", None)}

            def in_victim(key):
                h = key[0]
                return any(
                    (lo is None or h >= lo) and (hi is None or h < hi)
                    for lo, hi in (RANGES[rn] for rn in victim_regions))

            def settled():
                rows = self._rows(http_p, "SELECT host, ts FROM el")
                keys = [tuple(r) for r in rows]
                assert len(keys) == len(set(keys)), "duplicated rows"
                with acked_lock:
                    missing = {k for k in acked - set(keys)
                               if not in_victim(k)}
                return not missing

            self._wait_until(settled, timeout=60,
                             what="acked-row integrity")
        finally:
            for p in procs:
                if p.poll() is None:
                    p.send_signal(signal.SIGTERM)
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()


@pytest.mark.slow
class TestReplicaCluster:
    """ISSUE 19 acceptance drive: a REAL 4-datanode cluster (separate
    processes over a shared object store, WAL fsync-on-ack). ADMIN ADD
    REPLICA attaches a continuously-replicated follower; kill -9 of the
    region leader under sustained acked sync ingest promotes the
    caught-up follower with ZERO acked-row loss/duplication, and
    SET read_replica reads answer before and after the promotion."""

    _spawn = TestMultiProcessCluster._spawn
    _http = TestMultiProcessCluster._http
    _wait_tcp = TestMultiProcessCluster._wait_tcp
    _sql = TestElasticCluster._sql
    _rows = TestElasticCluster._rows
    _wait_until = TestElasticCluster._wait_until

    def test_kill_leader_under_sync_ingest_zero_acked_loss(
            self, tmp_path):
        import socket
        import threading

        def free_port():
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            p = s.getsockname()[1]
            s.close()
            return p

        LEASE_S = 2.0
        meta_p, http_p = free_port(), free_port()
        dn_ports = {i: free_port() for i in (1, 2, 3, 4)}
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        shared_home = str(tmp_path / "shared")
        procs, dn_procs = [], {}
        try:
            procs.append(self._spawn(
                "metasrv", "start", "--bind-addr", f"127.0.0.1:{meta_p}",
                "--store", str(tmp_path / "kv.json"),
                "--failover-interval", "0.5",
                "--datanode-lease-secs", str(LEASE_S), env=env))
            self._wait_tcp(meta_p, procs[0])
            for i, port in dn_ports.items():
                p = self._spawn(
                    "datanode", "start", "--node-id", str(i),
                    "--rpc-addr", f"127.0.0.1:{port}",
                    "--metasrv-addr", f"127.0.0.1:{meta_p}",
                    "--heartbeat-interval", "0.5",
                    # fsync before every ack: an acked row is durable in
                    # the leader's node-scoped WAL on the shared home,
                    # where promotion salvage can reach it after SIGKILL
                    "--wal-sync-on-write",
                    "--data-home", shared_home, env=env)
                procs.append(p)
                dn_procs[i] = p
            for i, port in dn_ports.items():
                self._wait_tcp(port, dn_procs[i])
            procs.append(self._spawn(
                "frontend", "start",
                "--metasrv-addr", f"127.0.0.1:{meta_p}",
                "--http-addr", f"127.0.0.1:{http_p}", env=env))
            self._wait_tcp(http_p, procs[-1])

            self._sql(http_p, """
CREATE TABLE rt (host STRING, ts TIMESTAMP TIME INDEX, v DOUBLE,
                 PRIMARY KEY(host))""")

            def placement():
                return {
                    (r[0], r[1]): (r[2], r[3]) for r in self._rows(
                        http_p,
                        "SELECT peer_id, is_leader, status, "
                        "replicated_seq FROM "
                        "information_schema.region_peers WHERE "
                        "table_name = 'greptime.public.rt'")}

            leader = next(p for (p, is_l) in placement() if is_l == "Yes")
            follower = next(i for i in (1, 2, 3, 4) if i != leader)
            self._sql(http_p,
                      f"ADMIN ADD REPLICA rt 0 TO {follower}")
            self._wait_until(
                lambda: placement().get((follower, "No"),
                                        ("", None))[0] == "ALIVE",
                what="replica bootstrap")

            # bounded-staleness replica reads answer BEFORE promotion
            self._sql(http_p, "SET read_replica = 'follower'")
            self._sql(http_p, "SET replica_max_lag_ms = 60000")
            self._sql(http_p, "INSERT INTO rt VALUES ('h0', 1000, 1.0)")
            self._wait_until(
                lambda: all(
                    self._rows(http_p,
                               "SELECT count(*) FROM rt")[0][0] >= 1
                    for _ in range(4)),
                what="replica-mode reads before promotion")

            acked = set()
            acked_lock = threading.Lock()
            stop = threading.Event()

            def ingest():
                n = 0
                while not stop.is_set():
                    n += 1
                    batch = [(f"h{j}", 10_000 + n * 10 + j)
                             for j in range(10)]
                    vals = ", ".join(f"('{h}', {ts}, 1.0)"
                                     for h, ts in batch)
                    try:
                        self._sql(http_p,
                                  f"INSERT INTO rt VALUES {vals}",
                                  timeout=30)
                        with acked_lock:
                            acked.update(batch)
                    except Exception:  # noqa: BLE001 — unacked writes
                        pass           # are legal during the fault
                    time.sleep(0.05)

            t = threading.Thread(target=ingest, daemon=True)
            t.start()
            try:
                # let acked sync writes accumulate on the leader, with
                # the shipper streaming them to the follower
                self._wait_until(
                    lambda: len(acked) >= 100,
                    what="sustained acked ingest")
                t_kill = time.time()
                dn_procs[leader].kill()       # SIGKILL, no shutdown
                # meta detects the lost lease and promotes the (only,
                # hence most-caught-up) follower via the atomic
                # route-commit path; queries keep answering throughout
                self._wait_until(
                    lambda: placement().get((follower, "Yes"),
                                            ("", None))[0] == "ALIVE",
                    timeout=60, what="follower promotion")
                handoff_s = time.time() - t_kill
                # detection is bounded by the lease window; the full
                # handoff adds salvage/replay + heartbeat cadence slack
                assert handoff_s < 10 * LEASE_S, handoff_s
                # replica-mode reads still answer AFTER promotion (the
                # pool degrades to the new leader when no follower is
                # attached)
                assert self._rows(
                    http_p, "SELECT count(*) FROM rt")[0][0] > 0
            finally:
                stop.set()
                t.join(timeout=60)

            # post-promotion liveness: new writes ack through the
            # promoted leader
            self._sql(http_p,
                      "INSERT INTO rt VALUES ('h_post', 99000, 1.0)")

            # --- integrity: EVERY acked row exactly once — the kill -9
            # loss domain is empty because acks waited on fsync and
            # promotion salvaged the dead leader's WAL tail ---
            self._sql(http_p, "SET read_replica = 'leader'")

            def settled():
                rows = self._rows(http_p, "SELECT host, ts FROM rt")
                keys = [tuple(r) for r in rows]
                assert len(keys) == len(set(keys)), "duplicated rows"
                with acked_lock:
                    missing = acked - set(keys)
                return not missing

            self._wait_until(settled, timeout=60,
                             what="zero acked-row loss")
        finally:
            for p in procs:
                if p.poll() is None:
                    p.send_signal(signal.SIGTERM)
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()


class TestDistributedIngest:
    """Auto create/alter ingest through a distributed frontend (the
    HTTP/Influx/OpenTSDB handler path on a cluster router)."""

    @pytest.fixture()
    def fe(self, tmp_path):
        from greptimedb_tpu.client import LocalDatanodeClient
        from greptimedb_tpu.meta import MetaClient
        datanodes, clients = {}, {}
        srv = MetaSrv(MemKv())
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

    def test_auto_create_and_insert(self, fe):
        n = fe.handle_row_insert(
            "autodist",
            {"host": ["a", "b"], "greptime_timestamp": [1, 2],
             "v": [1.0, 2.0]}, tag_columns=["host"])
        assert n == 2
        out = fe.do_query("SELECT count(*) AS c FROM autodist")[-1]
        assert next(out.batches[0].rows())[0] == 2

    def test_auto_alter_adds_field(self, fe):
        fe.handle_row_insert(
            "evolving", {"host": ["a"], "greptime_timestamp": [1],
                         "v": [1.0]}, tag_columns=["host"])
        n = fe.handle_row_insert(
            "evolving", {"host": ["a"], "greptime_timestamp": [2],
                         "v": [2.0], "extra": [7.5]}, tag_columns=["host"])
        assert n == 1
        out = fe.do_query("SELECT sum(extra) AS s FROM evolving")[-1]
        assert next(out.batches[0].rows())[0] == 7.5

    def test_new_tag_rejected(self, fe):
        from greptimedb_tpu.errors import InvalidArgumentsError
        fe.handle_row_insert(
            "tagged", {"host": ["a"], "greptime_timestamp": [1],
                       "v": [1.0]}, tag_columns=["host"])
        with pytest.raises(InvalidArgumentsError, match="tag"):
            fe.handle_row_insert(
                "tagged", {"host": ["a"], "dc": ["x"],
                           "greptime_timestamp": [2], "v": [2.0]},
                tag_columns=["host", "dc"])


class TestDistributedLockAndElection:
    """Reference: meta-srv/src/lock/ + election/etcd.rs — KV-lease based."""

    def test_lock_mutual_exclusion(self):
        from greptimedb_tpu.meta.lock import DistributedLock
        kv = MemKv()
        a = DistributedLock(kv, "ddl", holder="a")
        b = DistributedLock(kv, "ddl", holder="b")
        assert a.try_acquire()
        assert not b.try_acquire()
        assert a.try_acquire()            # re-entrant renewal
        a.release()
        assert b.try_acquire()

    def test_expired_lease_taken_over(self):
        from greptimedb_tpu.meta.lock import DistributedLock
        kv = MemKv()
        a = DistributedLock(kv, "x", holder="a", lease_secs=5)
        b = DistributedLock(kv, "x", holder="b", lease_secs=5)
        t0 = time.time()
        assert a.try_acquire(now=t0)
        assert not b.try_acquire(now=t0 + 2)
        assert b.try_acquire(now=t0 + 6)  # a's lease expired
        assert a.holder_of(now=t0 + 7) == "b"

    def test_stale_release_does_not_break_new_holder(self):
        # release() must be compare-and-delete: after a's lease expires and
        # b takes over, a's late release must NOT delete b's lock
        from greptimedb_tpu.meta.lock import DistributedLock
        kv = MemKv()
        a = DistributedLock(kv, "x", holder="a", lease_secs=5)
        b = DistributedLock(kv, "x", holder="b", lease_secs=5)
        t0 = time.time()
        assert a.try_acquire(now=t0)
        assert b.try_acquire(now=t0 + 6)   # takeover after expiry
        assert not a.release()             # stale holder: no-op
        assert b.holder_of(now=t0 + 7) == "b"

    def test_compare_and_delete_atomicity(self):
        kv = MemKv()
        kv.put("k", b"v1")
        assert not kv.compare_and_delete("k", b"other")
        assert kv.get("k") == b"v1"
        assert kv.compare_and_delete("k", b"v1")
        assert kv.get("k") is None

    def test_context_manager(self):
        from greptimedb_tpu.meta.lock import DistributedLock
        kv = MemKv()
        with DistributedLock(kv, "cm", holder="a") as lock:
            assert lock.holder_of() == "a"
        assert DistributedLock(kv, "cm", holder="b").try_acquire()

    def test_election_single_leader(self):
        from greptimedb_tpu.meta.lock import Election
        kv = MemKv()
        e1 = Election(kv, "meta-1")
        e2 = Election(kv, "meta-2")
        assert e1.campaign_once()
        assert not e2.campaign_once()
        assert e1.is_leader and not e2.is_leader
        assert e2.leader() == "meta-1"

    def test_election_failover_on_lease_expiry(self):
        from greptimedb_tpu.meta.lock import Election
        kv = MemKv()
        e1 = Election(kv, "meta-1", lease_secs=5)
        e2 = Election(kv, "meta-2", lease_secs=5)
        t0 = time.time()
        assert e1.campaign_once(now=t0)
        # leader dies; challenger wins after the lease lapses
        assert not e2.campaign_once(now=t0 + 2)
        assert e2.campaign_once(now=t0 + 6)
        assert e2.leader() == "meta-2"
