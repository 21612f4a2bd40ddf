"""Differential matrix for the one plan IR (ISSUE 16).

Every front end lowers onto the same columnar plan (query/ir.py), so
the answers must agree across execution shapes:

- PromQL instant + range aggregates: the lowered moment-frame path vs
  the row path (numeric tolerance — the row path computes on device in
  float32 and quantizes to 6 significant digits, the lowered path
  finalizes in host float64);
- standalone vs in-process 4-datanode vs real-Flight sockets, over
  hash- AND range-partitioned tables: exact aggregates byte-identical
  (both sides fold the same f64 moment frames);
- flow folds (including avg) through the IR vs the host reduce;
- plan-codec version skew: an old datanode rejects a plan carrying a
  moment op it does not know, and the frontend degrades to the raw
  path — never a wrong answer.
"""

import logging
import time

import numpy as np
import pytest

from greptimedb_tpu.client import DatanodeClient, LocalDatanodeClient
from greptimedb_tpu.datanode import DatanodeInstance, DatanodeOptions
from greptimedb_tpu.datatypes.record_batch import pretty_print
from greptimedb_tpu.errors import UnsupportedError
from greptimedb_tpu.frontend import FrontendInstance
from greptimedb_tpu.frontend.distributed import DistInstance, DistTable
from greptimedb_tpu.meta import MemKv, MetaClient, MetaSrv, Peer
from greptimedb_tpu.query import agg_plan, tpu_exec
from greptimedb_tpu.session import QueryContext

# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

HASH_PART = " PARTITION BY HASH (host) PARTITIONS 8"
RANGE_PART = (" PARTITION BY RANGE COLUMNS (host) ("
              "PARTITION r0 VALUES LESS THAN ('h2'), "
              "PARTITION r1 VALUES LESS THAN ('h4'), "
              "PARTITION r2 VALUES LESS THAN (MAXVALUE))")

DDL = ("CREATE TABLE ctr (host STRING, dc STRING, ts TIMESTAMP TIME "
       "INDEX, val DOUBLE, PRIMARY KEY(host, dc))")


def _seed_rows():
    """Deterministic counter-ish series with gaps and resets."""
    rows = []
    rng = np.random.default_rng(11)
    for h in range(6):
        v = 0.0
        for i in range(80):
            if rng.random() < 0.2:
                continue                      # gap
            v += float(rng.integers(1, 9))
            if rng.random() < 0.06:
                v = 0.0                       # counter reset
            rows.append(f"('h{h}', 'dc{h % 2}', {i * 10_000}, {v})")
    return ",".join(rows)


@pytest.fixture()
def fe(tmp_path):
    inst = FrontendInstance(DatanodeInstance(
        DatanodeOptions(data_home=str(tmp_path / "sa"))))
    inst.start()
    inst.do_query(DDL)
    inst.do_query("INSERT INTO ctr VALUES " + _seed_rows())
    yield inst
    inst.shutdown()


def _mk_cluster(tmp_path, n, part):
    datanodes, clients = {}, {}
    srv = MetaSrv(MemKv(), datanode_lease_secs=3600)
    meta = MetaClient(srv)
    for i in range(1, n + 1):
        dn = DatanodeInstance(DatanodeOptions(
            data_home=str(tmp_path / f"dn{i}"), node_id=i,
            register_numbers_table=False))
        dn.start()
        datanodes[i] = dn
        clients[i] = LocalDatanodeClient(dn)
        srv.register_datanode(Peer(i, f"dn{i}"))
        srv.handle_heartbeat(i)
    fe = DistInstance(meta, clients)
    fe.do_query(DDL + part)
    fe.do_query("INSERT INTO ctr VALUES " + _seed_rows())
    return fe, datanodes


QUERIES = [
    "sum by (host) (rate(ctr[1m]))",
    "sum by (dc) (increase(ctr[1m]))",
    "sum (delta(ctr[1m]))",
    "avg by (host) (ctr)",
    "min by (host) (ctr{host!='h1'})",
    "count (sum_over_time(ctr[1m]))",
    "max by (host) (max_over_time(ctr{dc='dc0'}[1m]))",
    "sum by (host) (count_over_time(ctr[1m]))",
    "avg by (host) (avg_over_time(ctr[1m]))",
    "sum by (host) (last_over_time(ctr[1m]))",
    "sum by (host) (rate(ctr[1m] offset 30s))",
]
SPAN = (0, 790_000, 60_000)


def _vec(inst, q, span=SPAN):
    v, steps = inst.promql_engine().query_range(
        q, span[0], span[1], span[2], QueryContext())
    out = {}
    for i, lbl in enumerate(v.labels):
        out[tuple(sorted(lbl.items()))] = (v.values[i], v.ok[i])
    return out


def _tql(inst, q, span=SPAN):
    return pretty_print(inst.do_query(
        f"TQL EVAL ({span[0] // 1000}, {span[1] // 1000}, "
        f"'{span[2] // 1000}s') {q}")[0].batches)


def _assert_close(a, b, rtol):
    assert set(a) == set(b), (set(a) ^ set(b))
    for k in a:
        va, oka = a[k]
        vb, okb = b[k]
        assert np.array_equal(oka, okb), k
        assert np.allclose(np.where(oka, va, 0.0),
                           np.where(okb, vb, 0.0),
                           rtol=rtol, atol=1e-9), k


# ---------------------------------------------------------------------------
# PromQL: lowered vs row path (standalone)
# ---------------------------------------------------------------------------

class TestLoweredVsRowPath:
    @pytest.mark.parametrize("q", QUERIES)
    def test_differential(self, fe, q, monkeypatch):
        monkeypatch.setattr(tpu_exec, "TPU_DISPATCH_MIN_ROWS", 10**9)
        row = _vec(fe, q)
        monkeypatch.setattr(tpu_exec, "TPU_DISPATCH_MIN_ROWS", 0)
        lowered = _vec(fe, q)
        # row path: device float32 + 6-significant-digit quantization;
        # lowered path: host float64 moment finalization
        _assert_close(row, lowered, rtol=2e-5)

    def test_row_path_shapes_untouched(self, fe, monkeypatch):
        """Non-lowerable shapes give byte-identical answers whatever the
        dispatch floor says (they never lower)."""
        for q in ["topk(2, ctr)", "rate(ctr[2m])",      # non-tumbling
                  "stddev by (host) (ctr)",
                  "sum by (host) (rate(ctr{host=~'h[12]'}[1m]))"]:
            monkeypatch.setattr(tpu_exec, "TPU_DISPATCH_MIN_ROWS", 10**9)
            row = _vec(fe, q)
            monkeypatch.setattr(tpu_exec, "TPU_DISPATCH_MIN_ROWS", 0)
            assert _vec(fe, q).keys() == row.keys(), q


# ---------------------------------------------------------------------------
# PromQL: distributed vs standalone (exact aggs byte-identical)
# ---------------------------------------------------------------------------

class TestDistVsStandalone:
    @pytest.mark.parametrize("part", [HASH_PART, RANGE_PART],
                             ids=["hash", "range"])
    def test_in_process_4dn(self, fe, tmp_path, part, monkeypatch):
        monkeypatch.setattr(tpu_exec, "TPU_DISPATCH_MIN_ROWS", 0)
        dist, datanodes = _mk_cluster(tmp_path, 4, part)
        try:
            for q in QUERIES:
                assert _tql(fe, q) == _tql(dist, q), q
        finally:
            for dn in datanodes.values():
                dn.shutdown()

    def test_raw_pull_knob_still_correct(self, fe, tmp_path, monkeypatch):
        """SET dist_partial_agg = 0 forces the raw-pull row path on the
        distributed side; answers stay correct (f32 tolerance vs the
        lowered standalone)."""
        monkeypatch.setattr(tpu_exec, "TPU_DISPATCH_MIN_ROWS", 0)
        dist, datanodes = _mk_cluster(tmp_path, 4, HASH_PART)
        try:
            dist.do_query("SET dist_partial_agg = 0")
            q = "sum by (host) (rate(ctr[1m]))"
            _assert_close(_vec(fe, q), _vec(dist, q), rtol=2e-5)
        finally:
            agg_plan._PARTIAL_PUSHDOWN[0] = True
            for dn in datanodes.values():
                dn.shutdown()


# ---------------------------------------------------------------------------
# PromQL over real Flight sockets (was: silently empty)
# ---------------------------------------------------------------------------

@pytest.fixture()
def flight_cluster(tmp_path):
    from greptimedb_tpu.client.flight import FlightDatanodeClient
    from greptimedb_tpu.servers.flight import FlightDatanodeServer
    datanodes, servers, clients = {}, {}, {}
    srv = MetaSrv(MemKv(), datanode_lease_secs=3600)
    meta = MetaClient(srv)
    for i in (1, 2):
        dn = DatanodeInstance(DatanodeOptions(
            data_home=str(tmp_path / f"dn{i}"), node_id=i,
            register_numbers_table=False))
        dn.start()
        fs = FlightDatanodeServer(dn)
        fs.serve_in_background()
        t0 = time.time()
        while fs.port == 0 and time.time() - t0 < 10:
            time.sleep(0.01)
        datanodes[i] = dn
        servers[i] = fs
        clients[i] = FlightDatanodeClient(fs.address, node_id=i)
        srv.register_datanode(Peer(i, fs.address))
        srv.handle_heartbeat(i)
    fe = DistInstance(meta, clients)
    yield fe
    for c in clients.values():
        c.close()
    for s in servers.values():
        s.shutdown()
    for dn in datanodes.values():
        dn.shutdown()


class TestRealFlight:
    def test_lowered_and_row_paths_match_standalone(
            self, fe, flight_cluster, monkeypatch):
        monkeypatch.setattr(tpu_exec, "TPU_DISPATCH_MIN_ROWS", 0)
        flight_cluster.do_query(DDL + HASH_PART)
        flight_cluster.do_query("INSERT INTO ctr VALUES " + _seed_rows())
        for q in ["sum by (host) (rate(ctr[1m]))",     # lowered scatter
                  "avg by (dc) (ctr)",                 # lowered instant
                  "rate(ctr{host='h1'}[2m])"]:         # row path -> wire scan
            a = _tql(fe, q)
            b = _tql(flight_cluster, q)
            assert b.count("\n") > 3, f"silently empty over Flight: {q}"
            assert a == b, q

    def test_version_skew_degrades_to_raw(self, fe, flight_cluster,
                                          monkeypatch):
        """An old datanode that doesn't know `increase` rejects the
        shipped plan; the frontend degrades to the raw row path and the
        answer stays correct."""
        from greptimedb_tpu.query import plan_codec
        monkeypatch.setattr(tpu_exec, "TPU_DISPATCH_MIN_ROWS", 0)
        flight_cluster.do_query(DDL + HASH_PART)
        flight_cluster.do_query("INSERT INTO ctr VALUES " + _seed_rows())
        monkeypatch.setattr(
            plan_codec, "KNOWN_MOMENT_OPS",
            plan_codec.KNOWN_MOMENT_OPS - {"increase"})
        q = "sum by (host) (rate(ctr[1m]))"
        skewed = _vec(flight_cluster, q)
        monkeypatch.setattr(tpu_exec, "TPU_DISPATCH_MIN_ROWS", 10**9)
        row = _vec(fe, q)
        _assert_close(row, skewed, rtol=2e-5)


class TestRemoteStubErrors:
    def test_unsupported_names_the_knob(self, fe, caplog):
        """A DistTable whose datanodes expose no data plane must raise a
        clear UnsupportedError naming the IR knob — never return an
        empty result."""
        table = fe.catalog.table("greptime", "public", "ctr")

        class RemoteStub(DatanodeClient):      # no .datanode attribute
            node_id = 99

        # the standalone catalog's table is region-backed; wrap its route
        # metadata into a DistTable whose every client is a dead stub
        dist, datanodes = None, {}
        try:
            import tempfile
            with tempfile.TemporaryDirectory() as td:
                from pathlib import Path
                dist, datanodes = _mk_cluster(Path(td), 1, HASH_PART)
                real = dist.catalog.table("greptime", "public", "ctr")
                stub = RemoteStub()
                remote = DistTable(real.info, real.partition_rule,
                                   real.route,
                                   {i: stub for i in dist.clients})
                with caplog.at_level(logging.WARNING):
                    assert remote.regions == {}
                from greptimedb_tpu.promql import lowering
                eng = dist.promql_engine()

                class Sel:
                    metric = "ctr"
                    matchers = []
                    at_ms = None

                with pytest.raises(UnsupportedError,
                                   match="dist_partial_agg"):
                    lowering._wire_scan_selection(
                        remote, Sel(), "ctr", ["host", "dc"], ["val"],
                        False, 0, 1000)
                del eng
        finally:
            for dn in datanodes.values():
                dn.shutdown()


# ---------------------------------------------------------------------------
# satellite 1: select decodes only referenced tag columns
# ---------------------------------------------------------------------------

class TestSelectiveTagDecode:
    def test_only_matcher_columns_decoded_fully(self, fe, monkeypatch):
        from greptimedb_tpu.storage.series import SeriesDict
        calls = []
        orig = SeriesDict.decode_tag_column

        def spy(self, ids, idx):
            calls.append((idx, len(np.atleast_1d(ids))))
            return orig(self, ids, idx)

        monkeypatch.setattr(SeriesDict, "decode_tag_column", spy)
        monkeypatch.setattr(tpu_exec, "TPU_DISPATCH_MIN_ROWS", 10**9)
        _vec(fe, "rate(ctr{host='h1'}[2m])")   # row path, 2-tag table
        # tag 0 (host) is matcher-referenced: decoded for all series;
        # tag 1 (dc) is not: decoded only for the surviving series
        by_idx = {}
        for idx, n in calls:
            by_idx.setdefault(idx, set()).add(n)
        assert max(by_idx[0]) == 6              # all series
        assert max(by_idx[1]) == 1              # only h1 survived


# ---------------------------------------------------------------------------
# flows: IR moment-frame folds + avg
# ---------------------------------------------------------------------------

FLOW = ("CREATE FLOW ctr_1m AS SELECT host, dc, "
        "date_bin(INTERVAL '1 minute', ts) AS ts, avg(val) AS v_avg, "
        "sum(val) AS v_sum, count(val) AS n FROM ctr "
        "GROUP BY host, dc, ts")
SINK_Q = ("SELECT host, dc, ts, v_avg, v_sum, n FROM ctr_1m "
          "ORDER BY host, dc, ts")


def _sink_frame(inst):
    import pandas as pd
    parts = [pd.DataFrame(b.to_pydict())
             for b in inst.do_query(SINK_Q)[0].batches]
    return pd.concat(parts, ignore_index=True)


class TestFlowIrFolds:
    def test_flow_avg_standalone(self, fe):
        fe.do_query(FLOW)
        fe.datanode.flow_manager.tick()
        sink = _sink_frame(fe)
        raw = pretty_print(fe.do_query(
            "SELECT host, dc, date_bin(INTERVAL '1 minute', ts) AS b, "
            "avg(val), sum(val), count(val) FROM ctr "
            "GROUP BY host, dc, b ORDER BY host, dc, b")[0].batches)
        import re
        raw_avgs = [float(m) for m in re.findall(
            r"\|\s(-?\d+\.\d+)\s+\|\s-?\d+\.\d+\s+\|\s\d+\s+\|", raw)]
        assert len(raw_avgs) == len(sink)
        assert np.allclose(sink["v_avg"].to_numpy(), raw_avgs, rtol=2e-5)

    def test_flow_ir_fold_matches_host_reduce(self, fe, tmp_path):
        """Drive fold_generic directly against the DistTable (what a
        real-Flight frontend does): the IR moment-frame fold must match
        the standalone device fold within f32 tolerance, and the
        degrade knob must not change the answer."""
        from greptimedb_tpu.flow import lowering as flowering
        fe.do_query(FLOW)
        fe.datanode.flow_manager.tick()
        dist, datanodes = _mk_cluster(tmp_path, 4, HASH_PART)
        try:
            dist.do_query(FLOW)
            spec = dist.flow_manager.flows()[0]
            src = dist.catalog.table(spec.catalog, spec.schema,
                                     spec.source)
            dst = dist.catalog.table(spec.catalog, spec.schema, spec.sink)
            w, n = flowering.fold_generic(spec, src, dst)
            assert w > 0 and n > 0
            a, b = _sink_frame(fe), _sink_frame(dist)
            assert list(a["host"]) == list(b["host"])
            assert list(a["ts"]) == list(b["ts"])
            for col in ("v_avg", "v_sum", "n"):
                assert np.allclose(a[col].to_numpy(dtype=float),
                                   b[col].to_numpy(dtype=float),
                                   rtol=2e-5), col
            # incremental fold through the degrade (raw scan) path
            more = ",".join(f"('h{h}', 'dc{h % 2}', {800_000 + i * 1000},"
                            f" 1.0)" for h in range(6) for i in range(5))
            fe.do_query("INSERT INTO ctr VALUES " + more)
            dist.do_query("INSERT INTO ctr VALUES " + more)
            fe.datanode.flow_manager.tick()
            agg_plan._PARTIAL_PUSHDOWN[0] = False
            try:
                flowering.fold_generic(spec, src, dst)
            finally:
                agg_plan._PARTIAL_PUSHDOWN[0] = True
            a, b = _sink_frame(fe), _sink_frame(dist)
            for col in ("v_avg", "v_sum", "n"):
                assert np.allclose(a[col].to_numpy(dtype=float),
                                   b[col].to_numpy(dtype=float),
                                   rtol=2e-5), col
        finally:
            for dn in datanodes.values():
                dn.shutdown()


# ---------------------------------------------------------------------------
# EXPLAIN surface
# ---------------------------------------------------------------------------

class TestPromqlExplain:
    def test_tql_explain_standalone(self, fe, monkeypatch):
        monkeypatch.setattr(tpu_exec, "TPU_DISPATCH_MIN_ROWS", 0)
        out = pretty_print(fe.do_query(
            "TQL EXPLAIN (0, 790, '60s') "
            "sum by (host) (rate(ctr[1m]))")[0].batches)
        assert "PromAggregate: sum by (host)" in out
        assert "TpuAggregateExec:" in out
        assert "time_bucket(60000ms)" in out
        assert "Dispatch:" in out

    def test_tql_explain_row_path_reason(self, fe, monkeypatch):
        monkeypatch.setattr(tpu_exec, "TPU_DISPATCH_MIN_ROWS", 10**9)
        out = pretty_print(fe.do_query(
            "TQL EXPLAIN (0, 790, '60s') "
            "sum by (host) (rate(ctr[1m]))")[0].batches)
        assert "promql-row-path" in out

    def test_tql_analyze_stages(self, fe, monkeypatch):
        monkeypatch.setattr(tpu_exec, "TPU_DISPATCH_MIN_ROWS", 0)
        out = pretty_print(fe.do_query(
            "TQL ANALYZE (0, 790, '60s') "
            "sum by (host) (rate(ctr[1m]))")[0].batches)
        assert "elapsed:" in out and "series:" in out
        assert "finalize" in out        # the IR executor's stage line

    def test_dist_explain_prints_scatter(self, tmp_path, monkeypatch):
        monkeypatch.setattr(tpu_exec, "TPU_DISPATCH_MIN_ROWS", 0)
        dist, datanodes = _mk_cluster(tmp_path, 4, HASH_PART)
        try:
            out = pretty_print(dist.do_query(
                "TQL EXPLAIN (0, 790, '60s') "
                "sum by (host) (rate(ctr[1m]))")[0].batches)
            assert "aggregate-pushdown" in out
            assert "fan-out" in out
            # and what executes ships partial frames, not rows
            from greptimedb_tpu.common import exec_stats
            stats = exec_stats.ExecStats()
            with exec_stats.collect(stats):
                dist.do_query("TQL EVAL (0, 790, '60s') "
                              "sum by (host) (rate(ctr[1m]))")
            assert stats.totals()["partial_bytes"] > 0
        finally:
            for dn in datanodes.values():
                dn.shutdown()

    def test_http_explain_param(self, fe, monkeypatch):
        """?explain=1 renders the same plan lines through the engine's
        public explain_lines API."""
        monkeypatch.setattr(tpu_exec, "TPU_DISPATCH_MIN_ROWS", 0)
        lines = fe.promql_engine().explain_lines(
            "sum by (host) (rate(ctr[1m]))", 0, 790_000, 60_000)
        joined = "\n".join(lines)
        assert "PromSeriesScan: ctr" in joined
        assert "TpuAggregateExec:" in joined


# ---------------------------------------------------------------------------
# the resident read path's modules import one way (ISSUE 47)
# ---------------------------------------------------------------------------

#: bottom up: a module imports only modules above it in this list
#: (`query/tpu_exec.py`'s docstring has the map)
READ_PATH = ["storage.scan_cache", "query.agg_plan", "query.scan_launch",
             "query.scan_narrow", "query.scan_full", "query.moment_fold",
             "query.tpu_exec"]


def _package_imports(module: str):
    """-> [(imported module, inside a function body)] of one module of
    the package, `greptimedb_tpu.` cut off, relative imports resolved;
    `from . import a` names the module `a`."""
    import ast
    import os

    import greptimedb_tpu
    path = os.path.join(os.path.dirname(greptimedb_tpu.__file__),
                        *module.split(".")) + ".py"
    with open(path) as f:
        tree = ast.parse(f.read())
    nested = {id(n) for fn in ast.walk(tree)
              if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))
              for n in ast.walk(fn)}
    here = ("greptimedb_tpu." + module).split(".")[:-1]
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = ".".join(here[:len(here) - node.level + 1]
                            if node.level else [])
            base = ".".join(x for x in (base, node.module) if x)
            names = [base] + [f"{base}.{a.name}" for a in node.names]
        else:
            continue
        out += [(n[len("greptimedb_tpu."):], id(node) in nested)
                for n in names if n.startswith("greptimedb_tpu.")]
    return out


def test_resident_read_path_imports_one_way():
    """Plan, cache, launch and fold under the region executor, each
    importing only what lies below it: the scan cache nothing of
    `query/`, no module `tpu_exec` (so `scan_narrow` no longer reaches
    back into its caller), `scan_narrow` not `scan_full`, and none of
    them another inside a function body (where a cycle would hide)."""
    for i, module in enumerate(READ_PATH):
        for imported, in_function in _package_imports(module):
            if module == "storage.scan_cache":
                assert not imported.startswith("query"), imported
            if imported in READ_PATH:
                assert READ_PATH.index(imported) < i, (module, imported)
                assert not in_function, (module, imported)
