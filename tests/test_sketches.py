"""Distributed aggregation v2 tests (ISSUE 14).

Covers: sketch primitives (HLL accuracy, exact-set merge + degrade,
t-digest rank error, wire codec + typed corruption errors), the
differential matrix (new agg shapes × NULLs × empty regions × 1/4
datanodes × hash/range rules — exact ops byte-identical to the raw-row
fallback, sketch ops within the documented bound), the spy assertion
that count(DISTINCT) GROUP BY scatters region_moments partial RPCs and
ZERO raw-row scans, the sketch_codec corruption degrade (typed error →
raw-row retry → right answer + greptime_sketch_degrade_total), the
cost-based raw-pull choice, the SET knobs, and the flow-compile
rejection of approx aggregates.
"""

import math

import numpy as np
import pytest

from greptimedb_tpu.client import LocalDatanodeClient
from greptimedb_tpu.common import failpoint
from greptimedb_tpu.datanode import DatanodeInstance, DatanodeOptions
from greptimedb_tpu.errors import (
    InvalidArgumentsError, SketchCodecError, UnsupportedError)
from greptimedb_tpu.frontend.distributed import DistInstance
from greptimedb_tpu.meta import MemKv, MetaClient, MetaSrv, Peer
from greptimedb_tpu.query import agg_plan, moment_fold, sketches
from greptimedb_tpu.query.sketches import (
    EXACT_SET_LIMIT, DistinctSketch, HyperLogLog, TDigest, decode_sketch,
    encode_sketch, hash64)
from greptimedb_tpu.session import QueryContext


@pytest.fixture(autouse=True)
def _clean_knobs():
    failpoint.reset()
    yield
    failpoint.reset()
    agg_plan.configure_partial_pushdown(enabled=True)
    sketches.configure(exact_distinct=False, error_target=0.01)


# ---------------------------------------------------------------------------
# sketch primitives
# ---------------------------------------------------------------------------

class TestDistinctSketch:
    def test_exact_set_merge_is_exact(self):
        a = DistinctSketch.from_values(np.array([1.0, 2.0, 2.0, np.nan]))
        b = DistinctSketch.from_values(np.array([2.0, 3.0, -0.0, 0.0]))
        a.merge(b)
        assert a.exact and a.result() == 4       # {0, 1, 2, 3}

    def test_string_sets(self):
        a = DistinctSketch.from_values(np.array(["x", "y"], dtype=object))
        b = DistinctSketch.from_values(np.array(["y", "z"], dtype=object))
        assert a.merge(b).result() == 3

    def test_degrades_past_bound_and_stays_mergeable(self):
        a = DistinctSketch.from_values(
            np.arange(EXACT_SET_LIMIT - 100, dtype=np.int64))
        assert a.exact
        b = DistinctSketch.from_values(
            np.arange(2000, 6000, dtype=np.int64))
        a.merge(b)
        assert not a.exact
        est = a.result()
        assert abs(est - 6000) / 6000 < 0.05, est

    def test_hll_accuracy_within_documented_bound(self):
        rng = np.random.default_rng(7)
        vals = rng.integers(0, 1 << 60, 100_000)
        h = HyperLogLog()
        h.add_hashes(hash64(vals))
        true = len(np.unique(vals))
        # documented: 1.04/sqrt(2^p) ≈ 0.8% at p=14; allow 3 sigma
        assert abs(h.result() - true) / true < 0.025

    def test_hash64_is_process_stable(self):
        # crc/splitmix, never Python's seeded hash(): same input, same
        # hashes, so sketches merge across processes
        assert hash64(np.array([1.5, 2.5])).tolist() == \
            hash64(np.array([1.5, 2.5])).tolist()
        assert hash64(np.array(["abc"], dtype=object))[0] == \
            hash64(np.array(["abc"], dtype=object))[0]


class TestTDigest:
    def test_rank_error_and_merge(self):
        rng = np.random.default_rng(3)
        v = rng.normal(0, 1, 50_000)
        whole = TDigest.from_values(v)
        parts = [TDigest.from_values(v[i::8]) for i in range(8)]
        merged = parts[0]
        for p in parts[1:]:
            merged = merged.merge(p)
        for d in (whole, merged):
            for q in (5, 50, 95, 99):
                val = d.quantile(q)
                rank = float((v <= val).mean())
                assert abs(rank - q / 100.0) < 0.015, (q, rank)

    def test_small_inputs(self):
        assert TDigest.from_values(np.array([], np.float64)) \
            .quantile(50) is None
        assert TDigest.from_values(np.array([4.0])).quantile(95) == 4.0


class TestCodec:
    def test_roundtrip(self):
        for sk in (DistinctSketch.from_values(np.array([1.5, 2.5])),
                   DistinctSketch.from_values(
                       np.array([3, 4], dtype=np.int64)),
                   DistinctSketch.from_values(
                       np.array(["a", "b"], dtype=object)),
                   TDigest.from_values(np.arange(100, dtype=np.float64))):
            enc = encode_sketch(sk)
            dec = decode_sketch(enc)
            if isinstance(sk, TDigest):
                assert dec.quantile(50) == sk.quantile(50)
            else:
                assert dec.result() == sk.result()

    def test_hll_roundtrip(self):
        sk = DistinctSketch.from_values(np.arange(EXACT_SET_LIMIT + 10))
        assert not sk.exact
        assert decode_sketch(encode_sketch(sk)).result() == sk.result()

    def test_corruption_raises_typed_error(self):
        good = encode_sketch(DistinctSketch.from_values(np.array([1.0])))
        for bad in (b"", b"GSK", good[:-1], good[:-4] + b"zzzz",
                    b"XXX" + good[3:], good[:5] + b"\xff" + good[6:],
                    3.14, None):
            with pytest.raises(SketchCodecError):
                decode_sketch(bad)

    def test_version_skew_raises(self):
        import struct
        import zlib
        good = encode_sketch(DistinctSketch.from_values(np.array([1.0])))
        body = bytearray(good[:-4])
        body[3] = 99                         # future codec version
        framed = bytes(body) + struct.pack(
            "<I", zlib.crc32(bytes(body)) & 0xFFFFFFFF)
        with pytest.raises(SketchCodecError, match="version"):
            decode_sketch(framed)

    def test_error_target_knob(self):
        sketches.configure(error_target=0.05)
        assert sketches.hll_precision() < 14
        with pytest.raises(InvalidArgumentsError):
            sketches.configure(error_target=0.5)


# ---------------------------------------------------------------------------
# cluster fixtures + spies
# ---------------------------------------------------------------------------

class SpyClient(LocalDatanodeClient):
    def __init__(self, datanode, log):
        super().__init__(datanode)
        self.log = log

    def scan_batches(self, *a, **kw):
        self.log.append(("scan", self.node_id))
        return super().scan_batches(*a, **kw)

    def region_moments(self, *a, **kw):
        self.log.append(("moments", self.node_id))
        return super().region_moments(*a, **kw)


def make_cluster(tmp_path, n_datanodes):
    datanodes, clients, log = {}, {}, []
    srv = MetaSrv(MemKv(), datanode_lease_secs=3600)
    meta = MetaClient(srv)
    for i in range(1, n_datanodes + 1):
        dn = DatanodeInstance(DatanodeOptions(
            data_home=str(tmp_path / f"dn{i}"), node_id=i,
            register_numbers_table=False))
        dn.start()
        datanodes[i] = dn
        clients[i] = SpyClient(dn, log)
        srv.register_datanode(Peer(i, f"dn{i}"))
        srv.handle_heartbeat(i)
    return DistInstance(meta, clients), datanodes, log


HASH_DDL = """
CREATE TABLE {name} (host STRING, ts TIMESTAMP TIME INDEX, a DOUBLE,
                     b DOUBLE, n BIGINT, PRIMARY KEY(host))
PARTITION BY HASH (host) PARTITIONS 8
"""

RANGE_DDL = """
CREATE TABLE {name} (host STRING, ts TIMESTAMP TIME INDEX, a DOUBLE,
                     b DOUBLE, n BIGINT, PRIMARY KEY(host))
PARTITION BY RANGE COLUMNS (host) (
  PARTITION r0 VALUES LESS THAN ('h2'),
  PARTITION r1 VALUES LESS THAN ('h6'),
  PARTITION r2 VALUES LESS THAN (MAXVALUE))
"""


def seed(fe, name, ctx, hosts=6, rows_per=40):
    """Integer-valued doubles (so float sums fold exactly) with NULLs
    sprinkled through both fields; hosts h0..h5 over 8 hash buckets
    leave some regions EMPTY by construction."""
    vals = []
    for h in range(hosts):
        for i in range(rows_per):
            a = "NULL" if (h + i) % 11 == 0 else float(i % 9)
            b = "NULL" if (h * i) % 13 == 5 else float(1 + i % 4)
            vals.append(f"('h{h}', {i * 1000}, {a}, {b}, {i % 5})")
    fe.do_query(f"INSERT INTO {name} VALUES " + ",".join(vals), ctx)


def rows_of(fe, ctx, sql):
    out = fe.do_query(sql, ctx)[-1]
    return [tuple(r.values())
            for b in out.batches for r in b.to_pylist()]


SHAPES = [
    # (sql template, sketch columns by index — () = must be byte-identical)
    ("SELECT host, count(DISTINCT a) AS cd FROM {t} "
     "GROUP BY host ORDER BY host", ()),
    ("SELECT host, count(DISTINCT n) AS cd, count(a) AS c FROM {t} "
     "GROUP BY host ORDER BY host", ()),
    ("SELECT count(DISTINCT host) AS ch FROM {t}", ()),
    ("SELECT host, sum(a*b) AS s, avg(a+n) AS av FROM {t} "
     "GROUP BY host ORDER BY host", ()),
    ("SELECT host, count(DISTINCT a) AS cd FROM {t} "
     "WHERE host IN ('h1','h3') GROUP BY host ORDER BY host", ()),
    ("SELECT date_bin(INTERVAL '10 seconds', ts) AS tb, "
     "count(DISTINCT a) AS cd FROM {t} GROUP BY tb ORDER BY tb", ()),
    ("SELECT host, approx_distinct(a) AS ad FROM {t} "
     "GROUP BY host ORDER BY host", ()),
    ("SELECT host, approx_percentile(a, 95) AS p FROM {t} "
     "GROUP BY host ORDER BY host", (1,)),
    ("SELECT median(a) AS m FROM {t}", (0,)),
]


class TestDifferentialMatrix:
    """Every (shape × rule × cluster size): the partial pushdown answers
    exactly like the raw-row fallback for exact ops (incl. the exact-set
    distinct below the bound), and within the documented bound for
    sketch ops. NULLs and empty regions ride every case."""

    @pytest.mark.parametrize("n_dn", [1, 4])
    @pytest.mark.parametrize("ddl,table", [(HASH_DDL, "mh"),
                                           (RANGE_DDL, "mr")])
    def test_matrix(self, tmp_path, n_dn, ddl, table):
        fe, datanodes, log = make_cluster(tmp_path / f"{table}{n_dn}",
                                          n_dn)
        ctx = QueryContext()
        try:
            fe.do_query(ddl.format(name=table), ctx)
            seed(fe, table, ctx)
            for sql_t, approx_cols in SHAPES:
                sql = sql_t.format(t=table)
                got = rows_of(fe, ctx, sql)
                dispatch = fe.query_engine.last_exec_stats.dispatch
                fe.do_query("SET dist_partial_agg = 0", ctx)
                want = rows_of(fe, ctx, sql)
                fe.do_query("SET dist_partial_agg = 1", ctx)
                assert len(got) == len(want), (sql, got, want)
                for g, w in zip(got, want):
                    assert len(g) == len(w), sql
                    for i, (gv, wv) in enumerate(zip(g, w)):
                        if i in approx_cols:
                            # sketch vs exact percentile: both engines
                            # within the documented t-digest rank bound
                            # (tiny groups: centroids are the points)
                            assert isinstance(gv, float)
                            assert abs(gv - wv) <= 1.0 + 1e-9, \
                                (sql, gv, wv)
                        elif isinstance(gv, float) and \
                                isinstance(wv, float) and \
                                math.isnan(gv) and math.isnan(wv):
                            pass
                        else:
                            # exact ops: byte-identical to the raw path
                            assert gv == wv, (sql, i, g, w)
                # the shapes must actually push down (except under the
                # knob, restored above)
                assert dispatch is None or "raw-pull" not in dispatch, \
                    (sql, dispatch)
        finally:
            for dn in datanodes.values():
                dn.shutdown()

    def test_empty_table_shapes(self, tmp_path):
        fe, datanodes, log = make_cluster(tmp_path / "empty", 2)
        ctx = QueryContext()
        try:
            fe.do_query(HASH_DDL.format(name="e"), ctx)
            assert rows_of(fe, ctx,
                           "SELECT count(DISTINCT a) AS c FROM e") == [(0,)]
            got = rows_of(fe, ctx, "SELECT approx_percentile(a, 50) FROM e")
            assert len(got) == 1 and (got[0][0] is None or
                                      math.isnan(got[0][0]))
            assert rows_of(fe, ctx, "SELECT host, count(DISTINCT a) FROM e "
                                    "GROUP BY host") == []
        finally:
            for dn in datanodes.values():
                dn.shutdown()


class TestSpyNoRawScan:
    def test_count_distinct_pushes_partials_only(self, tmp_path):
        """Acceptance: count(DISTINCT) GROUP BY over 4 datanodes issues
        region_moments partial RPCs and ZERO raw-row scan RPCs."""
        fe, datanodes, log = make_cluster(tmp_path / "spy", 4)
        ctx = QueryContext()
        try:
            fe.do_query(HASH_DDL.format(name="spy"), ctx)
            seed(fe, "spy", ctx)
            log.clear()
            got = rows_of(fe, ctx, "SELECT host, count(DISTINCT a) AS cd, "
                                   "approx_percentile(a, 95) AS p FROM spy "
                                   "GROUP BY host ORDER BY host")
            assert len(got) == 6
            kinds = {k for k, _ in log}
            assert "moments" in kinds and "scan" not in kinds, log
            nodes = {n for k, n in log if k == "moments"}
            assert len(nodes) == 4, log      # every datanode reduced
        finally:
            for dn in datanodes.values():
                dn.shutdown()

    def test_exact_distinct_forces_raw_rows(self, tmp_path):
        fe, datanodes, log = make_cluster(tmp_path / "exact", 2)
        ctx = QueryContext()
        try:
            fe.do_query(HASH_DDL.format(name="ex"), ctx)
            seed(fe, "ex", ctx)
            fe.do_query("SET exact_distinct = 1", ctx)
            log.clear()
            got = rows_of(fe, ctx, "SELECT host, count(DISTINCT a) AS cd "
                                   "FROM ex GROUP BY host ORDER BY host")
            assert len(got) == 6
            # no sketch partials: the statement went through the raw
            # CPU fallback (in-process clients serve it from the local
            # frame cache, a real wire from scan_batches — either way,
            # zero region_moments RPCs)
            kinds = {k for k, _ in log}
            assert "moments" not in kinds, log
            assert fe.query_engine.last_exec_stats.dispatch == \
                "cpu-fallback"
        finally:
            for dn in datanodes.values():
                dn.shutdown()


class TestDegrade:
    def test_corrupt_sketch_degrades_to_raw_and_counts(self, tmp_path):
        """A corrupt sketch frame raises the typed error, the statement
        retries via the raw-row path (greptime_sketch_degrade_total),
        and the answer is the exact one — never wrong."""
        from prometheus_client import REGISTRY

        def counter(name):
            return REGISTRY.get_sample_value(name) or 0.0

        fe, datanodes, log = make_cluster(tmp_path / "deg", 2)
        ctx = QueryContext()
        try:
            fe.do_query(HASH_DDL.format(name="dg"), ctx)
            seed(fe, "dg", ctx)
            want = rows_of(fe, ctx, "SELECT host, count(DISTINCT a) AS c "
                                    "FROM dg GROUP BY host ORDER BY host")
            before = counter("greptime_sketch_degrade_total")
            failpoint.configure("sketch_codec", "err")
            try:
                got = rows_of(fe, ctx,
                              "SELECT host, count(DISTINCT a) AS c "
                              "FROM dg GROUP BY host ORDER BY host")
            finally:
                failpoint.configure("sketch_codec", None)
            assert got == want
            assert counter("greptime_sketch_degrade_total") > before
            stats = fe.query_engine.last_exec_stats
            assert "sketch_degrade" in stats.stages
        finally:
            for dn in datanodes.values():
                dn.shutdown()

    def test_truncated_frame_in_finalize_is_typed(self):
        import pandas as pd
        plan = agg_plan.TpuPlan(
            tag_groups=[], bucket=None,
            moments=[agg_plan.Moment("distinct", "a", "__m0")],
            finals=[("__agg0", "count_distinct", ["__m0"])],
            time_lo=None, time_hi=None, tag_predicates=[],
            field_filters=[])
        good = encode_sketch(DistinctSketch.from_values(np.array([1.0])))
        df = pd.DataFrame({"__m0": [good[:-2]], "__rowcount": [1]})
        with pytest.raises(SketchCodecError):
            moment_fold._finalize(df, plan)


class TestCostDispatch:
    def test_unique_keys_choose_raw_pull(self, tmp_path):
        """~1 row per group with a t-digest per group: the partial
        frames outweigh the raw rows, the planner says so in the SAME
        line EXPLAIN prints, and the answer still lands (via the
        raw-row scatter)."""
        fe, datanodes, log = make_cluster(tmp_path / "cost", 2)
        ctx = QueryContext()
        try:
            fe.do_query("CREATE TABLE u (k STRING, ts TIMESTAMP TIME "
                        "INDEX, v DOUBLE, PRIMARY KEY(k)) "
                        "PARTITION BY HASH (k) PARTITIONS 4", ctx)
            fe.do_query("INSERT INTO u VALUES " + ",".join(
                f"('k{i:03d}', {i * 1000}, {float(i)})"
                for i in range(64)), ctx)
            got = rows_of(fe, ctx, "SELECT k, approx_percentile(v, 95) "
                                   "AS p FROM u GROUP BY k ORDER BY k")
            assert len(got) == 64 and got[0] == ("k000", 0.0)
            dispatch = fe.query_engine.last_exec_stats.dispatch
            assert dispatch.startswith("raw-pull ("), dispatch
            assert "est_rows=" in dispatch
            # EXPLAIN renders the identical decision line
            out = fe.do_query("EXPLAIN SELECT k, approx_percentile(v, 95)"
                              " AS p FROM u GROUP BY k", ctx)[-1]
            text = out.batches[0].to_pylist()[0]["plan"]
            assert "raw-pull (" in text, text
        finally:
            for dn in datanodes.values():
                dn.shutdown()

    def test_group_reducing_shapes_choose_pushdown(self, tmp_path):
        fe, datanodes, log = make_cluster(tmp_path / "cost2", 2)
        ctx = QueryContext()
        try:
            fe.do_query(HASH_DDL.format(name="cp"), ctx)
            seed(fe, "cp", ctx)
            rows_of(fe, ctx, "SELECT host, count(DISTINCT a) AS c FROM cp "
                             "GROUP BY host ORDER BY host")
            dispatch = fe.query_engine.last_exec_stats.dispatch
            assert dispatch.startswith("aggregate-pushdown ("), dispatch
            assert "est_rows=" in dispatch and "est_groups=" in dispatch
        finally:
            for dn in datanodes.values():
                dn.shutdown()


class TestKnobExplainParity:
    def test_dist_partial_agg_off_explains_what_executes(self, tmp_path):
        """Review fix: the kill switch is applied at PLAN time, so
        EXPLAIN and execution render the same (raw) decision instead of
        an EXPLAIN claiming pushdown over a raw-row execution."""
        fe, datanodes, log = make_cluster(tmp_path / "parity", 2)
        ctx = QueryContext()
        try:
            fe.do_query(HASH_DDL.format(name="pa"), ctx)
            seed(fe, "pa", ctx)
            fe.do_query("SET dist_partial_agg = 0", ctx)
            out = fe.do_query("EXPLAIN SELECT host, count(a) AS c "
                              "FROM pa GROUP BY host", ctx)[-1]
            text = out.batches[0].to_pylist()[0]["plan"]
            assert "aggregate-pushdown" not in text, text
            assert "CpuAggregateExec" in text, text
            rows_of(fe, ctx, "SELECT host, count(a) AS c FROM pa "
                             "GROUP BY host")
            assert fe.query_engine.last_exec_stats.dispatch == \
                "cpu-fallback"
            fe.do_query("SET dist_partial_agg = 1", ctx)
            out = fe.do_query("EXPLAIN SELECT host, count(a) AS c "
                              "FROM pa GROUP BY host", ctx)[-1]
            text = out.batches[0].to_pylist()[0]["plan"]
            assert "aggregate-pushdown" in text, text
        finally:
            for dn in datanodes.values():
                dn.shutdown()


class _RemoteView:
    """Hides .datanode so a LocalDatanodeClient looks like a wire
    client to the cost estimator."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        if name == "datanode":
            raise AttributeError(name)
        return getattr(self._inner, name)


class TestHeartbeatEstimates:
    def test_remote_clients_estimate_from_heartbeat(self, tmp_path):
        """Review fix: datanodes behind a wire client feed the cost
        planner through the heartbeat's region_stats (rows + series +
        time span), so the cost-based choice is live on real clusters,
        not only in-process ones."""
        from greptimedb_tpu.meta.service import DatanodeStat
        from greptimedb_tpu.query.stream_exec import region_stat_entries

        fe, datanodes, log = make_cluster(tmp_path / "hb", 2)
        ctx = QueryContext()
        try:
            fe.do_query(HASH_DDL.format(name="hb"), ctx)
            seed(fe, "hb", ctx)
            srv = fe.meta._srv
            for i, dn in datanodes.items():
                regions = list(dn.storage.list_regions().values())
                entries, rows, size = region_stat_entries(regions)
                assert all("series" in e and "time_span" in e
                           for e in entries)
                srv.handle_heartbeat(i, DatanodeStat(
                    region_count=len(regions), approximate_rows=rows,
                    approximate_bytes=size, region_stats=entries))
            table = fe.catalog.table("greptime", "public", "hb")
            table.clients = {k: _RemoteView(v)
                             for k, v in table.clients.items()}
            wanted = [rr.region_number
                      for rr in table.route.region_routes]
            est = table._region_estimates(wanted)
            # every routed region is estimated via the heartbeat stats
            assert est, est
            assert sum(r for r, _, _ in est.values()) == 240  # 6×40 rows
            assert all(s >= 1 for rn, (r, s, _) in est.items() if r > 0)
            # and the dispatch line carries the estimates
            rows_got = rows_of(fe, ctx, "SELECT host, count(DISTINCT a) "
                                        "AS c FROM hb GROUP BY host "
                                        "ORDER BY host")
            assert len(rows_got) == 6
            dispatch = fe.query_engine.last_exec_stats.dispatch
            assert "est_rows=240" in dispatch, dispatch
        finally:
            for dn in datanodes.values():
                dn.shutdown()


class TestObservability:
    def test_finalize_reports_partials_and_processes_column(self, tmp_path):
        fe, datanodes, log = make_cluster(tmp_path / "obs", 2)
        ctx = QueryContext()
        try:
            fe.do_query(HASH_DDL.format(name="ob"), ctx)
            seed(fe, "ob", ctx)
            out = fe.do_query(
                "EXPLAIN ANALYZE SELECT host, count(DISTINCT a) AS cd, "
                "sum(a) AS s FROM ob GROUP BY host", ctx)[-1]
            by_stage = {r["stage"]: r for b in out.batches
                        for r in b.to_pylist()}
            fin = by_stage["finalize"]["detail"]
            assert "partial_frames=" in fin
            assert "partial_bytes=" in fin
            assert "count_distinct:sketch" in fin and "sum:exact" in fin
            # ExecStats totals carry partial bytes (processes view)
            totals = fe.query_engine.last_exec_stats.totals()
            assert totals["partial_bytes"] > 0
            # the pushdown ships at most a third of what the raw-row
            # scatter would: the columns the statement reads, as bytes
            table = fe.catalog.table("greptime", "public", "ob")
            raw_bytes = sum(
                sum(len(str(v)) for v in col.data)
                if col.data.dtype == object else col.data.nbytes
                for b in table.scan_batches(projection=["host", "ts", "a"])
                for col in b.columns)
            assert raw_bytes >= 3 * totals["partial_bytes"]
            # the information_schema view exposes the column
            out = fe.do_query("SELECT partial_bytes FROM "
                              "information_schema.processes", ctx)[-1]
            assert out.batches[0].schema.names() == ["partial_bytes"]
        finally:
            for dn in datanodes.values():
                dn.shutdown()


class TestStandaloneFallback:
    """Satellite 1: approx aggs in the standalone CPU executor answer
    within the same documented bound as the distributed sketch path."""

    @pytest.fixture()
    def standalone(self, tmp_path):
        from greptimedb_tpu.frontend.instance import FrontendInstance
        dn = DatanodeInstance(DatanodeOptions(
            data_home=str(tmp_path / "sa"), register_numbers_table=False))
        dn.start()
        fe = FrontendInstance(dn)
        fe.start()
        yield fe
        dn.shutdown()

    def test_same_bound_both_engines(self, tmp_path, standalone):
        ctx = QueryContext()
        standalone.do_query(
            "CREATE TABLE s (host STRING, ts TIMESTAMP TIME INDEX, "
            "a DOUBLE, PRIMARY KEY(host))", ctx)
        rng = np.random.default_rng(5)
        vals = rng.normal(50, 10, 4000)
        standalone.do_query("INSERT INTO s VALUES " + ",".join(
            f"('h{i % 3}', {i * 100}, {v})"
            for i, v in enumerate(vals)), ctx)
        fe, datanodes, _ = make_cluster(tmp_path / "dsb", 2)
        try:
            fe.do_query("CREATE TABLE s (host STRING, ts TIMESTAMP TIME "
                        "INDEX, a DOUBLE, PRIMARY KEY(host)) "
                        "PARTITION BY HASH (host) PARTITIONS 4", ctx)
            fe.do_query("INSERT INTO s VALUES " + ",".join(
                f"('h{i % 3}', {i * 100}, {v})"
                for i, v in enumerate(vals)), ctx)
            for sql in ("SELECT approx_distinct(a) AS d FROM s",
                        "SELECT approx_percentile(a, 95) AS p FROM s"):
                (sa,) = rows_of(standalone, ctx, sql)
                (di,) = rows_of(fe, ctx, sql)
                if "distinct" in sql:
                    true = len(np.unique(vals))
                    for got in (sa[0], di[0]):
                        assert abs(got - true) / true < 0.03, (sql, got)
                else:
                    for got in (sa[0], di[0]):
                        rank = float((vals <= got).mean())
                        assert abs(rank - 0.95) < 0.02, (sql, got, rank)
        finally:
            for dn in datanodes.values():
                dn.shutdown()

    def test_approx_percentile_validates_params(self, standalone):
        ctx = QueryContext()
        standalone.do_query(
            "CREATE TABLE v (host STRING, ts TIMESTAMP TIME INDEX, "
            "a DOUBLE, PRIMARY KEY(host))", ctx)
        standalone.do_query("INSERT INTO v VALUES ('h', 0, 1.0)", ctx)
        with pytest.raises(InvalidArgumentsError):
            standalone.do_query("SELECT approx_percentile(a) FROM v", ctx)
        with pytest.raises(InvalidArgumentsError):
            standalone.do_query(
                "SELECT approx_percentile(a, 150) FROM v", ctx)


class TestSketchFramesOverWire:
    def test_flight_roundtrip_of_sketch_partials(self, tmp_path):
        """Sketch partials are a NEW wire shape (binary columns in the
        region_moments stream): push count(DISTINCT)+p95 through a real
        Flight socket and compare against the in-process answer."""
        import socket
        import time as _time

        from greptimedb_tpu.client.flight import FlightDatanodeClient
        from greptimedb_tpu.servers.flight import FlightDatanodeServer

        dn = DatanodeInstance(DatanodeOptions(
            data_home=str(tmp_path / "wire"), node_id=1,
            register_numbers_table=False))
        dn.start()
        srv = FlightDatanodeServer(dn)
        srv.serve_in_background()
        deadline = _time.monotonic() + 10
        while _time.monotonic() < deadline:
            try:
                with socket.create_connection(
                        ("127.0.0.1", srv.port), timeout=0.2):
                    break
            except OSError:
                _time.sleep(0.05)
        meta_srv = MetaSrv(MemKv(), datanode_lease_secs=3600)
        meta = MetaClient(meta_srv)
        meta_srv.register_datanode(Peer(1, srv.address))
        meta_srv.handle_heartbeat(1)
        client = FlightDatanodeClient(srv.address, node_id=1)
        fe = DistInstance(meta, {1: client})
        ctx = QueryContext()
        try:
            fe.do_query(HASH_DDL.format(name="w"), ctx)
            seed(fe, "w", ctx, hosts=3, rows_per=20)
            got = rows_of(fe, ctx,
                          "SELECT host, count(DISTINCT a) AS cd, "
                          "approx_percentile(a, 95) AS p, sum(a*b) AS s "
                          "FROM w GROUP BY host ORDER BY host")
            assert "aggregate-pushdown" in \
                fe.query_engine.last_exec_stats.dispatch
            fe.do_query("SET dist_partial_agg = 0", ctx)
            want = rows_of(fe, ctx,
                           "SELECT host, count(DISTINCT a) AS cd, "
                           "approx_percentile(a, 95) AS p, sum(a*b) AS s "
                           "FROM w GROUP BY host ORDER BY host")
            fe.do_query("SET dist_partial_agg = 1", ctx)
            assert len(got) == 3
            for g, w in zip(got, want):
                assert g[0] == w[0] and g[1] == w[1] and g[3] == w[3]
                assert abs(g[2] - w[2]) <= 1.0 + 1e-9
        finally:
            client.close()
            srv.shutdown()
            dn.shutdown()


class TestFlowRejectsApprox:
    def test_create_flow_with_approx_agg_hints(self, tmp_path):
        fe, datanodes, _ = make_cluster(tmp_path / "flow", 1)
        ctx = QueryContext()
        try:
            fe.do_query(HASH_DDL.format(name="src"), ctx)
            with pytest.raises(UnsupportedError,
                               match="sketch"):
                fe.do_query(
                    "CREATE FLOW f AS SELECT host, "
                    "date_bin(INTERVAL '1 minute', ts) AS tb, "
                    "approx_distinct(a) AS d FROM src "
                    "GROUP BY host, tb", ctx)
        finally:
            for dn in datanodes.values():
                dn.shutdown()
