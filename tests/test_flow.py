"""Continuous rollup flow tests: DDL, incremental fold + watermark,
transparent rollup rewrite (differential vs raw scan), crash recovery,
partitioned destinations, distributed (meta-kv) flows.

Covers the ISSUE 3 acceptance criteria: folds only rows past the
watermark (asserted on fold counters), survives restart without
double-folding, and serves matching GROUP BY date_bin queries via the
`rollup-rewrite` dispatch with answers equal to the raw scan.
"""

import math

import pytest

from greptimedb_tpu.datanode.instance import DatanodeInstance, DatanodeOptions
from greptimedb_tpu.errors import (GreptimeError, InvalidArgumentsError,
                                   PlanError, UnsupportedError)
from greptimedb_tpu.frontend.instance import FrontendInstance
from greptimedb_tpu.session import QueryContext


def mk_fe(tmp_path):
    dn = DatanodeInstance(DatanodeOptions(
        data_home=str(tmp_path), register_numbers_table=False))
    dn.start()
    fe = FrontendInstance(dn)
    fe.start()
    return fe


@pytest.fixture()
def fe(tmp_path):
    inst = mk_fe(tmp_path)
    yield inst
    inst.shutdown()


def rows(out):
    return [list(r) for r in out.batches[0].rows()]


def q1(fe, sql):
    return rows(fe.do_query(sql)[0])


def _mk_cpu(fe, n_per_host=600, hosts=("a", "b"), with_nulls=False):
    fe.do_query("CREATE TABLE cpu (host STRING, ts TIMESTAMP TIME INDEX, "
                "v DOUBLE, PRIMARY KEY(host))")
    vals = []
    for h in hosts:
        scale = 1.0 if h == "a" else 10.0
        for i in range(n_per_host):
            v = "NULL" if with_nulls and i % 7 == 0 else repr(scale * i)
            vals.append(f"('{h}', {i * 1000}, {v})")
    fe.do_query("INSERT INTO cpu VALUES " + ",".join(vals))


FLOW_SQL = ("CREATE FLOW cpu_1m AS SELECT host, "
            "date_bin(INTERVAL '1 minute', ts) AS b, "
            "sum(v) AS v_sum, count(v) AS v_cnt, min(v) AS v_min, "
            "max(v) AS v_max, first(v) AS v_first, last(v) AS v_last, "
            "count(*) AS n FROM cpu GROUP BY host, b")


class TestFlowDdl:
    def test_create_show_drop(self, fe):
        _mk_cpu(fe, 120)
        fe.do_query(FLOW_SQL)
        got = q1(fe, "SHOW FLOWS")
        assert len(got) == 1
        name, src, sink, stride = got[0][:4]
        assert (name, src, sink, stride) == ("cpu_1m", "cpu", "cpu_1m",
                                             60_000)
        # the sink materialized as an ordinary table
        assert q1(fe, "SHOW TABLES LIKE 'cpu_1m'") == [["cpu_1m"]]
        # idempotent create
        fe.do_query(FLOW_SQL.replace("CREATE FLOW",
                                     "CREATE FLOW IF NOT EXISTS"))
        with pytest.raises(InvalidArgumentsError):
            fe.do_query(FLOW_SQL)
        fe.do_query("DROP FLOW cpu_1m")
        assert q1(fe, "SHOW FLOWS") == []
        with pytest.raises(InvalidArgumentsError):
            fe.do_query("DROP FLOW cpu_1m")
        fe.do_query("DROP FLOW IF EXISTS cpu_1m")   # silent

    def test_flow_listed_in_information_schema(self, fe):
        _mk_cpu(fe, 60)
        fe.do_query(FLOW_SQL)
        got = q1(fe, "SELECT flow_name, source_table, sink_table, "
                     "stride_ms FROM information_schema.flows")
        assert got == [["cpu_1m", "cpu", "cpu_1m", 60_000]]

    def test_create_flow_errors(self, fe):
        _mk_cpu(fe, 10)
        with pytest.raises(UnsupportedError, match="not derivable"):
            fe.do_query("CREATE FLOW f AS SELECT stddev(v) FROM cpu "
                        "GROUP BY date_bin(INTERVAL '1 minute', ts)")
        with pytest.raises(PlanError, match="date_bin"):
            fe.do_query("CREATE FLOW f AS SELECT host, sum(v) FROM cpu "
                        "GROUP BY host")
        with pytest.raises(PlanError, match="date_bin"):
            # zero stride
            fe.do_query("CREATE FLOW f AS SELECT sum(v) FROM cpu "
                        "GROUP BY date_bin(INTERVAL '0 minutes', ts)")
        with pytest.raises(PlanError, match="WHERE"):
            fe.do_query("CREATE FLOW f AS SELECT sum(v) FROM cpu "
                        "WHERE host = 'a' "
                        "GROUP BY date_bin(INTERVAL '1 minute', ts)")
        with pytest.raises(GreptimeError, match="not found"):
            fe.do_query("CREATE FLOW f AS SELECT sum(v) FROM nope "
                        "GROUP BY date_bin(INTERVAL '1 minute', ts)")
        with pytest.raises(InvalidArgumentsError, match="differ"):
            fe.do_query("CREATE FLOW cpu AS SELECT host, sum(v) FROM cpu "
                        "GROUP BY host, date_bin(INTERVAL '1 minute', ts)")


class TestIncrementalFold:
    def test_watermark_folds_only_new_rows(self, fe):
        _mk_cpu(fe, 600)
        fe.do_query(FLOW_SQL)
        fm = fe.datanode.flow_manager
        written = fm.tick()
        assert written["greptime.public.cpu_1m"] == 2 * 10
        spec = fm.flows()[0]
        assert spec.stats["rows_folded"] == 1200
        assert spec.stats["folds"] == 1
        # steady state: nothing new → no fold work at all
        assert fm.tick()["greptime.public.cpu_1m"] == 0
        assert spec.stats["folds"] == 1
        # new rows: only the delta is folded, re-folding the touched
        # bucket idempotently
        fe.do_query("INSERT INTO cpu VALUES ('a', 600000, 600.0), "
                    "('a', 601000, 601.0)")
        fm.tick()
        assert spec.stats["rows_folded"] == 1202
        assert spec.stats["folds"] == 2
        got = q1(fe, "SELECT v_cnt, n FROM cpu_1m "
                     "WHERE host = 'a' AND ts = 600000")
        assert got == [[2.0, 2.0]]
        # a late (out-of-order) write re-folds from its bucket onward
        fe.do_query("INSERT INTO cpu VALUES ('a', 1000, 999.0)")
        fm.tick()
        got = q1(fe, "SELECT v_max FROM cpu_1m "
                     "WHERE host = 'a' AND ts = 0")
        assert got == [[999.0]]

    def test_rewrite_dispatch_and_equality(self, fe):
        _mk_cpu(fe, 600)
        fe.do_query(FLOW_SQL)
        fe.datanode.flow_manager.tick()
        sql = ("SELECT host, date_bin(INTERVAL '5 minutes', ts) AS b, "
               "sum(v), count(v), avg(v) FROM cpu "
               "GROUP BY host, b ORDER BY host, b")
        rolled = q1(fe, sql)
        assert "rollup-rewrite" in \
            fe.query_engine.last_exec_stats.dispatch
        fe.do_query("SET rollup_rewrite = 0")
        raw = q1(fe, sql)
        assert "rollup-rewrite" not in \
            (fe.query_engine.last_exec_stats.dispatch or "")
        fe.do_query("SET rollup_rewrite = 1")
        assert rolled == raw
        # EXPLAIN names the dispatch without folding
        plan = q1(fe, "EXPLAIN " + sql)[0][1]
        assert "Dispatch: rollup-rewrite (flow cpu_1m" in plan
        assert "TableScan: cpu_1m" in plan
        # EXPLAIN ANALYZE records the rewrite stage + dispatch line
        stages = q1(fe, "EXPLAIN ANALYZE " + sql)
        by_stage = {r[0]: r[4] for r in stages}
        assert "rollup-rewrite" in by_stage["dispatch"]
        assert "flow=cpu_1m" in by_stage["rollup_rewrite"]

    def test_rewrite_refreshes_lagging_sink(self, fe):
        """A query through the rewrite first folds pending rows, so the
        transparent path never serves stale buckets."""
        _mk_cpu(fe, 300)
        fe.do_query(FLOW_SQL)
        # no manual tick: the SELECT itself must catch the sink up
        sql = ("SELECT host, date_bin(INTERVAL '1 minute', ts) AS b, "
               "sum(v) FROM cpu GROUP BY host, b ORDER BY host, b")
        rolled = q1(fe, sql)
        assert "rollup-rewrite" in fe.query_engine.last_exec_stats.dispatch
        fe.do_query("SET rollup_rewrite = 0")
        try:
            assert rolled == q1(fe, sql)
        finally:
            fe.do_query("SET rollup_rewrite = 1")


class TestRollupDifferential:
    """Acceptance: every rollup-rewritten query equals the raw-scan
    answer (fp tolerance) across aggs × strides."""

    AGGS = ["sum(v)", "count(v)", "count(*)", "min(v)", "max(v)",
            "first(v)", "last(v)", "avg(v)"]
    STRIDES = ["1 minute", "2 minutes", "5 minutes"]

    def _diff(self, fe, sql):
        rolled = q1(fe, sql)
        assert "rollup-rewrite" in \
            fe.query_engine.last_exec_stats.dispatch, sql
        fe.do_query("SET rollup_rewrite = 0")
        try:
            raw = q1(fe, sql)
        finally:
            fe.do_query("SET rollup_rewrite = 1")
        assert len(rolled) == len(raw), sql
        for rr, rw in zip(rolled, raw):
            assert len(rr) == len(rw), sql
            for a, b in zip(rr, rw):
                if isinstance(a, float) or isinstance(b, float):
                    if (a is None) != (b is None):
                        raise AssertionError((sql, rr, rw))
                    if a is not None and not (
                            math.isnan(a) and math.isnan(b)):
                        assert abs(a - b) <= 1e-9 * max(
                            1.0, abs(a), abs(b)), (sql, rr, rw)
                else:
                    assert a == b, (sql, rr, rw)

    def test_aggs_by_strides(self, fe):
        _mk_cpu(fe, 600, with_nulls=True)
        fe.do_query(FLOW_SQL)
        fe.datanode.flow_manager.tick()
        for stride in self.STRIDES:
            cols = ", ".join(self.AGGS)
            self._diff(
                fe, f"SELECT host, date_bin(INTERVAL '{stride}', ts) AS b, "
                    f"{cols} FROM cpu GROUP BY host, b ORDER BY host, b")

    def test_filters_having_order(self, fe):
        _mk_cpu(fe, 600)
        fe.do_query(FLOW_SQL)
        fe.datanode.flow_manager.tick()
        # tag filter + aligned time range + HAVING over an aggregate
        self._diff(
            fe, "SELECT host, date_bin(INTERVAL '2 minutes', ts) AS b, "
                "sum(v) AS s FROM cpu "
                "WHERE host = 'b' AND ts >= 60000 AND ts < 480000 "
                "GROUP BY host, b HAVING sum(v) > 0 ORDER BY s DESC, b")
        # global (tagless) rollup over the time bucket only
        self._diff(
            fe, "SELECT date_bin(INTERVAL '5 minutes', ts) AS b, "
                "count(*), avg(v) FROM cpu GROUP BY b ORDER BY b")

    def test_non_rewritable_shapes_stay_raw(self, fe):
        _mk_cpu(fe, 600)
        fe.do_query(FLOW_SQL)
        fe.datanode.flow_manager.tick()
        for sql in [
            # stride not a multiple of the flow stride
            "SELECT date_bin(INTERVAL '90 seconds', ts) AS b, sum(v) "
            "FROM cpu GROUP BY b",
            # unaligned time bound would clip a fine bucket
            "SELECT date_bin(INTERVAL '1 minute', ts) AS b, sum(v) "
            "FROM cpu WHERE ts >= 1500 GROUP BY b",
            # field predicate cannot be applied post-aggregation
            "SELECT date_bin(INTERVAL '1 minute', ts) AS b, sum(v) "
            "FROM cpu WHERE v > 5 GROUP BY b",
            # aggregate the flow does not store
            "SELECT date_bin(INTERVAL '1 minute', ts) AS b, stddev(v) "
            "FROM cpu GROUP BY b",
            # finer stride than the flow
            "SELECT date_bin(INTERVAL '30 seconds', ts) AS b, sum(v) "
            "FROM cpu GROUP BY b",
        ]:
            fe.do_query(sql)
            d = fe.query_engine.last_exec_stats.dispatch or ""
            assert "rollup-rewrite" not in d, sql


class TestReviewRegressions:
    def test_dropped_sink_falls_back_to_raw(self, fe):
        """DROP TABLE on the sink (flow still registered) must not break
        queries on the source — the rewrite falls back to the raw scan."""
        _mk_cpu(fe, 120)
        fe.do_query(FLOW_SQL)
        fe.datanode.flow_manager.tick()
        fe.do_query("DROP TABLE cpu_1m")
        sql = ("SELECT host, date_bin(INTERVAL '1 minute', ts) AS b, "
               "sum(v) FROM cpu GROUP BY host, b ORDER BY host, b")
        got = q1(fe, sql)
        assert len(got) == 2 * 2
        d = fe.query_engine.last_exec_stats.dispatch or ""
        assert "rollup-rewrite" not in d

    def test_show_flows_where_rejected(self, fe):
        from greptimedb_tpu.sql.parser import ParserError
        with pytest.raises(ParserError, match="LIKE"):
            fe.do_query("SHOW FLOWS WHERE flow_name = 'x'")

    def test_delete_triggers_retraction_refold(self, fe):
        """DELETE of already-folded rows advances the sequence with no
        new scan rows — the fold must re-reduce instead of silently
        advancing the watermark past the retraction."""
        _mk_cpu(fe, 120)
        fe.do_query(FLOW_SQL)
        fm = fe.datanode.flow_manager
        fm.tick()
        sql = ("SELECT host, date_bin(INTERVAL '1 minute', ts) AS b, "
               "sum(v), count(v) FROM cpu GROUP BY host, b "
               "ORDER BY host, b")
        fe.do_query("DELETE FROM cpu WHERE host = 'a' AND ts = 0")
        fm.tick()
        rolled = q1(fe, sql)
        assert "rollup-rewrite" in fe.query_engine.last_exec_stats.dispatch
        fe.do_query("SET rollup_rewrite = 0")
        try:
            assert rolled == q1(fe, sql)
        finally:
            fe.do_query("SET rollup_rewrite = 1")

    def test_delete_plus_insert_same_interval_refolds(self, fe):
        """A DELETE hidden behind new INSERTs in the same fold interval
        must still retract (the live-row count probe catches it even
        though the seq filter alone cannot)."""
        _mk_cpu(fe, 120)
        fe.do_query(FLOW_SQL)
        fm = fe.datanode.flow_manager
        fm.tick()
        fe.do_query("DELETE FROM cpu WHERE host = 'a' AND ts = 0")
        fe.do_query("INSERT INTO cpu VALUES ('a', 200000, 1.0)")
        fm.tick()
        sql = ("SELECT host, date_bin(INTERVAL '1 minute', ts) AS b, "
               "count(v) FROM cpu GROUP BY host, b ORDER BY host, b")
        rolled = q1(fe, sql)
        assert "rollup-rewrite" in fe.query_engine.last_exec_stats.dispatch
        fe.do_query("SET rollup_rewrite = 0")
        try:
            assert rolled == q1(fe, sql)
        finally:
            fe.do_query("SET rollup_rewrite = 1")

    def test_integer_columns_keep_their_type(self, fe):
        """sum/min/max/first/last over integer source columns must come
        back integral through the rollup, as on the raw path."""
        fe.do_query("CREATE TABLE m (host STRING, ts TIMESTAMP TIME "
                    "INDEX, c BIGINT, PRIMARY KEY(host))")
        fe.do_query("INSERT INTO m VALUES " + ",".join(
            f"('a', {i * 1000}, {i})" for i in range(120)))
        fe.do_query("CREATE FLOW m_1m AS SELECT host, sum(c) AS c_sum, "
                    "max(c) AS c_max, first(c) AS c_first FROM m "
                    "GROUP BY host, date_bin(INTERVAL '1 minute', ts)")
        fe.datanode.flow_manager.tick()
        sql = ("SELECT host, date_bin(INTERVAL '2 minutes', ts) AS b, "
               "sum(c), max(c), first(c) FROM m GROUP BY host, b")
        rolled = q1(fe, sql)
        assert "rollup-rewrite" in fe.query_engine.last_exec_stats.dispatch
        fe.do_query("SET rollup_rewrite = 0")
        try:
            raw = q1(fe, sql)
        finally:
            fe.do_query("SET rollup_rewrite = 1")
        assert rolled == raw
        # exact int equality, not 1770.0 vs 1770
        assert all(isinstance(v, int) for v in rolled[0][2:])

    def test_first_last_require_full_tag_set(self, fe):
        """first/last cannot merge across collapsed tag dimensions: a
        GROUP BY without the flow's tags stays on the raw scan."""
        _mk_cpu(fe, 300)
        fe.do_query(FLOW_SQL)
        fe.datanode.flow_manager.tick()
        sql = ("SELECT date_bin(INTERVAL '5 minutes', ts) AS b, first(v) "
               "FROM cpu GROUP BY b ORDER BY b")
        raw_first = q1(fe, sql)
        d = fe.query_engine.last_exec_stats.dispatch or ""
        assert "rollup-rewrite" not in d
        # sanity: sum over the same collapsed shape still rewrites and
        # agrees with the raw answer
        sql2 = ("SELECT date_bin(INTERVAL '5 minutes', ts) AS b, sum(v) "
                "FROM cpu GROUP BY b ORDER BY b")
        rolled = q1(fe, sql2)
        assert "rollup-rewrite" in fe.query_engine.last_exec_stats.dispatch
        fe.do_query("SET rollup_rewrite = 0")
        try:
            assert rolled == q1(fe, sql2)
            assert raw_first == q1(fe, sql)
        finally:
            fe.do_query("SET rollup_rewrite = 1")

    def test_full_bucket_delete_removes_ghost_sink_rows(self, fe):
        """Deleting every row of a bucket must delete the bucket's sink
        row too — a refold alone cannot emit it, and a ghost row would
        make rollup answers diverge from the raw scan."""
        _mk_cpu(fe, 180)
        fe.do_query(FLOW_SQL)
        fm = fe.datanode.flow_manager
        fm.tick()
        assert len(q1(fe, "SELECT ts FROM cpu_1m WHERE host = 'a'")) == 3
        fe.do_query("DELETE FROM cpu WHERE ts < 60000")
        fm.tick()
        # bucket 0 vanished from the sink for both hosts
        assert len(q1(fe, "SELECT ts FROM cpu_1m WHERE host = 'a'")) == 2
        sql = ("SELECT host, date_bin(INTERVAL '1 minute', ts) AS b, "
               "sum(v), count(*) FROM cpu GROUP BY host, b "
               "ORDER BY host, b")
        rolled = q1(fe, sql)
        assert "rollup-rewrite" in fe.query_engine.last_exec_stats.dispatch
        fe.do_query("SET rollup_rewrite = 0")
        try:
            assert rolled == q1(fe, sql)
        finally:
            fe.do_query("SET rollup_rewrite = 1")

    def test_retraction_does_not_inflate_fold_counters(self, fe):
        """rows_folded tracks rows newly past the watermark; a DELETE
        retraction re-reduces but must not count re-read old rows."""
        _mk_cpu(fe, 120)
        fe.do_query(FLOW_SQL)
        fm = fe.datanode.flow_manager
        fm.tick()
        spec = fm.flows()[0]
        assert spec.stats["rows_folded"] == 240
        fe.do_query("DELETE FROM cpu WHERE host = 'a' AND ts = 0")
        fm.tick()
        assert spec.stats["rows_folded"] == 240

    def test_tag_subset_flow_rejected(self, fe):
        """A flow grouping by a tag subset would collapse distinct
        series onto one sink key (MVCC dedup keeps one) — reject it;
        coarser grouping belongs at query time via the rewrite."""
        _mk_cpu(fe, 10)
        with pytest.raises(PlanError, match="every tag column"):
            fe.do_query("CREATE FLOW f AS SELECT sum(v) FROM cpu "
                        "GROUP BY date_bin(INTERVAL '1 minute', ts)")

    def test_cold_region_fold_skips_scan_cache(self, fe):
        """A source region past the streaming threshold folds through
        the window-bounded host path — same answers, no scan-cache
        residency pinned by the background fold."""
        from greptimedb_tpu.query import stream_exec
        from greptimedb_tpu.storage import scan_cache
        _mk_cpu(fe, 600)
        fe.do_query(FLOW_SQL)
        fm = fe.datanode.flow_manager
        saved = stream_exec.stream_threshold_rows()
        try:
            stream_exec.configure_streaming(threshold_rows=1)
            scan_cache.SCAN_CACHE._entries.clear()
            fm.tick()
            assert scan_cache.SCAN_CACHE.resident_bytes() == 0
            spec = fm.flows()[0]
            assert spec.stats["rows_folded"] == 1200
            # incremental on the cold path too (ts-watermarked: refolds
            # from the last bucket boundary only)
            fe.do_query("INSERT INTO cpu VALUES ('a', 600000, 1.0)")
            folded = spec.stats["rows_folded"]
            fm.tick()
            assert spec.stats["rows_folded"] - folded <= 2 * 60 + 1
            sql = ("SELECT host, date_bin(INTERVAL '5 minutes', ts) AS "
                   "b, sum(v), count(v) FROM cpu GROUP BY host, b "
                   "ORDER BY host, b")
            rolled = q1(fe, sql)
            fe.do_query("SET rollup_rewrite = 0")
            assert rolled == q1(fe, sql)
            fe.do_query("SET rollup_rewrite = 1")
        finally:
            stream_exec.configure_streaming(threshold_rows=saved)

    def test_create_flow_without_from_is_clean_error(self, fe):
        with pytest.raises(PlanError, match="FROM"):
            fe.do_query("CREATE FLOW f SINK TO s AS SELECT 1")

    def test_explain_converts_time_literals_like_execution(self, fe):
        _mk_cpu(fe, 300)
        fe.do_query(FLOW_SQL)
        plan = q1(fe, "EXPLAIN SELECT date_bin(INTERVAL '1 minute', ts) "
                      "AS b, sum(v) FROM cpu "
                      "WHERE ts >= '1970-01-01 00:01:00' GROUP BY b")[0][1]
        assert "Dispatch: rollup-rewrite" in plan

    def test_cross_schema_source_rejected(self, fe):
        fe.do_query("CREATE DATABASE other")
        fe.do_query("CREATE TABLE other.m (host STRING, ts TIMESTAMP "
                    "TIME INDEX, v DOUBLE, PRIMARY KEY(host))")
        with pytest.raises(UnsupportedError, match="current database"):
            fe.do_query("CREATE FLOW f AS SELECT sum(v) FROM other.m "
                        "GROUP BY date_bin(INTERVAL '1 minute', ts)")


class TestCrashRecovery:
    def test_flow_survives_restart_without_double_fold(self, tmp_path):
        fe = mk_fe(tmp_path)
        _mk_cpu(fe, 300)
        fe.do_query(FLOW_SQL)
        fm = fe.datanode.flow_manager
        fm.tick()
        spec = fm.flows()[0]
        assert spec.stats["rows_folded"] == 600
        before = q1(fe, "SELECT host, ts, v_cnt FROM cpu_1m "
                        "ORDER BY host, ts")
        fe.shutdown()

        fe2 = mk_fe(tmp_path)
        try:
            # flow + watermark + sink rows recovered
            assert q1(fe2, "SHOW FLOWS")[0][0] == "cpu_1m"
            fm2 = fe2.datanode.flow_manager
            spec2 = fm2.flows()[0]
            assert spec2.stats["rows_folded"] == 600
            assert spec2.watermarks
            # ticking after restart folds NOTHING (watermark held)
            fm2.tick()
            assert spec2.stats["rows_folded"] == 600
            assert q1(fe2, "SELECT host, ts, v_cnt FROM cpu_1m "
                           "ORDER BY host, ts") == before
            # new rows fold exactly once and counts still match raw
            fe2.do_query("INSERT INTO cpu VALUES ('a', 300000, 1.0), "
                         "('b', 300000, 2.0)")
            fm2.tick()
            assert spec2.stats["rows_folded"] == 602
            sql = ("SELECT host, date_bin(INTERVAL '1 minute', ts) AS b, "
                   "count(v) FROM cpu GROUP BY host, b ORDER BY host, b")
            rolled = q1(fe2, sql)
            fe2.do_query("SET rollup_rewrite = 0")
            assert rolled == q1(fe2, sql)
            fe2.do_query("SET rollup_rewrite = 1")
        finally:
            fe2.shutdown()


class TestPartitionedDestination:
    PART_DDL = ("CREATE TABLE agg (host STRING, ts TIMESTAMP TIME INDEX, "
                "v_sum DOUBLE, PRIMARY KEY(host)) "
                "PARTITION BY RANGE COLUMNS (host) ("
                "PARTITION p0 VALUES LESS THAN ('b'), "
                "PARTITION p1 VALUES LESS THAN (MAXVALUE))")

    def test_downsample_into_partitioned_table(self, fe):
        """Satellite: /v1/admin/downsample no longer refuses partitioned
        destinations — rows route through partition/splitter.py."""
        from greptimedb_tpu.storage.downsample import downsample_region
        _mk_cpu(fe, 300)
        fe.do_query(self.PART_DDL.replace("v_sum", "v"))
        src = fe.catalog.table("greptime", "public", "cpu")
        dst = fe.catalog.table("greptime", "public", "agg")
        assert len(dst.regions) == 2
        wrote = 0
        for region in src.regions.values():
            wrote += downsample_region(region, dst, stride_ms=60_000,
                                       aggs={"v": "avg"})
        assert wrote == 2 * 5
        # each bucket row landed in its partition's region
        per_region = [r.snapshot().read_merged().num_rows
                      for r in dst.regions.values()]
        assert sorted(per_region) == [5, 5]
        got = q1(fe, "SELECT host, ts, v FROM agg ORDER BY host, ts")
        assert got[0] == ["a", 0, 29.5]

    def test_flow_into_partitioned_sink(self, fe):
        _mk_cpu(fe, 300)
        fe.do_query(self.PART_DDL)
        fe.do_query("CREATE FLOW f1 SINK TO agg AS SELECT host, "
                    "sum(v) AS v_sum FROM cpu "
                    "GROUP BY host, date_bin(INTERVAL '1 minute', ts)")
        fe.datanode.flow_manager.tick()
        per_region = [r.snapshot().read_merged().num_rows
                      for r in fe.catalog.table(
                          "greptime", "public", "agg").regions.values()]
        assert sorted(per_region) == [5, 5]


class TestDistributedFlows:
    def _cluster(self, data_home):
        from greptimedb_tpu.client import LocalDatanodeClient
        from greptimedb_tpu.frontend.distributed import DistInstance
        from greptimedb_tpu.meta import MetaClient, Peer
        from greptimedb_tpu.meta.kv import MemKv
        from greptimedb_tpu.meta.service import MetaSrv
        srv = MetaSrv(MemKv())
        datanodes, clients = [], {}
        for i in (1, 2):
            dn = DatanodeInstance(DatanodeOptions(
                data_home=f"{data_home}/dn{i}", node_id=i,
                register_numbers_table=False))
            dn.start()
            datanodes.append(dn)
            clients[i] = LocalDatanodeClient(dn)
            srv.register_datanode(Peer(i, f"dn{i}"))
            srv.handle_heartbeat(i)
        return srv, datanodes, MetaClient(srv), clients

    def test_flow_on_distributed_frontend(self, tmp_path):
        from greptimedb_tpu.frontend.distributed import DistInstance
        srv, datanodes, meta, clients = self._cluster(str(tmp_path))
        try:
            fe = DistInstance(meta, clients)
            ctx = QueryContext()
            fe.do_query("CREATE TABLE cpu (host STRING, ts TIMESTAMP "
                        "TIME INDEX, v DOUBLE, PRIMARY KEY(host))", ctx)
            vals = ", ".join(f"('h{i % 3}', {i * 1000}, {float(i)})"
                             for i in range(240))
            fe.do_query("INSERT INTO cpu VALUES " + vals, ctx)
            fe.do_query("CREATE FLOW cpu_1m AS SELECT host, sum(v) AS "
                        "v_sum, count(v) AS v_cnt FROM cpu GROUP BY "
                        "host, date_bin(INTERVAL '1 minute', ts)", ctx)
            fe.flow_manager.tick()
            got = rows(fe.do_query(
                "SELECT host, ts, v_sum FROM cpu_1m "
                "ORDER BY host, ts", ctx)[0])
            assert len(got) == 3 * 4
            # a second frontend on the same meta recovers the flow
            fe2 = DistInstance(meta, clients)
            assert [f.name for f in fe2.flow_manager.flows()] == ["cpu_1m"]
            # incremental: a second tick with no new data writes the
            # refold of the last bucket only
            spec = fe.flow_manager.flows()[0]
            folded = spec.stats["rows_folded"]
            fe.flow_manager.tick()
            assert spec.stats["rows_folded"] - folded <= 3 * 60
        finally:
            for dn in datanodes:
                dn.shutdown()
