"""Query engine tests: fallback executor, TPU fast path, SHOW/DESCRIBE.

The fallback (pandas) and TPU paths are cross-checked on identical data —
the fallback is the oracle, mirroring how the reference validates pushed
scans against DataFusion."""

import math

import numpy as np
import pytest

from greptimedb_tpu import DEFAULT_CATALOG_NAME as CAT, DEFAULT_SCHEMA_NAME as SCH
from greptimedb_tpu.catalog import MemoryCatalogManager
from greptimedb_tpu.datatypes import data_type as dt
from greptimedb_tpu.datatypes.schema import ColumnSchema, Schema, SemanticType
from greptimedb_tpu.errors import TableNotFoundError, UnsupportedError
from greptimedb_tpu.mito import MitoEngine
from greptimedb_tpu.query import QueryEngine
from greptimedb_tpu.query import agg_plan, tpu_exec
from greptimedb_tpu.storage import scan_cache
from greptimedb_tpu.session import QueryContext
from greptimedb_tpu.sql import parse_sql
from greptimedb_tpu.storage.engine import EngineConfig, StorageEngine
from greptimedb_tpu.table import CreateTableRequest, NumbersTable


@pytest.fixture(autouse=True)
def _force_tpu_dispatch(monkeypatch):
    """These tests cross-check the TPU path against the fallback on small
    tables; disable the cost-based row threshold so the device path actually
    executes (its dispatch behavior is tested separately below)."""
    monkeypatch.setattr(tpu_exec, "TPU_DISPATCH_MIN_ROWS", 0)


def test_cost_dispatch_small_scan_uses_cpu(tmp_path, monkeypatch):
    """BASELINE config 1 regression: small scans must take the CPU columnar
    path — exact float64 results, no device round-trip latency."""
    monkeypatch.setattr(tpu_exec, "TPU_DISPATCH_MIN_ROWS", 131072)
    storage = StorageEngine(EngineConfig(data_home=str(tmp_path)))
    mito = MitoEngine(storage)
    cm = MemoryCatalogManager()
    schema = Schema([
        ColumnSchema("host", dt.STRING, nullable=False,
                     semantic_type=SemanticType.TAG),
        ColumnSchema("ts", dt.TIMESTAMP_MILLISECOND, nullable=False,
                     semantic_type=SemanticType.TIMESTAMP),
        ColumnSchema("cpu", dt.FLOAT64),
    ])
    t = mito.create_table(CreateTableRequest(
        "monitor", schema, primary_key_indices=[0]))
    cm.register_table(CAT, SCH, "monitor", t)
    t.insert({"host": ["host1", "host2"], "ts": [1000, 1000],
              "cpu": [66.6, 77.7]})
    engine = QueryEngine(cm)
    executed = []
    orig = tpu_exec.region_moment_frames
    monkeypatch.setattr(tpu_exec, "region_moment_frames",
                        lambda *a, **k: (executed.append(1), orig(*a, **k))[1])
    rows = run(engine, "SELECT host, avg(cpu) AS c FROM monitor "
                       "GROUP BY host ORDER BY host").batches[0].to_pylist()
    # float64-exact: 66.6 survives only on the CPU path (device mirror is f32)
    assert [(r["host"], r["c"]) for r in rows] == \
        [("host1", 66.6), ("host2", 77.7)]
    assert executed == [], "small scan took the device path"
    storage.close()


@pytest.fixture()
def world(tmp_path):
    storage = StorageEngine(EngineConfig(data_home=str(tmp_path)))
    mito = MitoEngine(storage)
    cm = MemoryCatalogManager()
    schema = Schema([
        ColumnSchema("host", dt.STRING, nullable=False,
                     semantic_type=SemanticType.TAG),
        ColumnSchema("region", dt.STRING, nullable=False,
                     semantic_type=SemanticType.TAG),
        ColumnSchema("ts", dt.TIMESTAMP_MILLISECOND, nullable=False,
                     semantic_type=SemanticType.TIMESTAMP),
        ColumnSchema("cpu", dt.FLOAT64),
        ColumnSchema("mem", dt.FLOAT64),
    ])
    table = mito.create_table(CreateTableRequest(
        "monitor", schema, primary_key_indices=[0, 1]))
    rng = np.random.default_rng(9)
    n = 4000
    hosts = [f"h{i % 5}" for i in range(n)]
    regions = ["east" if i % 2 else "west" for i in range(n)]
    ts = (np.arange(n) * 250).tolist()          # 0..1000s, 4 per second
    cpu = rng.random(n).round(4).tolist()
    mem = [None if i % 17 == 0 else float(i % 100) for i in range(n)]
    table.insert({"host": hosts, "region": regions, "ts": ts,
                  "cpu": cpu, "mem": mem})
    cm.register_table(CAT, SCH, "monitor", table)
    cm.register_table(CAT, SCH, "numbers", NumbersTable())
    engine = QueryEngine(cm)
    return engine, table, dict(host=hosts, region=regions, ts=ts, cpu=cpu,
                               mem=mem)


def run(engine, sql):
    return engine.execute(parse_sql(sql), QueryContext())


class TestFallback:
    def test_select_star_limit(self, world):
        engine, *_ = world
        out = run(engine, "SELECT * FROM monitor ORDER BY ts LIMIT 3")
        assert out.num_rows == 3
        assert out.schema.names() == ["host", "region", "ts", "cpu", "mem"]

    def test_projection_exprs(self, world):
        engine, *_ = world
        out = run(engine, "SELECT cpu * 100 AS pct, host FROM monitor "
                          "WHERE ts = 0")
        row = out.batches[0].to_pylist()[0]
        assert math.isclose(row["pct"], world[2]["cpu"][0] * 100)

    def test_where_and_order(self, world):
        engine, _, data = world
        out = run(engine, "SELECT ts FROM monitor WHERE host = 'h1' AND "
                          "ts < 10000 ORDER BY ts DESC")
        vals = [r["ts"] for r in out.batches[0].to_pylist()]
        want = sorted((t for h, t in zip(data["host"], data["ts"])
                       if h == "h1" and t < 10000), reverse=True)
        assert vals == want

    def test_numbers(self, world):
        engine, *_ = world
        out = run(engine, "SELECT number FROM numbers ORDER BY number DESC "
                          "LIMIT 5")
        assert [r["number"] for r in out.batches[0].to_pylist()] == \
            [99, 98, 97, 96, 95]

    def test_no_from(self, world):
        engine, *_ = world
        out = run(engine, "SELECT 1 + 1, 'x'")
        row = out.batches[0].to_pylist()[0]
        assert list(row.values()) == [2, "x"]

    def test_case_and_functions(self, world):
        engine, *_ = world
        out = run(engine, """
            SELECT host, CASE WHEN cpu > 0.5 THEN 'hot' ELSE 'cold' END AS t
            FROM monitor WHERE ts = 0""")
        assert out.batches[0].to_pylist()[0]["t"] in ("hot", "cold")
        out = run(engine, "SELECT abs(-3.5), pow(2, 10)")
        row = list(out.batches[0].to_pylist()[0].values())
        assert row == [3.5, 1024.0]

    def test_aggregate_with_expr_group(self, world):
        engine, _, data = world
        # group by an expression the TPU path doesn't take (modulo)
        out = run(engine, """
            SELECT ts % 2 AS par, count(*) AS c FROM monitor GROUP BY par
            ORDER BY par""")
        rows = out.batches[0].to_pylist()
        assert sum(r["c"] for r in rows) == 4000

    def test_table_not_found(self, world):
        engine, *_ = world
        with pytest.raises(TableNotFoundError):
            run(engine, "SELECT * FROM nope")

    def test_distinct(self, world):
        engine, *_ = world
        out = run(engine, "SELECT DISTINCT region FROM monitor ORDER BY region")
        assert [r["region"] for r in out.batches[0].to_pylist()] == \
            ["east", "west"]

    def test_count_distinct(self, world):
        engine, *_ = world
        out = run(engine, "SELECT count(DISTINCT host) AS c FROM monitor")
        assert out.batches[0].to_pylist()[0]["c"] == 5

    def test_having(self, world):
        engine, _, data = world
        out = run(engine, """
            SELECT host, count(*) AS c FROM monitor GROUP BY host
            HAVING count(*) > 100 ORDER BY host""")
        assert all(r["c"] == 800 for r in out.batches[0].to_pylist())

    def test_subquery_from(self, world):
        engine, *_ = world
        out = run(engine, """
            SELECT count(*) AS c FROM
            (SELECT host FROM monitor WHERE ts < 1000) s""")
        assert out.batches[0].to_pylist()[0]["c"] == 4

    def test_correlated_exists_unsupported_error(self, world):
        """An unqualified outer-column reference inside EXISTS surfaces
        the 'correlated ... not supported' taxonomy error, not a raw
        column-not-found."""
        from greptimedb_tpu.errors import UnsupportedError
        engine, *_ = world
        with pytest.raises(UnsupportedError, match="correlated"):
            run(engine, """
                SELECT host FROM monitor m WHERE EXISTS
                (SELECT 1 FROM monitor WHERE host = no_such_col)""")


class TestTpuPath:
    def _oracle(self, engine, sql, monkeypatch):
        """Run the same query with the TPU path disabled."""
        import greptimedb_tpu.query.tpu_exec as tx
        orig = tx.try_execute
        monkeypatch.setattr(tx, "try_execute", lambda *a, **k: None)
        try:
            return run(engine, sql)
        finally:
            monkeypatch.setattr(tx, "try_execute", orig)

    @pytest.mark.parametrize("sql", [
        "SELECT host, avg(cpu) FROM monitor GROUP BY host",
        "SELECT host, region, max(cpu), min(cpu) FROM monitor "
        "GROUP BY host, region",
        "SELECT host, count(*) FROM monitor WHERE ts >= 100000 AND "
        "ts < 500000 GROUP BY host",
        "SELECT host, sum(mem), count(mem) FROM monitor GROUP BY host",
        "SELECT host, date_bin(INTERVAL '1 minute', ts) AS minute, "
        "avg(cpu) FROM monitor GROUP BY host, minute",
        "SELECT avg(cpu), max(mem), count(*) FROM monitor",
        "SELECT host, stddev(cpu) FROM monitor GROUP BY host",
        "SELECT host, first(cpu), last(cpu) FROM monitor GROUP BY host",
        "SELECT region, avg(cpu) FROM monitor WHERE host != 'h0' "
        "GROUP BY region",
        "SELECT host, avg(cpu) FROM monitor WHERE mem > 50 GROUP BY host",
        "SELECT host, avg(cpu) AS a FROM monitor GROUP BY host "
        "HAVING avg(cpu) > 0.4 ORDER BY a DESC LIMIT 3",
    ])
    def test_matches_fallback(self, world, sql, monkeypatch):
        engine, table, _ = world
        a = __import__("greptimedb_tpu.query.planner",
                       fromlist=["analyze"]).analyze(parse_sql(sql))
        plan = agg_plan.plan_for(table, a, parse_sql(sql))
        assert plan is not None, f"expected TPU plan for: {sql}"
        got = run(engine, sql)
        want = self._oracle(engine, sql, monkeypatch)
        gr = got.batches[0].to_pylist()
        wr = want.batches[0].to_pylist()
        key = lambda r: tuple(str(v) for v in r.values())
        if "ORDER BY" not in sql:
            gr = sorted(gr, key=key)
            wr = sorted(wr, key=key)
        assert len(gr) == len(wr), sql
        for g, w in zip(gr, wr):
            assert list(g) == list(w), sql
            for k in g:
                gv, wv = g[k], w[k]
                if isinstance(gv, float) and isinstance(wv, float):
                    if math.isnan(gv) and math.isnan(wv):
                        continue
                    assert math.isclose(gv, wv, rel_tol=1e-3, abs_tol=1e-4), \
                        (sql, k, gv, wv)
                else:
                    assert gv == wv, (sql, k, gv, wv)

    def test_plan_rejects_unsupported(self, world):
        engine, table, _ = world
        for sql in [
            "SELECT host, percentile(cpu, 50) FROM monitor GROUP BY host",
            "SELECT ts % 2, count(*) FROM monitor GROUP BY 1",
            "SELECT host, avg(abs(cpu)) FROM monitor GROUP BY host",
            # distinct sketches only pay on the distributed pushdown; a
            # LOCAL table keeps the exact fallback (ISSUE 14)
            "SELECT host, count(DISTINCT region) FROM monitor GROUP BY host",
        ]:
            stmt = parse_sql(sql)
            a = __import__("greptimedb_tpu.query.planner",
                           fromlist=["analyze"]).analyze(stmt)
            assert agg_plan.plan_for(table, a, stmt) is None, sql

    def test_plan_accepts_expression_args(self, world):
        """ISSUE 14: arithmetic agg arguments plan as virtual expression
        moments instead of falling back."""
        engine, table, _ = world
        for sql in [
            "SELECT host, avg(cpu + 1) FROM monitor GROUP BY host",
            "SELECT host, sum(cpu * mem) FROM monitor GROUP BY host",
        ]:
            stmt = parse_sql(sql)
            a = __import__("greptimedb_tpu.query.planner",
                           fromlist=["analyze"]).analyze(stmt)
            plan = agg_plan.plan_for(table, a, stmt)
            assert plan is not None and plan.field_exprs, sql


class TestShow:
    def test_show_describe(self, world):
        engine, *_ = world
        out = run(engine, "SHOW TABLES")
        names = [r["Tables"] for r in out.batches[0].to_pylist()]
        assert "monitor" in names
        out = run(engine, "SHOW TABLES LIKE 'mon%'")
        assert [r["Tables"] for r in out.batches[0].to_pylist()] == ["monitor"]
        out = run(engine, "DESCRIBE monitor")
        rows = out.batches[0].to_pylist()
        by_col = {r["Column"]: r for r in rows}
        assert by_col["ts"]["Key"] == "TIME INDEX"
        assert by_col["host"]["Semantic Type"] == "TAG"
        assert by_col["cpu"]["Semantic Type"] == "FIELD"
        out = run(engine, "SHOW CREATE TABLE monitor")
        ddl = out.batches[0].to_pylist()[0]["Create Table"]
        assert "TIME INDEX (ts)" in ddl and "PRIMARY KEY (host, region)" in ddl

    def test_explain(self, world):
        engine, *_ = world
        out = run(engine, "EXPLAIN SELECT host, avg(cpu) FROM monitor "
                          "GROUP BY host")
        plan = out.batches[0].to_pylist()[0]["plan"]
        assert "TpuAggregateExec" in plan


class TestReviewRegressions:
    def test_case_on_filtered_frame(self, world):
        # CASE over a WHERE-filtered frame must align with the frame index
        engine, _, data = world
        out = run(engine, """
            SELECT ts, CASE WHEN cpu > 0.5 THEN 'hot' ELSE 'cold' END AS t
            FROM monitor WHERE ts >= 500 AND ts < 1500 ORDER BY ts""")
        rows = out.batches[0].to_pylist()
        assert len(rows) == 4
        for r in rows:
            i = data["ts"].index(r["ts"])
            want = "hot" if data["cpu"][i] > 0.5 else "cold"
            assert r["t"] == want

    def test_constant_projection_empty_result(self, world):
        engine, *_ = world
        out = run(engine, "SELECT 1 AS one FROM monitor WHERE ts < 0")
        assert out.num_rows == 0
        # but SELECT without FROM still yields one row
        assert run(engine, "SELECT 1").num_rows == 1

    def test_fractional_time_bounds_match_fallback(self, world, monkeypatch):
        engine, table, _ = world
        sql = ("SELECT count(*) AS c FROM monitor WHERE ts >= 499.5 "
               "AND ts < 750.5")
        got = run(engine, sql).batches[0].to_pylist()
        import greptimedb_tpu.query.tpu_exec as tx
        monkeypatch.setattr(tx, "try_execute", lambda *a, **k: None)
        want = run(engine, sql).batches[0].to_pylist()
        assert got == want

    def test_unaliased_aggregate_names(self, world):
        engine, *_ = world
        out = run(engine, "SELECT host, avg(cpu) FROM monitor GROUP BY host")
        assert out.schema.names() == ["host", "avg(cpu)"]


def test_alter_on_demand_rejects_new_tags(tmp_path):
    from greptimedb_tpu.datanode import DatanodeInstance, DatanodeOptions
    from greptimedb_tpu.frontend import FrontendInstance
    from greptimedb_tpu.errors import InvalidArgumentsError
    dn = DatanodeInstance(DatanodeOptions(data_home=str(tmp_path)))
    fe = FrontendInstance(dn)
    fe.start()
    fe.handle_row_insert("up", {"host": ["a"], "greptime_timestamp": [1000],
                                "greptime_value": [1.0]},
                         tag_columns=["host"])
    with pytest.raises(InvalidArgumentsError, match="tag"):
        fe.handle_row_insert(
            "up", {"host": ["a"], "az": ["az1"],
                   "greptime_timestamp": [2000], "greptime_value": [2.0]},
            tag_columns=["host", "az"])
    fe.shutdown()


class TestAdviceRegressions:
    """Regressions for the round-1 advisor findings (ADVICE.md)."""

    def _partitioned(self, tmp_path):
        from greptimedb_tpu.mito import MitoEngine
        from greptimedb_tpu.storage.engine import EngineConfig, StorageEngine
        storage = StorageEngine(EngineConfig(data_home=str(tmp_path)))
        mito = MitoEngine(storage)
        stmt = parse_sql("""
            CREATE TABLE p (host STRING, ts TIMESTAMP TIME INDEX,
                            cpu DOUBLE, PRIMARY KEY(host))
            PARTITION BY RANGE COLUMNS (host) (
              PARTITION r0 VALUES LESS THAN ('m'),
              PARTITION r1 VALUES LESS THAN (MAXVALUE))""")
        schema = Schema([
            ColumnSchema("host", dt.STRING, nullable=False,
                         semantic_type=SemanticType.TAG),
            ColumnSchema("ts", dt.TIMESTAMP_MILLISECOND, nullable=False,
                         semantic_type=SemanticType.TIMESTAMP),
            ColumnSchema("cpu", dt.FLOAT64),
        ])
        t = mito.create_table(CreateTableRequest(
            "p", schema, primary_key_indices=[0], partitions=stmt.partitions))
        cm = MemoryCatalogManager()
        cm.register_table(CAT, SCH, "p", t)
        return QueryEngine(cm), t

    def test_first_last_across_regions_absolute_ts(self, tmp_path):
        # region bases differ: r1's earliest row (ts=50) precedes r0's
        # (ts=100); region-relative min_ts would tie at 0 and pick r0
        engine, t = self._partitioned(tmp_path)
        t.insert({"host": ["alpha", "alpha", "zulu", "zulu"],
                  "ts": [100, 200, 50, 300],
                  "cpu": [111.0, 5.0, 999.0, 7.0]})
        out = run(engine, "SELECT first(cpu) AS f, last(cpu) AS l FROM p")
        row = out.batches[0].to_pylist()[0]
        assert row["f"] == 999.0    # value at absolute earliest ts=50
        assert row["l"] == 7.0      # value at absolute latest ts=300

    def test_fallback_first_without_ts_projection(self, tmp_path, monkeypatch):
        # CPU fallback must project the time index even when the query
        # doesn't reference it, so first/last stay time-ordered. Scan order
        # is series-major (host asc, ts asc): host 'b' holds the earliest
        # row, so unsorted scan order would return 'a's value.
        engine, t = self._partitioned(tmp_path)
        t.insert({"host": ["a", "a", "b", "b"],
                  "ts": [100, 200, 10, 300],
                  "cpu": [111.0, 5.0, 999.0, 7.0]})
        import greptimedb_tpu.query.tpu_exec as tx
        monkeypatch.setattr(tx, "try_execute", lambda *a, **k: None)
        out = run(engine, "SELECT first(cpu) AS f, last(cpu) AS l FROM p")
        row = out.batches[0].to_pylist()[0]
        assert row["f"] == 999.0 and row["l"] == 7.0

    def test_date_trunc_week_monday_aligned(self, world, monkeypatch):
        engine, *_ = world
        from greptimedb_tpu.query.functions import _date_trunc
        # 1970-01-08 (Thursday) truncates to Monday 1970-01-05
        assert _date_trunc("week", [7 * 86_400_000])[0] == 4 * 86_400_000
        # pre-epoch-Monday values floor to the previous Monday
        assert _date_trunc("week", [0])[0] == 4 * 86_400_000 - 604_800_000
        # TPU bucket path agrees with the fallback
        sql = ("SELECT date_trunc('week', ts) AS w, count(*) AS c "
               "FROM monitor GROUP BY w")
        got = run(engine, sql).batches[0].to_pylist()
        import greptimedb_tpu.query.tpu_exec as tx
        monkeypatch.setattr(tx, "try_execute", lambda *a, **k: None)
        want = run(engine, sql).batches[0].to_pylist()
        key = lambda r: r["w"]
        assert sorted(got, key=key) == sorted(want, key=key)


class TestIncrementalScanCache:
    """VERDICT round-1 weakness 5: scan prep must be proportional to new
    data — version bumps merge deltas instead of re-reading the region."""

    def _mk(self, tmp_path):
        from greptimedb_tpu.mito import MitoEngine
        from greptimedb_tpu.storage.engine import EngineConfig, StorageEngine
        storage = StorageEngine(EngineConfig(data_home=str(tmp_path)))
        mito = MitoEngine(storage)
        schema = Schema([
            ColumnSchema("host", dt.STRING, nullable=False,
                         semantic_type=SemanticType.TAG),
            ColumnSchema("ts", dt.TIMESTAMP_MILLISECOND, nullable=False,
                         semantic_type=SemanticType.TIMESTAMP),
            ColumnSchema("cpu", dt.FLOAT64),
        ])
        t = mito.create_table(CreateTableRequest(
            "inc", schema, primary_key_indices=[0]))
        cm = MemoryCatalogManager()
        cm.register_table(CAT, SCH, "inc", t)
        return QueryEngine(cm), t, storage

    def test_incremental_matches_full(self, tmp_path):
        engine, t, storage = self._mk(tmp_path)
        t.insert({"host": ["a", "b"], "ts": [1, 2], "cpu": [1.0, 2.0]})
        r1 = run(engine, "SELECT host, sum(cpu) AS s FROM inc GROUP BY host")
        t.insert({"host": ["a", "c"], "ts": [3, 4], "cpu": [3.0, 4.0]})
        got = run(engine, "SELECT host, sum(cpu) AS s FROM inc "
                          "GROUP BY host").batches[0].to_pylist()
        cache = scan_cache.SCAN_CACHE
        scan_cache.SCAN_CACHE = scan_cache._ScanCache()   # force full rebuild
        try:
            want = run(engine, "SELECT host, sum(cpu) AS s FROM inc "
                               "GROUP BY host").batches[0].to_pylist()
        finally:
            scan_cache.SCAN_CACHE = cache
        key = lambda r: r["host"]
        assert sorted(got, key=key) == sorted(want, key=key)
        storage.close()

    def test_update_and_delete_through_delta(self, tmp_path):
        engine, t, storage = self._mk(tmp_path)
        t.insert({"host": ["a", "b"], "ts": [1, 2], "cpu": [1.0, 2.0]})
        run(engine, "SELECT sum(cpu) FROM inc")      # build cache
        t.insert({"host": ["a"], "ts": [1], "cpu": [10.0]})   # overwrite
        t.delete({"host": ["b"], "ts": [2]})
        got = run(engine, "SELECT sum(cpu) AS s FROM inc")
        assert got.batches[0].to_pylist()[0]["s"] == 10.0
        storage.close()

    def test_flush_does_not_reread_ssts(self, tmp_path):
        engine, t, storage = self._mk(tmp_path)
        region = next(iter(t.regions.values()))
        t.insert({"host": ["a"], "ts": [1], "cpu": [1.0]})
        run(engine, "SELECT sum(cpu) FROM inc")      # cache covers seq 1
        t.flush()                                    # rows move to an SST
        reads = []
        orig = region.access_layer.read_sst
        region.access_layer.read_sst = \
            lambda *a, **k: (reads.append(1), orig(*a, **k))[1]
        got = run(engine, "SELECT sum(cpu) AS s FROM inc")
        assert got.batches[0].to_pylist()[0]["s"] == 1.0
        assert reads == [], "flushed-but-covered SST was re-read"
        region.access_layer.read_sst = orig
        storage.close()

    def test_ttl_retraction_rebuilds(self, tmp_path):
        engine, t, storage = self._mk(tmp_path)
        region = next(iter(t.regions.values()))
        region.ttl_ms = 60_000
        now = 1_000_000
        t.insert({"host": ["a", "a"], "ts": [now - 120_000, now],
                  "cpu": [1.0, 2.0]})
        run(engine, "SELECT sum(cpu) FROM inc")      # cache holds both rows
        t.flush()
        region.compact(now_ms=now)                   # TTL drops the old row
        got = run(engine, "SELECT sum(cpu) AS s FROM inc")
        assert got.batches[0].to_pylist()[0]["s"] == 2.0
        storage.close()


def test_incremental_cache_randomized_oracle(tmp_path):
    """Property test: random interleavings of inserts/overwrites/deletes/
    flushes must leave the incremental cache identical to a full rebuild."""
    from greptimedb_tpu.storage.engine import EngineConfig, StorageEngine
    from greptimedb_tpu.storage.write_batch import WriteBatch
    rng = np.random.default_rng(7)
    schema = Schema([
        ColumnSchema("host", dt.STRING, nullable=False,
                     semantic_type=SemanticType.TAG),
        ColumnSchema("ts", dt.TIMESTAMP_MILLISECOND, nullable=False,
                     semantic_type=SemanticType.TIMESTAMP),
        ColumnSchema("cpu", dt.FLOAT64),
    ])
    storage = StorageEngine(EngineConfig(data_home=str(tmp_path)))
    r = storage.create_region("rnd", schema)
    cache = scan_cache._ScanCache()
    for round_ in range(12):
        n = int(rng.integers(1, 60))
        hosts = [f"h{int(h)}" for h in rng.integers(0, 5, n)]
        ts = rng.integers(0, 200, n).tolist()     # heavy key collisions
        wb = WriteBatch(schema)
        wb.put({"host": hosts, "ts": ts,
                "cpu": rng.random(n).round(3).tolist()})
        r.write(wb)
        if rng.random() < 0.3:
            m = int(rng.integers(1, 10))
            wb = WriteBatch(schema)
            wb.delete({"host": [f"h{int(h)}" for h in rng.integers(0, 5, m)],
                       "ts": rng.integers(0, 200, m).tolist()})
            r.write(wb)
        if rng.random() < 0.4:
            r.flush()
        got = cache.get(r)                        # incremental path
        want = scan_cache._ScanCache().get(r)       # fresh full rebuild
        assert got.num_rows == want.num_rows, f"round {round_}"
        assert np.array_equal(got.series_ids, want.series_ids)
        assert np.array_equal(got.ts, want.ts)
        gv, _ = got.fields["cpu"]
        wv, _ = want.fields["cpu"]
        assert np.allclose(gv, wv, equal_nan=True), f"round {round_}"
    storage.close()
