"""A dashboard that refreshes while the fleet writes (ISSUE 37): the scan
cache is refreshed by what was written (`scan_cache._ScanCache`: an immutable
base with its mirrors and programs, and a tail of the rows written since),
and a statement after a write answers as a from-scratch reference does.

The reference is independent of the refresh: every row ever written, in
write order, deduplicated from scratch (the newest write of a (series,
timestamp) wins, a tombstone drops it) and aggregated in float64 pandas /
numpy (`benchlib/promref.py`, `promlong.py` for PromQL). Two tables take
the same writes: `cpu` (two tags, `usage`, and `idle` with NULLs) for the
full and the narrowed launch, `reqs` (one counter) for lowered PromQL.
Samples lie 3 s off the minute, so no window edge holds one.
"""

import argparse
import os
import sys
import threading
import types

import numpy as np
import pandas as pd
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchlib import promlong  # noqa: E402
from benchlib import promref  # noqa: E402

from greptimedb_tpu.common import telemetry  # noqa: E402
from greptimedb_tpu.datanode.instance import (  # noqa: E402
    DatanodeInstance, DatanodeOptions)
from greptimedb_tpu.frontend.instance import FrontendInstance  # noqa: E402
from greptimedb_tpu.ops.kernels import (  # noqa: E402
    _sorted_grouped_aggregate_pre)
from greptimedb_tpu.query import (  # noqa: E402
    moment_fold, scan_narrow, tpu_exec)
from greptimedb_tpu.storage import scan_cache  # noqa: E402

HOSTS, TICKS, TICK_MS = 12, 480, 10_000
T0 = 1_700_000_040_000 + 3_000              # 3 s past a whole minute
MINUTE0 = T0 - 3_000
RESIDENT = "device-resident (scan cache)"
RTOL = 1e-5                                 # f32 mirrors (PERF.md section 6)
#: h05 is loaded from this tick on: the ticks before it are "older rows"
H05_FIRST = 10


def metric(name: str, **labels) -> float:
    counter = telemetry._counters.get(name)
    if counter is None:
        return 0.0
    child = counter.labels(**labels) if labels else counter
    return child._value.get()


def host(h: int) -> str:
    return f"h{h:02d}"


def usage(h: int, k: int, gen: int = 0) -> float:
    return float((h * 37 + k * 11 + gen * 5) % 1009) / 7.0


def idle(h: int, k: int):
    return None if (h + k) % 7 == 0 else float(h + k / 10)


def reqs(h: int, k: int, gen: int = 0) -> float:
    """A counter at 1e9 that grows 10 to 30 a tick and restarts once."""
    return 1e9 * (k < 150 + h) + 20.0 * k + (h * k) % 11 + gen


def ts(k: int) -> int:
    return T0 + k * TICK_MS


class Db:
    """One frontend, the load, and the log of every write."""

    def __init__(self, data_home: str):
        self.fe = FrontendInstance(DatanodeInstance(DatanodeOptions(
            data_home=data_home, register_numbers_table=False)))
        self.fe.start()
        self.fe.do_query(
            "CREATE TABLE cpu (host STRING, region STRING, "
            "ts TIMESTAMP TIME INDEX, usage DOUBLE, idle DOUBLE, "
            "PRIMARY KEY(host, region))")
        self.fe.do_query(
            "CREATE TABLE reqs (host STRING, ts TIMESTAMP TIME INDEX, "
            "greptime_value DOUBLE, PRIMARY KEY(host))")
        self.log = []                   # (op, host number, tick, generation)
        self.put([(h, k) for h in range(HOSTS) for k in range(TICKS)
                  if h != 5 or k >= H05_FIRST])

    def close(self):
        self.fe.do_query("SET tpu_dispatch_min_rows = 131072")
        self.fe.shutdown()

    # ---- writes, each logged -------------------------------------------
    def put(self, cells, gen: int = 0):
        cells = list(cells)
        self.log += [("put", h, k, gen) for h, k in cells]
        for at in range(0, len(cells), 2000):
            part = cells[at:at + 2000]
            self.fe.do_query(
                "INSERT INTO cpu (host, region, ts, usage, idle) VALUES "
                + ", ".join(
                f"('{host(h)}', 'r{h % 3}', {ts(k)}, {usage(h, k, gen)!r}, "
                f"{'NULL' if idle(h, k) is None else repr(idle(h, k))})"
                for h, k in part))
            self.fe.do_query("INSERT INTO reqs VALUES " + ", ".join(
                f"('{host(h)}', {ts(k)}, {reqs(h, k, gen)!r})"
                for h, k in part))

    def delete(self, cells):
        for h, k in cells:
            self.log.append(("delete", h, k, 0))
            self.fe.do_query(f"DELETE FROM cpu WHERE host = '{host(h)}' "
                             f"AND region = 'r{h % 3}' AND ts = {ts(k)}")
            self.fe.do_query(f"DELETE FROM reqs WHERE host = '{host(h)}' "
                             f"AND ts = {ts(k)}")

    def flush(self):
        self.fe.do_query("ADMIN FLUSH TABLE cpu")
        self.fe.do_query("ADMIN FLUSH TABLE reqs")

    # ---- the from-scratch reference --------------------------------------
    def rows(self) -> pd.DataFrame:
        """Every row ever written, deduplicated from scratch."""
        log = pd.DataFrame(self.log, columns=["op", "h", "k", "gen"])
        log = log[~log.duplicated(["h", "k"], keep="last")]
        log = log[log.op == "put"].sort_values(["h", "k"])
        return pd.DataFrame({
            "h": log.h.to_numpy(), "k": log.k.to_numpy(),
            "host": [host(h) for h in log.h], "ts": [ts(k) for k in log.k],
            "usage": [usage(h, k, g) for h, k, g
                      in zip(log.h, log.k, log.gen)],
            "idle": [np.nan if idle(h, k) is None else idle(h, k)
                     for h, k in zip(log.h, log.k)],
            "reqs": [reqs(h, k, g) for h, k, g
                     in zip(log.h, log.k, log.gen)]})

    def samples(self):
        """`reqs` as the PromQL reference wants a metric: one scrape
        grid, per series the ticks that exist (contiguous by the cases'
        construction)."""
        rows = self.rows()
        hosts = sorted(rows.h.unique())
        ticks = int(rows.k.max()) + 1
        values = np.full((len(hosts), ticks), np.nan)
        values[np.searchsorted(hosts, rows.h), rows.k] = rows.reqs
        exists = ~np.isnan(values)
        first = exists.argmax(axis=1)
        last = ticks - exists[:, ::-1].argmax(axis=1)
        assert (exists.sum(axis=1) == last - first).all(), "a gap"
        return types.SimpleNamespace(
            times=T0 + np.arange(ticks, dtype=np.int64) * TICK_MS,
            values=values, first=first, last=last,
            labels={"host": np.array([host(h) for h in hosts])})

    # ---- statements ---------------------------------------------------------
    def sql(self, sql: str) -> pd.DataFrame:
        self.fe.do_query("SET tpu_dispatch_min_rows = 1")
        out = self.fe.do_query(sql)
        out = out[-1] if isinstance(out, list) else out
        frames = [pd.DataFrame(b.to_pydict()) for b in out.batches]
        return pd.concat(frames, ignore_index=True) if frames else \
            pd.DataFrame()

    def stages(self, sql: str) -> dict:
        rows = self.sql("EXPLAIN ANALYZE " + sql)
        return {r.stage: (float(r.elapsed_ms), r.detail or "")
                for r in rows.itertuples()}

    def region(self, table: str = "cpu"):
        t = self.fe.catalog.table("greptime", "public", table)
        return next(iter(t.regions.values()))


@pytest.fixture
def db(tmp_path):
    d = Db(str(tmp_path))
    yield d
    d.close()


# ---------------------------------------------------------------------------
# the three statement kinds and their references
# ---------------------------------------------------------------------------

AGGS = ("min(usage) AS lo, max(usage) AS hi, count(idle) AS n, "
        "sum(usage) AS s, avg(idle) AS a, first(usage) AS f, "
        "last(usage) AS l")
BY_MINUTE = "date_bin(INTERVAL '1 minute', ts) AS b"
#: three hosts' ranges pad past an eighth of this table: the full launch
#: with the mask from the ranges; one host's take the narrowed launch
PICKED = [1, 5, 7]
IN_PICKED = ", ".join(f"'{host(h)}'" for h in PICKED)

FULL = f"SELECT host, {BY_MINUTE}, {AGGS} FROM cpu GROUP BY host, b"
LAST = f"SELECT host, {AGGS} FROM cpu GROUP BY host"
RANGES_IN = (f"SELECT host, {BY_MINUTE}, {AGGS} FROM cpu "
             f"WHERE host IN ({IN_PICKED}) GROUP BY host, b")
NARROW_EQ = f"SELECT host, {AGGS} FROM cpu WHERE host = 'h05' GROUP BY host"


def sql_reference(rows: pd.DataFrame, hosts=None, by_minute=True):
    if hosts is not None:
        rows = rows[rows.h.isin(hosts)]
    rows = rows.sort_values(["host", "ts"])
    keys = ["host"]
    if by_minute:
        rows = rows.assign(b=(rows.ts - MINUTE0) // 60_000 * 60_000 + MINUTE0)
        keys.append("b")
    g = rows.groupby(keys, sort=True)
    return pd.DataFrame({
        "lo": g.usage.min(), "hi": g.usage.max(), "n": g.idle.count(),
        "s": g.usage.sum(), "a": g.idle.mean(), "f": g.usage.first(),
        "l": g.usage.last()}).reset_index()


def check_sql(db: Db, sql: str, hosts=None, by_minute=True):
    got = db.sql(sql)
    want = sql_reference(db.rows(), hosts, by_minute)
    keys = ["host", "b"] if by_minute else ["host"]
    if by_minute:
        got["b"] = got["b"].astype("int64") // (
            1 if got["b"].dtype.kind in "iu" else 10**6)
    got = got.sort_values(keys).reset_index(drop=True)
    assert len(got) == len(want), (len(got), len(want))
    for key in keys:
        assert list(got[key]) == list(want[key]), key
    assert list(got.n) == list(want.n)
    for col in ("lo", "hi", "s", "a", "f", "l"):
        np.testing.assert_allclose(
            got[col].to_numpy(dtype=float), want[col].to_numpy(dtype=float),
            rtol=RTOL, atol=1e-6, equal_nan=True, err_msg=col)


def end_second(end_tick: int) -> int:
    """The whole minute at or after the last tick before `end_tick`."""
    return (MINUTE0 + -(-(end_tick * TICK_MS) // 60_000) * 60_000) // 1000


def tql(db: Db, expr: str, end_tick: int) -> dict:
    """A range query over the 12 minutes before `end_tick`'s minute, step
    60 s -> {(host, step ms): value}."""
    end_s = end_second(end_tick)
    rows = db.sql(f"TQL EVAL ({end_s - 720}, {end_s}, '60s') {expr}")
    cols = list(rows.columns)
    stamp = rows[cols[-2]]
    stamp = stamp.astype("int64") // (1 if stamp.dtype.kind in "iu"
                                      else 10**6)
    return {(r[0], int(t)): float(v) for r, t, v in zip(
        rows[cols[:-2]].itertuples(index=False), stamp, rows[cols[-1]])}, \
        np.arange(end_s - 720, end_s + 1, 60, dtype=np.int64) * 1000


MAX_OVER_TIME = "max by (host) (max_over_time(reqs[1m]))"
INCREASE = "sum by (host) (increase(reqs[1m]))"


def check_promql(db: Db, end_tick: int):
    s = db.samples()
    keep = np.ones(len(s.first), dtype=bool)
    got, steps = tql(db, MAX_OVER_TIME, end_tick)
    values, ok = promlong.over_time("max", s, keep, steps, 60_000)
    by, values, ok = promlong.aggregate("max", values, ok, [s.labels["host"]])
    want = promref.points(by, steps, values, ok)
    assert set(got) == set(want)
    for key, (v,) in want.items():
        assert got[key] == pytest.approx(v, rel=RTOL), key
    got, steps = tql(db, INCREASE, end_tick)
    values, ok = promref.extrapolated_rate(s, keep, steps, 60_000,
                                           per_second=False)
    by, values, ok = promref.aggregate("sum", values, ok, [s.labels["host"]])
    want = promref.points(by, steps, values, ok)
    assert set(got) == set(want)
    for key, (v,) in want.items():
        # a window's growth is made in float64 over one scan: the level
        # (1e9) costs no digit
        assert got[key] == pytest.approx(v, rel=RTOL, abs=1e-3), key


def check_all(db: Db, end_tick: int):
    check_sql(db, FULL)
    check_sql(db, LAST, by_minute=False)
    check_sql(db, RANGES_IN, hosts=PICKED)
    check_sql(db, NARROW_EQ, hosts=[5], by_minute=False)
    check_promql(db, end_tick)


def warm(db: Db):
    """What a server's warm statements do: the scan caches of both
    tables built, every statement kind sent once, before any write."""
    check_all(db, TICKS)
    assert scan_cache.SCAN_CACHE.get_parts(db.region())[1] is None


# ---------------------------------------------------------------------------
# the cases: what is written after the warm statements, and what the
# refresh may do about it
# ---------------------------------------------------------------------------

def appended(db):
    db.put([(h, k) for k in range(TICKS, TICKS + 4) for h in range(HOSTS)])
    return TICKS + 4


def older_rows(db):
    db.put([(5, k) for k in range(4, H05_FIRST)])
    return TICKS


def overwrite(db):
    db.put([(2, 100), (3, TICKS - 1)], gen=1)
    return TICKS


def delete(db):
    db.delete([(6, 0), (6, 1), (6, 2), (9, TICKS - 1)])
    return TICKS


def new_series(db):
    db.put([(40, k) for k in range(TICKS - 2, TICKS + 2)])
    return TICKS + 2


def through_an_sst(db):
    db.put([(h, k) for k in range(TICKS, TICKS + 3) for h in range(HOSTS)])
    db.flush()
    return TICKS + 3


def past_capacity(db):
    ticks = scan_cache.tail_capacity(HOSTS * TICKS) // HOSTS + 8
    for at in range(TICKS, TICKS + ticks, 100):
        db.put([(h, k) for k in range(at, min(at + 100, TICKS + ticks))
                for h in range(HOSTS)])
    return TICKS + ticks


def tail_then_overwrite_in_the_tail(db):
    db.put([(h, k) for k in range(TICKS, TICKS + 3) for h in range(HOSTS)])
    check_sql(db, LAST, by_minute=False)         # the tail holds them
    db.put([(h, TICKS + 1) for h in (0, 4)], gen=2)
    db.put([(h, TICKS + 3) for h in range(HOSTS)])
    return TICKS + 4


def tail_then_delete(db):
    db.put([(h, k) for k in range(TICKS, TICKS + 3) for h in range(HOSTS)])
    check_sql(db, LAST, by_minute=False)
    db.delete([(7, TICKS + 2), (8, 0)])
    return TICKS + 3


def alter(db):
    db.fe.do_query("ALTER TABLE cpu ADD COLUMN steal DOUBLE")
    db.put([(h, TICKS) for h in range(HOSTS)])
    return TICKS + 1


def ttl_retraction(db):
    """TTL drops the first ticks of every series (the reference's log
    forgets them): the cached scan cannot be refreshed, only rebuilt."""
    db.flush()
    for table in ("cpu", "reqs"):
        region = db.region(table)
        region.ttl_ms = (TICKS - 30) * TICK_MS
        region.compact(now_ms=ts(TICKS))
        assert region.retraction_epoch == 1
    db.log = [e for e in db.log if e[2] >= 30]
    return TICKS


#: (the case, tails kept / merges / rebuilds of `cpu`'s entry it leaves)
CASES = [
    (appended, "tail"),
    (older_rows, "tail"),
    (overwrite, "merge"),
    (delete, "merge"),
    (new_series, "tail"),
    (through_an_sst, "tail"),
    (past_capacity, "merge"),
    (tail_then_overwrite_in_the_tail, "tail"),
    (tail_then_delete, "merge"),
    (alter, "full"),
    (ttl_retraction, "full"),
]


@pytest.mark.parametrize("case,how", CASES, ids=[c.__name__ for c, _ in CASES])
def test_a_statement_after_a_write_answers_the_reference(db, case, how):
    warm(db)
    base = scan_cache.SCAN_CACHE.get_parts(db.region())[0]
    merges, misses = metric("scan_cache_merges"), metric("scan_cache_miss")
    end_tick = case(db)
    check_sql(db, FULL)
    now_base, tail = scan_cache.SCAN_CACHE.get_parts(db.region())
    if how == "tail":
        assert now_base is base and tail is not None and tail.pinned
        assert tail.num_rows == scan_cache.tail_capacity(base.num_rows)
        assert metric("scan_cache_merges") == merges
    elif how == "merge":
        assert now_base is not base and tail is None
        assert metric("scan_cache_merges") > merges
    else:
        assert now_base is not base and tail is None
        assert metric("scan_cache_miss") > misses
        assert metric("scan_cache_merges") == merges
    check_all(db, end_tick)


@pytest.mark.parametrize("sql,path", [
    (FULL, "full"), (LAST, "full"), (RANGES_IN, "full"),
    (NARROW_EQ, "narrow")], ids=["full", "last", "ranges-in", "narrow-eq"])
def test_every_statement_stays_device_resident_over_base_and_tail(db, sql,
                                                                  path):
    warm(db)
    appended(db)
    stages = db.stages(sql)
    assert stages["dispatch"][1] == RESIDENT
    detail = stages["reduce"][1]
    assert "tail_rows=48" in detail, detail
    assert f"path={path}" in detail and f"tail_path={path}" in detail
    # each launch says the forms its program took (ISSUE 45, 46): a few
    # hundred groups, and `first` / `last` read no run id
    assert "sums=edge, ext=rows" in detail, detail
    assert "tail_sums=edge, tail_ext=rows" in detail, detail


def test_lowered_promql_stays_device_resident_after_a_write(db):
    warm(db)
    end_tick = appended(db)
    end_s = end_second(end_tick)
    for expr, grows in ((MAX_OVER_TIME, False), (INCREASE, True)):
        merges = metric("scan_cache_merges")
        stages = db.stages(f"TQL EVAL ({end_s - 720}, {end_s}, '60s') {expr}")
        assert stages["dispatch"][1] == RESIDENT, stages["dispatch"]
        assert "lower" in stages
        # a window's growth too is two launches (ISSUE 44): the tail's
        # derived mirror is made across the seam (`reduce.seam`), nothing
        # merges
        assert metric("scan_cache_merges") == merges
        assert "tail_rows=" in stages["reduce"][1]
        assert ("reduce.seam" in stages) == grows


def test_a_statement_over_closed_history_skips_the_tail(db):
    warm(db)
    appended(db)
    closed = (f"SELECT host, {AGGS} FROM cpu WHERE ts >= {ts(0)} AND "
              f"ts < {ts(TICKS - 1) + 1} GROUP BY host")
    detail = db.stages(closed)["reduce"][1]
    assert "tail=skipped" in detail and "tail_rows" not in detail
    rows = db.rows()
    want = sql_reference(rows[rows.k < TICKS], by_minute=False)
    got = db.sql(closed).sort_values("host").reset_index(drop=True)
    np.testing.assert_allclose(got.s, want.s, rtol=RTOL)
    assert list(got.n) == list(want.n)


def test_closed_history_is_not_refreshed_until_a_row_lands_in_it(db):
    """Every unmerged row lies at or after the statement's range: the
    entry answers as it stands (`cache=hit`, no refresh, the watermark
    stays). A late row inside the range is a refresh."""
    warm(db)
    closed = (f"SELECT host, {AGGS} FROM cpu WHERE ts >= {ts(0)} AND "
              f"ts < {ts(TICKS - 1) + 1} GROUP BY host")

    def check_closed():
        rows = db.rows()
        want = sql_reference(rows[rows.k < TICKS], by_minute=False)
        got = db.sql(closed).sort_values("host").reset_index(drop=True)
        np.testing.assert_allclose(got.s, want.s, rtol=RTOL)
        assert list(got.n) == list(want.n)

    # the load leaves the memtables (a memtable's span bounds its unmerged
    # rows from below, and covers the rows already merged too)
    db.flush()
    appended(db)
    refreshes = metric("scan_cache_incremental")
    assert "cache=hit" in db.stages(closed)["scan_prep"][1]
    check_closed()
    assert metric("scan_cache_incremental") == refreshes
    assert scan_cache.SCAN_CACHE.get_parts(db.region())[1].valid_rows == 48
    older_rows(db)              # ticks 4..9 of h05: inside the range
    assert "cache=incremental" in db.stages(closed)["scan_prep"][1]
    check_closed()
    check_all(db, TICKS + 4)


def _partial(keys, moments, rowcount):
    return moment_fold._RunPartial(
        np.array([k[0] for k in keys], dtype=np.int32),
        np.array([k[1] for k in keys], dtype=np.int64),
        [np.array(m) for m in moments], np.array(rowcount), None)


def test_fold_runs_is_finalizes_fold_of_two_partials():
    """Runs (series, bucket) that base and tail both hold fold as
    `_finalize` folds two rows of one group; the others pass through."""
    from greptimedb_tpu.query.ir import plan_from_specs
    from greptimedb_tpu.query.agg_plan import BucketGroup
    from greptimedb_tpu.datatypes import ColumnSchema, Schema, SemanticType
    from greptimedb_tpu.datatypes import data_type as dt
    schema = Schema([
        ColumnSchema("host", dt.STRING, nullable=False,
                     semantic_type=SemanticType.TAG),
        ColumnSchema("ts", dt.TIMESTAMP_MILLISECOND, nullable=False,
                     semantic_type=SemanticType.TIMESTAMP),
        ColumnSchema("v", dt.FLOAT64)])
    plan = plan_from_specs(
        schema, [("s", "sum", "v"), ("lo", "min", "v"), ("hi", "max", "v"),
                 ("f", "first", "v"), ("l", "last", "v")],
        group_tags=["host"], bucket=BucketGroup(60_000, 0, "b"))
    ops = [(m.op, m.column) for m in plan.moments]
    assert sorted(ops) == sorted(
        (op, "v") for op in ("sum", "count", "min", "max", "first",
                             "min_ts", "last", "max_ts"))
    nan = float("nan")

    def partial(keys, rowcount, **by_op):
        return _partial(keys, [by_op[op] for op, _ in ops], rowcount)

    base = partial(
        [(0, 5), (0, 6), (2, 6)], [1, 2, 1],
        sum=[1.0, 2.0, nan], count=[1, 2, 0], min=[1.0, 0.5, nan],
        max=[1.0, 1.5, nan], first=[1.0, 0.5, nan],
        min_ts=[300, 360, 2**40], last=[1.0, 1.5, nan],
        max_ts=[300, 390, -2**40])
    tail = partial(
        [(0, 6), (1, 6), (2, 6)], [1, 1, 1],
        sum=[4.0, 9.0, 3.0], count=[1, 1, 1], min=[4.0, 9.0, 3.0],
        max=[4.0, 9.0, 3.0], first=[4.0, 9.0, 3.0], min_ts=[400, 410, 420],
        last=[4.0, 9.0, 3.0], max_ts=[400, 410, 420])
    out = moment_fold._fold_runs(base, tail, plan)
    assert list(zip(out.sids.tolist(), out.buckets.tolist())) == \
        [(0, 5), (0, 6), (2, 6), (1, 6)]
    got = {op: m.tolist() for (op, _), m in zip(ops, out.moments)}
    assert got["sum"] == [1.0, 6.0, 3.0, 9.0]
    assert got["count"] == [1, 3, 1, 1]
    assert got["min"] == [1.0, 0.5, 3.0, 9.0]
    assert got["max"] == [1.0, 4.0, 3.0, 9.0]
    assert got["first"] == [1.0, 0.5, 3.0, 9.0]     # (2, 6): base had NULLs
    assert got["last"] == [1.0, 4.0, 3.0, 9.0]
    assert got["min_ts"] == [300, 360, 420, 410]
    assert got["max_ts"] == [300, 400, 420, 410]
    assert out.rowcount.tolist() == [1, 3, 2, 1]
    # and `_finalize` over the two frames says the same
    import pandas as pd
    base.series_dict = tail.series_dict = out.series_dict = \
        types.SimpleNamespace(tag_id_column=lambda sids, i: (
            sids, ["a", "b", "c"]))
    folded = moment_fold._finalize(moment_fold._partial_frame(out, plan), plan)
    both = moment_fold._finalize(pd.concat(
        [moment_fold._partial_frame(base, plan),
         moment_fold._partial_frame(tail, plan)], ignore_index=True), plan)
    key = list(folded.columns[:2])
    pd.testing.assert_frame_equal(
        folded.sort_values(key).reset_index(drop=True),
        both.sort_values(key).reset_index(drop=True), check_dtype=False)


# ---------------------------------------------------------------------------
# what a refresh costs, counted
# ---------------------------------------------------------------------------

def test_a_refresh_leaves_the_base_and_uploads_the_tail_alone(db):
    warm(db)
    region = db.region()
    base = scan_cache.SCAN_CACHE.get_parts(region)[0]
    mirrors = {k: id(v) for k, v in base.device.items()
               if k.startswith(("f:", "v:", "__ts", "__all_valid"))}
    arrays = (id(base.series_ids), id(base.ts),
              {n: id(v) for n, (v, _) in base.fields.items()})
    launched = set(base.launched)
    assert mirrors and launched
    capacity = scan_cache.tail_capacity(base.num_rows)
    # on the device a row of `cpu` holds: ts 4 B, usage and idle 4 B each,
    # idle's validity, the pad mask and the all-valid mask 1 B each
    row_bytes = 4 + 4 + 4 + 1 + 1 + 1
    for n in (1, 3, 2):
        rows_before = metric("scan_cache_delta_rows")
        bytes_before = metric("scan_cache_upload_bytes")
        refreshes = metric("scan_cache_incremental")
        last = max(k for _, _, k, _ in db.log)
        db.put([(h, k) for k in range(last + 1, last + 1 + n)
                for h in range(HOSTS)])
        check_sql(db, LAST, by_minute=False)
        assert metric("scan_cache_incremental") == refreshes + 1
        assert metric("scan_cache_delta_rows") == rows_before + n * HOSTS
        uploaded = metric("scan_cache_upload_bytes") - bytes_before
        # the tail goes up whole, at its capacity, whatever the delta:
        # at most 2 x capacity x the bytes of a row, nothing of the base
        assert 0 < uploaded <= 2 * capacity * row_bytes, uploaded
    now, tail = scan_cache.SCAN_CACHE.get_parts(region)
    assert now is base and tail.valid_rows == 6 * HOSTS
    assert {k: id(v) for k, v in base.device.items() if k in mirrors} == \
        mirrors
    assert arrays == (id(base.series_ids), id(base.ts),
                      {n: id(v) for n, (v, _) in base.fields.items()})
    assert launched <= base.launched


def test_the_parts_of_scan_prep_lie_inside_their_row(db):
    import re
    warm(db)
    appended(db)
    stages = db.stages(LAST)
    t0 = {name: int(re.search(r"t0_ns=(\d+)$", detail).group(1))
          for name, (_, detail) in stages.items()
          if name.startswith("scan_prep")}
    assert set(t0) == {"scan_prep", "scan_prep.delta", "scan_prep.apply",
                       "scan_prep.upload"}
    assert "cache=incremental" in stages["scan_prep"][1]
    lo, hi = t0["scan_prep"], t0["scan_prep"] + stages["scan_prep"][0] * 1e6
    parts = ["scan_prep.delta", "scan_prep.apply", "scan_prep.upload"]
    for a, b in zip(parts, parts[1:]):
        assert t0[a] + stages[a][0] * 1e6 <= t0[b] + 1e3, (a, b)
    assert lo <= t0[parts[0]]
    assert t0[parts[-1]] + stages[parts[-1]][0] * 1e6 <= hi + 1e3
    assert sum(stages[p][0] for p in parts) <= stages["scan_prep"][0] + 1e-3
    # a statement that finds the cache current has no such part
    assert not [s for s in db.stages(LAST) if s.startswith("scan_prep.")]


def test_the_counters_move_by_what_was_written(db):
    warm(db)
    before = {n: metric(n) for n in (
        "scan_cache_hit", "scan_cache_incremental", "scan_cache_miss",
        "scan_cache_delta_rows", "scan_cache_merges", "scan_device_rows")}
    appended(db)                                    # 4 ticks x 12 hosts
    db.sql(LAST)
    rows = HOSTS * TICKS - H05_FIRST
    assert metric("scan_cache_incremental") == \
        before["scan_cache_incremental"] + 1
    assert metric("scan_cache_delta_rows") == \
        before["scan_cache_delta_rows"] + 48
    # the launch over the base and the one over the tail's rows
    assert metric("scan_device_rows") == \
        before["scan_device_rows"] + rows + 48
    db.sql(LAST)
    assert metric("scan_cache_hit") == before["scan_cache_hit"] + 1
    assert metric("scan_cache_delta_rows") == \
        before["scan_cache_delta_rows"] + 48
    assert (metric("scan_cache_miss"), metric("scan_cache_merges")) == \
        (before["scan_cache_miss"], before["scan_cache_merges"])


def test_other_callers_receive_one_sorted_scan(db):
    warm(db)
    appended(db)
    region = db.region()
    assert scan_cache.SCAN_CACHE.get_parts(region)[1] is not None
    scan = scan_cache.SCAN_CACHE.get(region)
    assert scan_cache.SCAN_CACHE.get_parts(region) == (scan, None)
    rows = db.rows()
    assert scan.num_rows == len(rows) and scan.valid_rows is None
    order = np.lexsort((scan.ts, scan.series_ids))
    assert (order == np.arange(scan.num_rows)).all()
    np.testing.assert_array_equal(np.sort(scan.fields["usage"][0]),
                                  np.sort(rows.usage.to_numpy()))


# ---------------------------------------------------------------------------
# no program for a table length: k writes of k sizes compile nothing
# ---------------------------------------------------------------------------

BIG_HOSTS, BIG_TICKS = 400, 330             # 132,000 rows: past the floor


@pytest.fixture(scope="module")
def big(tmp_path_factory):
    """A table of `TPU_DISPATCH_MIN_ROWS` rows or more, which is where a
    statement compiles the programs of its base's tail."""
    fe = FrontendInstance(DatanodeInstance(DatanodeOptions(
        data_home=str(tmp_path_factory.mktemp("big")),
        register_numbers_table=False)))
    fe.start()
    fe.do_query("CREATE TABLE big (host STRING, ts TIMESTAMP TIME INDEX, "
                "usage DOUBLE, PRIMARY KEY(host))")
    rng = np.random.default_rng(37)
    table = fe.catalog.table("greptime", "public", "big")
    k = np.arange(BIG_TICKS)
    for h in range(BIG_HOSTS):
        table.insert({"host": [f"b{h:03d}"] * BIG_TICKS,
                      "ts": (T0 + k * TICK_MS).tolist(),
                      "usage": rng.random(BIG_TICKS).tolist()})
    yield fe, table
    fe.shutdown()


def test_writes_of_any_size_after_the_warm_statements_compile_nothing(big):
    fe, table = big
    assert BIG_HOSTS * BIG_TICKS >= tpu_exec.TPU_DISPATCH_MIN_ROWS
    lastpoint = "SELECT host, last(usage) AS l FROM big GROUP BY host"
    a_few = ("SELECT host, max(usage) AS m FROM big WHERE host IN "
             "('b003', 'b077', 'b200') GROUP BY host")

    def answer(sql):
        # the static floor as it is, and the adaptive one forgotten
        fe.do_query("SET tpu_dispatch_min_rows = 131072")
        out = fe.do_query(sql)[-1]
        return pd.concat([pd.DataFrame(b.to_pydict()) for b in out.batches])

    # the warm statements, before any write: each compiles its programs
    # over the base and, there, over a stand-in for the base's tail
    for sql in (lastpoint, a_few, lastpoint, a_few):
        answer(sql)
    compiled = (_sorted_grouped_aggregate_pre._cache_size(),
                scan_narrow._narrow_reduce._cache_size())
    region = next(iter(table.regions.values()))
    base = scan_cache.SCAN_CACHE.get_parts(region)[0]
    # one executable a statement shape, compiled and not run: a table
    # nobody writes holds nothing of a tail's size on the device
    import jax
    capacity = scan_cache.tail_capacity(base.num_rows)
    assert len(base.tail_programs) == 2
    assert not [a for a in jax.live_arrays() if a.shape[:1] == (capacity,)]
    tick, written = BIG_TICKS, {}
    # k writes of k sizes: whole ticks, several ticks, some of the hosts
    for ticks, hosts in ((1, BIG_HOSTS), (3, BIG_HOSTS), (1, 7), (7, 150),
                         (2, BIG_HOSTS)):
        rng = np.random.default_rng(tick)
        for k in range(tick, tick + ticks):
            values = rng.random(hosts)
            table.insert({"host": [f"b{h:03d}" for h in range(hosts)],
                          "ts": [T0 + k * TICK_MS] * hosts,
                          "usage": values.tolist()})
            written.update({f"b{h:03d}": v for h, v in enumerate(values)})
        tick += ticks
        got = answer(lastpoint).set_index("host").l
        np.testing.assert_allclose(
            got[sorted(written)].to_numpy(),
            [written[h] for h in sorted(written)], rtol=RTOL)
        assert len(answer(a_few)) == 3
        assert (_sorted_grouped_aggregate_pre._cache_size(),
                scan_narrow._narrow_reduce._cache_size()) == compiled, \
            f"a write of {ticks} ticks x {hosts} hosts met a new program"
    now, tail = scan_cache.SCAN_CACHE.get_parts(region)
    assert now is base and tail.valid_rows == 400 * 6 + 7 + 7 * 150


def test_a_tail_that_meets_a_null_answers_and_takes_one_program_more(big):
    """ISSUE 41: which columns hold a NULL is part of a program's
    signature. The stand-in warms the pattern of a tail without one; the
    first NULL written brings a count of that column's own, one compile,
    and the right answer."""
    fe, table = big
    count = ("SELECT host, count(usage) AS c, last(usage) AS l FROM big "
             "WHERE host IN ('b010', 'b011') GROUP BY host")
    whole = "SELECT host, count(usage) AS c, last(usage) AS l FROM big " \
        "GROUP BY host"

    def answer(sql):
        fe.do_query("SET tpu_dispatch_min_rows = 131072")
        out = fe.do_query(sql)[-1]
        return pd.concat([pd.DataFrame(b.to_pydict())
                          for b in out.batches]).set_index("host")

    before = answer(whole)
    answer(whole)
    t = T0 + (BIG_TICKS + 1000) * TICK_MS      # after every other write
    table.insert({"host": ["b010", "b011"], "ts": [t, t],
                  "usage": [0.25, 0.5]})
    compiled = _sorted_grouped_aggregate_pre._cache_size()
    got = answer(whole)        # a tail without a NULL: the warmed program
    assert _sorted_grouped_aggregate_pre._cache_size() == compiled
    assert got.c["b010"] == before.c["b010"] + 1 and got.l["b011"] == 0.5
    table.insert({"host": ["b010", "b011"], "ts": [t + TICK_MS] * 2,
                  "usage": [None, 0.75]})
    got = answer(whole)
    assert _sorted_grouped_aggregate_pre._cache_size() == compiled + 1
    # the NULL is no value: b010 keeps its count and its last
    assert got.c["b010"] == before.c["b010"] + 1 and got.l["b010"] == 0.25
    assert got.c["b011"] == before.c["b011"] + 2 and got.l["b011"] == 0.75
    assert got.c.drop(["b010", "b011"]).equals(
        before.c.drop(["b010", "b011"]))
    narrow = answer(count)
    assert list(narrow.c) == [got.c["b010"], got.c["b011"]]
    assert list(narrow.l) == [0.25, 0.75]


# ---------------------------------------------------------------------------
# Memtable.snapshot under the lock; an empty table, then a bulk load
# ---------------------------------------------------------------------------

def test_snapshot_waits_for_a_write_between_two_column_appends(tmp_path):
    """A writer is held between the append of the timestamps and the
    append of the first field (a hook on that field's buffer); a reader's
    snapshot then has to wait for the memtable's lock, and what it gets
    holds every column at the length it reports."""
    from greptimedb_tpu.storage.engine import EngineConfig, StorageEngine
    from greptimedb_tpu.storage.write_batch import WriteBatch
    from greptimedb_tpu.datatypes import ColumnSchema, Schema, SemanticType
    from greptimedb_tpu.datatypes import data_type as dt
    schema = Schema([
        ColumnSchema("host", dt.STRING, nullable=False,
                     semantic_type=SemanticType.TAG),
        ColumnSchema("ts", dt.TIMESTAMP_MILLISECOND, nullable=False,
                     semantic_type=SemanticType.TIMESTAMP),
        ColumnSchema("cpu", dt.FLOAT64),
    ])
    storage = StorageEngine(EngineConfig(data_home=str(tmp_path)))
    region = storage.create_region("snap", schema)

    def batch(n, at):
        wb = WriteBatch(schema)
        wb.put({"host": [f"h{i}" for i in range(n)],
                "ts": [at + i for i in range(n)], "cpu": [1.0] * n})
        return wb

    region.write(batch(10, 0))
    mt = region.version_control.current.memtables.mutable
    held, release, waiting = (threading.Event() for _ in range(3))
    lock = mt._lock

    class Watched:
        """The memtable's lock, saying when someone has to wait."""

        def __enter__(self):
            if not lock.acquire(blocking=False):
                waiting.set()
                lock.acquire()
            return self

        def __exit__(self, *exc):
            lock.release()

    mt._lock = Watched()
    from greptimedb_tpu.storage.memtable import _GrowBuf

    class HeldBuf(_GrowBuf):
        """The `cpu` buffer, whose append waits to be released."""

        def append(self, values):
            held.set()
            release.wait()
            super().append(values)

    plain, validity = mt._fields["cpu"]
    cpu = HeldBuf(plain.arr.dtype)
    cpu.arr, cpu.len = plain.arr, plain.len
    mt._fields["cpu"] = (cpu, validity)
    result = {}
    writer = threading.Thread(
        target=lambda: region.write(batch(4000, 100)))   # grows the buffers
    reader = threading.Thread(
        target=lambda: (result.update(snap=mt.snapshot()), waiting.set()))
    writer.start()
    held.wait()
    assert mt._ts.len == 4010 and cpu.len == 10     # between two appends
    reader.start()
    waiting.wait()              # the reader blocked, or (unlocked) returned
    assert "snap" not in result, "snapshot did not wait for the write"
    release.set()
    writer.join()
    reader.join()
    snap = result["snap"]
    assert snap.num_rows == 4010
    assert len(snap.ts) == len(snap.series_ids) == len(snap.seq) == 4010
    assert all(len(d) == len(v) == 4010 for d, v in snap.fields.values())
    storage.close()


def test_a_statement_on_an_empty_table_then_a_bulk_load(tmp_path):
    """PERF.md section 7 3(a): the entry a statement leaves over an empty
    table is not a base to refresh row by row; the load arrives as one
    build and is counted once."""
    fe = FrontendInstance(DatanodeInstance(DatanodeOptions(
        data_home=str(tmp_path), register_numbers_table=False)))
    fe.start()
    try:
        fe.do_query("CREATE TABLE m (host STRING, ts TIMESTAMP TIME INDEX, "
                    "greptime_value DOUBLE, PRIMARY KEY(host))")
        fe.do_query("SET tpu_dispatch_min_rows = 1")
        count = "SELECT host, count(greptime_value) AS n FROM m GROUP BY host"
        assert sum(len(b.to_pydict()["host"])
                   for b in fe.do_query(count)[-1].batches) == 0
        table = fe.catalog.table("greptime", "public", "m")
        region = next(iter(table.regions.values()))
        assert scan_cache.SCAN_CACHE.cached(region)
        hosts, ticks = 300, 40
        loaded = fe.handle_bulk_load("m", {
            "host": np.repeat([f"h{h:03d}" for h in range(hosts)], ticks),
            "ts": np.tile(T0 + np.arange(ticks) * TICK_MS, hosts),
            "greptime_value": np.arange(hosts * ticks, dtype=np.float64)},
            tag_columns=["host"], timestamp_column="ts")
        assert loaded == hosts * ticks
        before = {n: metric(n) for n in (
            "scan_cache_miss", "scan_cache_incremental",
            "scan_cache_merges", "scan_cache_delta_rows")}
        fe.do_query("SET tpu_dispatch_min_rows = 1")
        out = fe.do_query(count)[-1]
        got = pd.concat([pd.DataFrame(b.to_pydict()) for b in out.batches])
        assert len(got) == hosts and set(got.n) == {ticks}
        assert metric("scan_cache_miss") == before["scan_cache_miss"] + 1
        assert {n: metric(n) for n in before if n != "scan_cache_miss"} == \
            {n: v for n, v in before.items() if n != "scan_cache_miss"}
        base, tail = scan_cache.SCAN_CACHE.get_parts(region)
        assert (base.num_rows, tail) == (hosts * ticks, None)
    finally:
        fe.do_query("SET tpu_dispatch_min_rows = 131072")
        fe.shutdown()


# ---------------------------------------------------------------------------
# `_merge_rows` by hand
# ---------------------------------------------------------------------------

def _rows(keys, seq, values, deleted=None, block=True):
    sids = np.array([k[0] for k in keys], dtype=np.int32)
    stamps = np.array([k[1] for k in keys], dtype=np.int64)
    vals = np.array(values, dtype=np.float64)
    blk = vals[:, None].copy() if block else None
    fields = scan_cache._block_fields(["v"], blk) if block \
        else {"v": (vals, None)}
    return scan_cache._Rows(
        sids, stamps, np.full(len(keys), seq, np.int64), fields,
        None if deleted is None else np.array(deleted, dtype=bool), blk)


@pytest.mark.parametrize("block", [True, False], ids=["block", "columns"])
def test_merge_rows_places_replaces_and_removes(block):
    old = _rows([(0, 10), (0, 20), (2, 5), (2, 15)], 1, [1, 2, 3, 4],
                block=block)
    new = _rows([(0, 15), (0, 20), (1, 7), (2, 1), (2, 15), (3, 0)], 2,
                [10, 20, 30, 40, 50, 60],
                deleted=[False, False, False, False, True, False],
                block=block)
    out = scan_cache._merge_rows(old, new)
    assert list(zip(out.sids.tolist(), out.ts.tolist())) == \
        [(0, 10), (0, 15), (0, 20), (1, 7), (2, 1), (2, 5), (3, 0)]
    assert out.fields["v"][0].tolist() == [1, 10, 20, 30, 40, 3, 60]
    assert out.seq.tolist() == [1, 2, 2, 2, 2, 1, 2]
    assert out.deleted is None and (out.block is not None) == block
    kept = scan_cache._merge_rows(old, new, drop_deleted=False)
    assert len(kept) == 8 and kept.deleted.tolist() == \
        [False] * 6 + [True, False]


def test_merge_rows_appends_without_a_search_over_times(monkeypatch):
    """What ticks give: every new row comes after its series' last."""
    old = _rows([(0, 10), (0, 20), (1, 10), (1, 20)], 1, [1, 2, 3, 4])
    new = _rows([(0, 30), (0, 40), (1, 30), (2, 30)], 2, [5, 6, 7, 8])
    monkeypatch.setattr(scan_cache, "_lower_bound", None)  # not reached
    out = scan_cache._merge_rows(old, new)
    assert out.fields["v"][0].tolist() == [1, 2, 5, 6, 3, 4, 7, 8]
    assert out.block.shape == (8, 1)


# ---------------------------------------------------------------------------
# `standalone start --wal-sync-on-write`
# ---------------------------------------------------------------------------

def _timer_count(name: str) -> float:
    return sum(value for metric_name, _, value, _ in
               telemetry.registry_snapshot()
               if metric_name == f"greptime_{name}_seconds_count")


def _standalone(tmp_path, *flags):
    from greptimedb_tpu.cmd.main import (build_parser, build_servers,
                                         load_options)
    args = build_parser().parse_args(
        ["standalone", "start", "--data-home", str(tmp_path),
         "--http-addr", "127.0.0.1:0", *flags])
    opts = load_options(args)
    opts.enable_mysql = opts.enable_postgres = opts.enable_grpc = False
    return opts, build_servers(opts)


@pytest.mark.parametrize("group_commit", [1, 0], ids=["group", "per-append"])
def test_an_acknowledged_write_has_passed_the_fsync(tmp_path, group_commit):
    import urllib.request
    opts, (fe, servers) = _standalone(tmp_path, "--wal-sync-on-write")
    assert opts.wal_sync_on_write
    http = servers[0]
    http.start()
    try:
        fe.do_query(f"SET wal_group_commit = {group_commit}")
        for i in range(3):
            before = _timer_count("wal_fsync")
            body = "\n".join(
                f"cpu,hostname=h{h} usage_user={i + h}.5 {1000 + i}"
                for h in range(5)).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{http.port}/v1/influxdb/write"
                "?precision=ms", data=body, method="POST")
            with urllib.request.urlopen(req, timeout=30) as resp:
                assert resp.status == 204
            # the acknowledgement came after (at least) one fsync wait
            assert _timer_count("wal_fsync") >= before + 1
    finally:
        fe.do_query("SET wal_group_commit = 1")
        http.shutdown()
        fe.shutdown()


def test_without_the_flag_an_acknowledgement_waits_for_no_fsync(tmp_path):
    import urllib.request
    opts, (fe, servers) = _standalone(tmp_path)
    assert not opts.wal_sync_on_write
    http = servers[0]
    http.start()
    try:
        before = _timer_count("wal_fsync")
        req = urllib.request.Request(
            f"http://127.0.0.1:{http.port}/v1/influxdb/write?precision=ms",
            data=b"cpu,hostname=h1 usage_user=1.5 1000", method="POST")
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert resp.status == 204
        assert _timer_count("wal_fsync") == before
    finally:
        http.shutdown()
        fe.shutdown()


def test_datanode_start_keeps_its_flag():
    from greptimedb_tpu.cmd.main import build_parser
    args = build_parser().parse_args(
        ["datanode", "start", "--node-id", "1", "--wal-sync-on-write"])
    assert args.wal_sync_on_write is True
    assert isinstance(args, argparse.Namespace)


# ---- the interpreter lock, shared between statements and the parser ------

def test_the_parser_gives_way_only_while_a_statement_runs(monkeypatch):
    """The gate's `give_way`, which the parser calls after every line of
    a body it was handed through `parse_turn`, offers the interpreter
    lock while a statement is registered and never otherwise (an
    ingest-only server pays nothing for it); a parser that was given no
    such function calls nothing."""
    from greptimedb_tpu.common import admission, process_list
    from greptimedb_tpu.servers import influxdb
    offered = []
    monkeypatch.setattr(admission.time, "sleep", offered.append)
    lines = 25
    body = "\n".join(f"cpu,hostname=h{i} usage_user={i}.5 {1000 + i}"
                     for i in range(lines))
    plain = influxdb.parse_lines(body, "ms")
    with admission.GATE.parse_turn() as give_way:
        alone = influxdb.parse_lines(body, "ms", give_way)
    assert offered == [] and not process_list.REGISTRY.busy()
    with process_list.track("SELECT 1"):
        assert process_list.REGISTRY.busy()
        plain_beside = influxdb.parse_lines(body, "ms")
        assert offered == []
        with admission.GATE.parse_turn() as give_way:
            beside = influxdb.parse_lines(body, "ms", give_way)
    assert offered == [0] * lines
    assert beside == alone == plain == plain_beside and len(alone) == lines
    assert not process_list.REGISTRY.busy()


def test_bodies_are_parsed_one_at_a_time(tmp_path, monkeypatch):
    """Two `/v1/influxdb/write` requests in flight: the second body's
    parse starts after the first's has ended (`GATE.parse_turn`), made
    deterministic with a hook inside the parser, both are acknowledged
    with their rows, and each wait for the turn is one observation of
    the `ingest_parse_wait` timer, outside `ingest_parse`."""
    import urllib.request
    from greptimedb_tpu.common import admission
    from greptimedb_tpu.servers import influxdb
    _, (fe, servers) = _standalone(tmp_path)
    http = servers[0]
    http.start()
    inside, release, events = threading.Event(), threading.Event(), []
    parse = influxdb.body_to_inserts

    def hooked(body, precision="ns", between_lines=None):
        events.append(("in", body[:12]))
        if len(events) == 1:            # the first parser waits inside
            inside.set()
            assert release.wait(30)
        assert between_lines == admission.GATE.give_way
        out = parse(body, precision, between_lines)
        events.append(("out", body[:12]))
        return out

    monkeypatch.setattr(influxdb, "body_to_inserts", hooked)
    url = f"http://127.0.0.1:{http.port}/v1/influxdb/write?precision=ms"
    status = {}
    waits = _timer_count("ingest_parse_wait")

    def post(name):
        req = urllib.request.Request(
            url, data=f"cpu,hostname={name} usage_user=1.5 1000".encode(),
            method="POST")
        with urllib.request.urlopen(req, timeout=60) as resp:
            status[name] = resp.status

    first = threading.Thread(target=post, args=("a",))
    second = threading.Thread(target=post, args=("b",))
    try:
        first.start()
        assert inside.wait(30)
        second.start()
        # the second request has reached the slot when the gate has
        # admitted it: no clock is asked, the first parser is still in
        second.join(0.5)
        assert events == [("in", "cpu,hostname")] and second.is_alive()
        release.set()
        first.join(60)
        second.join(60)
        assert status == {"a": 204, "b": 204}
        assert [e for e, _ in events] == ["in", "out", "in", "out"]
        assert _timer_count("ingest_parse_wait") == waits + 2
        out = fe.do_query("SELECT count(*) FROM cpu")[0]
        assert out.batches[0].to_pydict()["count(*)"] == [2]
    finally:
        release.set()
        http.shutdown()
        fe.shutdown()


def _ordered_by_pandas(frame, keys, ascs, nulls_first):
    cols, asc = [], []
    work = frame.copy()
    for i, (k, a, nf) in enumerate(zip(keys, ascs, nulls_first)):
        work[f"__n{i}"] = work[k].isna()
        cols += [f"__n{i}", k]
        asc += [not nf, a]
    return work.sort_values(cols, ascending=asc, kind="stable").index


@pytest.mark.parametrize("ascs,nulls_first", [
    ((True, True), (False, False)), ((False, True), (True, False)),
    ((True, False), (True, True)), ((False, False), (False, True)),
], ids=["asc-asc", "desc-asc", "asc-desc-nulls-first", "desc-desc"])
@pytest.mark.parametrize("kinds", [
    ("str", "float"), ("float", "int"), ("time", "str"), ("int", "float"),
], ids=lambda k: "-".join(k))
def test_order_by_positions_are_pandas_sort_values(kinds, ascs, nulls_first):
    """`engine._sort_positions` (one lexsort, no frame copy) orders as
    the frame's `sort_values` over NULL-flag and value columns did:
    ties keep their order, NULLs go where asked, DESC negates."""
    from greptimedb_tpu.query.engine import _sort_positions
    rng = np.random.default_rng(7)
    n = 400

    def column(kind):
        if kind == "str":
            v = np.array([f"h{j:02d}" for j in rng.integers(0, 9, n)],
                         dtype=object)
            v[rng.integers(0, n, 25)] = None
            return pd.Series(v)
        if kind == "float":
            v = rng.integers(0, 12, n).astype(np.float64) / 4
            v[rng.integers(0, n, 25)] = np.nan
            return pd.Series(v)
        if kind == "time":
            v = pd.Series(pd.to_datetime(
                rng.integers(0, 6, n) * 3_600_000, unit="ms"))
            v[rng.integers(0, n, 25)] = pd.NaT
            return v
        return pd.Series(rng.integers(-5, 5, n))

    frame = pd.DataFrame({"a": column(kinds[0]), "b": column(kinds[1])})
    order = _sort_positions([frame["a"], frame["b"]], list(ascs),
                            list(nulls_first))
    want = _ordered_by_pandas(frame, ["a", "b"], ascs, nulls_first)
    assert frame.index[order].tolist() == want.tolist()


@pytest.mark.parametrize("values,dtype", [
    ([np.iinfo(np.int64).min, 3, -2, 3], np.int64),
    ([0, 2**64 - 1, 7, 7], np.uint64), ([200, 0, 255, 9], np.uint8),
], ids=["int64-min", "uint64", "uint8"])
@pytest.mark.parametrize("asc", [True, False], ids=["asc", "desc"])
def test_order_by_positions_rank_what_negation_would_wrap(values, dtype, asc):
    from greptimedb_tpu.query.engine import _sort_positions
    col = pd.Series(np.array(values, dtype=dtype))
    want = col.sort_values(ascending=asc, kind="stable").index.tolist()
    assert _sort_positions([col], [asc], [not asc]).tolist() == want


def test_order_by_positions_raise_on_values_that_do_not_compare():
    from greptimedb_tpu.query.engine import _sort_positions
    col = pd.Series(np.array([{"a": 1}, {"b": 2}], dtype=object))
    with pytest.raises(TypeError):
        _sort_positions([col], [True], [False])


def test_a_full_collection_is_timed_without_a_lock():
    """`install_gc_timer`: a generation-2 collection moves the count and
    the seconds on /metrics, younger ones move nothing, and installing
    twice keeps one callback."""
    import gc
    telemetry.install_gc_timer()
    telemetry.install_gc_timer()
    assert sum(getattr(cb, "greptime_gc_timer", False)
               for cb in gc.callbacks) == 1

    def sample(suffix):
        return sum(v for name, _, v, _ in telemetry.registry_snapshot()
                   if name == "greptime_gc_full_collection_" + suffix)

    count, seconds = sample("seconds_count"), sample("seconds_sum")
    gc.collect(0)
    gc.collect(1)
    assert sample("seconds_count") == count
    gc.collect()
    assert sample("seconds_count") == count + 1
    assert sample("seconds_sum") > seconds
    assert sample("max_seconds") > 0
