"""The resident launch narrowed to the selected series' row ranges
(ISSUE 31, `query/scan_narrow.py`): a statement whose tag predicates keep
a few series answers from slices of the device mirrors, with the answers
of the full launch.

The table: 24 hosts x 800 ticks (19,200 rows) in three regions, NULLs in
`idle`, written out of order across two flushes and a memtable, with a
few rows overwritten. `min`, `max`, `count`, `first`, `last` must equal
the full launch bit for bit; `sum` and `avg` add fewer f32 values and
are held to a float64 pandas reference at 1e-5 relative (PERF.md
section 6).
"""

import contextlib

import numpy as np
import pandas as pd
import pytest

from greptimedb_tpu.common.telemetry import registry_snapshot
from greptimedb_tpu.datanode.instance import DatanodeInstance, DatanodeOptions
from greptimedb_tpu.frontend.instance import FrontendInstance
from greptimedb_tpu.query import (agg_plan, moment_fold, scan_full,
                                  scan_launch, scan_narrow, tpu_exec)
from greptimedb_tpu.storage import scan_cache

HOSTS, TICKS, TICK_MS = 24, 800, 10_000
T0 = 1_700_000_040_000                      # a whole minute
AVG_RTOL = 1e-5


def _value(h, t, gen):
    return float((h * 37 + t * 11 + gen * 5) % 1009) / 7.0


def _rows():
    """(host, region, ts, usage, idle, generation): every (host, tick)
    once, the ticks divisible by 50 a second time with another value."""
    rows = []
    for h in range(HOSTS):
        for t in range(TICKS):
            idle = None if (h + t) % 7 == 0 else float(h + t / 10)
            rows.append((f"h{h:02d}", f"r{h % 3}", T0 + t * TICK_MS,
                         _value(h, t, 0), idle, 0))
            if t % 50 == 0:
                rows.append((f"h{h:02d}", f"r{h % 3}", T0 + t * TICK_MS,
                             _value(h, t, 1), idle, 1))
    return rows


def _lit(v):
    return "NULL" if v is None else repr(v)


class Db:
    def __init__(self, data_home):
        self.dn = DatanodeInstance(DatanodeOptions(
            data_home=data_home, register_numbers_table=False))
        self.dn.start()
        self.fe = FrontendInstance(self.dn)
        self.fe.start()
        self.fe.do_query(
            "CREATE TABLE cpu (host STRING, region STRING, "
            "ts TIMESTAMP TIME INDEX, usage DOUBLE, idle DOUBLE, "
            "PRIMARY KEY(host, region))")
        rows = _rows()
        first = [r for r in rows if r[5] == 0]
        # three writes, none in key order: odd ticks, flush; even ticks
        # newest first, flush; the overwrites stay in the memtable
        for batch, flush in (
                ([r for r in first if (r[2] // TICK_MS) % 2], True),
                ([r for r in first if not (r[2] // TICK_MS) % 2][::-1], True),
                ([r for r in rows if r[5] == 1], False)):
            values = ", ".join(
                f"('{h}', '{g}', {ts}, {_lit(u)}, {_lit(i)})"
                for h, g, ts, u, i, _ in batch)
            self.fe.do_query(f"INSERT INTO cpu VALUES {values}")
            if flush:
                self.fe.do_query("ADMIN FLUSH TABLE cpu")
        latest = {}
        for h, g, ts, u, i, _ in rows:
            latest[(h, ts)] = (h, g, ts, u, i)
        self.ref = pd.DataFrame(sorted(latest.values()),
                                columns=["host", "region", "ts", "usage",
                                         "idle"])
        # a statement over every series builds the scan cache: without it
        # a point statement answers through the SST index, off the device
        self.sql("SELECT host, max(usage) FROM cpu GROUP BY host")

    def close(self):
        self.fe.shutdown()

    def sql(self, sql: str) -> pd.DataFrame:
        # the dispatch floor is process-global and latency-adaptive
        self.fe.do_query("SET tpu_dispatch_min_rows = 1")
        out = self.fe.do_query(sql)
        out = out[-1] if isinstance(out, list) else out
        frames = [pd.DataFrame(b.to_pydict()) for b in out.batches]
        return pd.concat(frames, ignore_index=True) if frames else \
            pd.DataFrame()

    def stages(self, sql: str) -> dict:
        rows = self.sql("EXPLAIN ANALYZE " + sql)
        return {r.stage: r.detail or "" for r in rows.itertuples()}


@pytest.fixture(scope="module")
def db(tmp_path_factory):
    d = Db(str(tmp_path_factory.mktemp("narrow")))
    yield d
    d.fe.do_query("SET tpu_dispatch_min_rows = 131072")
    d.close()


@pytest.fixture
def full(monkeypatch):
    """-> a context in which every resident launch is the full one."""
    @contextlib.contextmanager
    def forced():
        with monkeypatch.context() as m:
            m.setattr(scan_narrow, "_NARROW_MAX_SHARE", 10**12)
            yield
    return forced


def total(metric: str, label: str) -> float:
    return sum(value for name, labels, value, _ in registry_snapshot()
               if name == metric and label in labels)


def counter(path: str) -> float:
    return total("greptime_scan_reads_total", f'path="{path}"')


def both(db, full, sql):
    """-> (narrow answer, full answer), each by the path it names."""
    before = counter("narrow"), counter("full")
    narrow = db.sql(sql)
    assert (counter("narrow"), counter("full")) == \
        (before[0] + 1, before[1]), "the statement did not run narrow"
    with full():
        whole = db.sql(sql)
    assert counter("full") == before[1] + 1
    return narrow, whole


def in_list(hosts):
    return ", ".join(f"'h{h:02d}'" for h in hosts)


LO, HI = T0 + 30 * TICK_MS, T0 + 330 * TICK_MS
WINDOW = f"ts >= {LO} AND ts < {HI}"

#: (id, WHERE, pandas filter) — every selection lies on the narrow side
PREDICATES = [
    ("eq", "host = 'h05'", lambda r: r.host == "h05"),
    ("in", f"host IN ({in_list([3, 11, 20])})",
     lambda r: r.host.isin(["h03", "h11", "h20"])),
    ("in-and-ne", f"host IN ({in_list([3, 11, 20, 21])}) AND region != 'r2'",
     lambda r: r.host.isin(["h03", "h11", "h20", "h21"])
     & (r.region != "r2")),
    ("first-and-last-series", f"host IN ({in_list([0, HOSTS - 1])})",
     lambda r: r.host.isin(["h00", f"h{HOSTS - 1:02d}"])),
]

#: (id, SELECT list, GROUP BY, reference keys)
GROUPINGS = [
    ("plain", "", "", []),
    ("by-host", "host, ", "host", ["host"]),
    ("by-minute", "date_bin(INTERVAL '1 minute', ts) AS minute, ",
     "minute", ["minute"]),
    ("by-host-and-minute",
     "host, date_bin(INTERVAL '1 minute', ts) AS minute, ",
     "host, minute", ["host", "minute"]),
]

EXACT = ("max(usage), min(usage), count(usage), count(idle), max(idle), "
         "first(usage), last(idle)")


def reference(ref: pd.DataFrame, keep, keys, windowed=True) -> pd.DataFrame:
    r = ref[keep(ref)]
    if windowed:
        r = r[(r.ts >= LO) & (r.ts < HI)]
    r = r.assign(minute=r.ts // 60_000 * 60_000).sort_values(["host", "ts"])
    aggs = dict(
        mx=("usage", "max"), mn=("usage", "min"), n=("usage", "count"),
        ni=("idle", "count"), mxi=("idle", "max"),
        sm=("usage", "sum"), av=("usage", "mean"), avi=("idle", "mean"))
    if keys:
        return r.groupby(keys).agg(**aggs).reset_index()
    return pd.DataFrame({k: [getattr(r[c], f)()] for k, (c, f)
                         in aggs.items()})


@pytest.mark.parametrize("group", GROUPINGS, ids=[g[0] for g in GROUPINGS])
@pytest.mark.parametrize("pred", PREDICATES, ids=[p[0] for p in PREDICATES])
def test_exact_aggregates_equal_the_full_launch(db, full, pred, group):
    _, where, keep = pred
    _, select, group_by, keys = group
    sql = f"SELECT {select}{EXACT} FROM cpu WHERE {where} AND {WINDOW}"
    if group_by:
        sql += f" GROUP BY {group_by} ORDER BY {group_by}"
    narrow, whole = both(db, full, sql)
    assert len(narrow) > 0
    pd.testing.assert_frame_equal(narrow, whole, check_exact=True)
    want = reference(db.ref, keep, keys)
    assert len(narrow) == len(want)
    # f32 mirrors: the extremes are the f32 roundings of the reference's
    assert np.array_equal(narrow["max(usage)"].to_numpy(),
                          want.mx.to_numpy().astype(np.float32))
    assert np.array_equal(narrow["count(usage)"].to_numpy(), want.n)
    assert np.array_equal(narrow["count(idle)"].to_numpy(), want.ni)


@pytest.mark.parametrize("group", GROUPINGS, ids=[g[0] for g in GROUPINGS])
@pytest.mark.parametrize("pred", PREDICATES, ids=[p[0] for p in PREDICATES])
def test_sum_and_avg_within_tolerance_of_float64(db, full, pred, group):
    _, where, keep = pred
    _, select, group_by, keys = group
    sql = (f"SELECT {select}sum(usage), avg(usage), avg(idle) FROM cpu "
           f"WHERE {where} AND {WINDOW}")
    if group_by:
        sql += f" GROUP BY {group_by} ORDER BY {group_by}"
    narrow, whole = both(db, full, sql)
    want = reference(db.ref, keep, keys)
    for got in (narrow, whole):
        np.testing.assert_allclose(got["sum(usage)"], want.sm, rtol=AVG_RTOL)
        np.testing.assert_allclose(got["avg(usage)"], want.av, rtol=AVG_RTOL)
        np.testing.assert_allclose(got["avg(idle)"], want.avi, rtol=AVG_RTOL)


def test_no_time_window_and_the_clamped_slice(db, full):
    """The last series' whole range ends at the table's last row: its
    slice of 1,024 starts before the range (clamped), the first series'
    at row 0."""
    sql = (f"SELECT host, {EXACT}, avg(usage) FROM cpu WHERE host IN "
           f"({in_list([0, HOSTS - 1])}) GROUP BY host ORDER BY host")
    narrow, whole = both(db, full, sql)
    pd.testing.assert_frame_equal(narrow.drop(columns="avg(usage)"),
                                  whole.drop(columns="avg(usage)"),
                                  check_exact=True)
    want = reference(db.ref, lambda r: r.host.isin(["h00", "h23"]),
                     ["host"], windowed=False)
    assert list(narrow["count(usage)"]) == [TICKS, TICKS] == list(want.n)
    np.testing.assert_allclose(narrow["avg(usage)"], want.av, rtol=AVG_RTOL)
    # a window that keeps only the table's last rows
    tail = (f"SELECT max(usage), count(usage) FROM cpu WHERE host = 'h23' "
            f"AND ts >= {T0 + (TICKS - 3) * TICK_MS}")
    narrow, whole = both(db, full, tail)
    pd.testing.assert_frame_equal(narrow, whole, check_exact=True)
    assert list(narrow["count(usage)"]) == [3]


def test_field_filter_rides_along(db, full):
    sql = (f"SELECT host, count(usage), max(usage), min(idle) FROM cpu "
           f"WHERE host IN ({in_list([4, 9])}) AND usage > 70 AND {WINDOW} "
           "GROUP BY host ORDER BY host")
    narrow, whole = both(db, full, sql)
    pd.testing.assert_frame_equal(narrow, whole, check_exact=True)
    r = db.ref
    r = r[r.host.isin(["h04", "h09"]) & (r.usage > 70) & (r.ts >= LO)
          & (r.ts < HI)]
    assert list(narrow["count(usage)"]) == list(r.groupby("host").usage.count())


@pytest.mark.parametrize("where", [
    "host = 'never-seen'",
    f"host IN ('h02') AND region = 'r0'",       # h02 lives in r2
    f"host = 'h07' AND ts >= {T0 + TICKS * TICK_MS}",
    f"host IN ('h07', 'h08') AND ts < {T0}",
], ids=["unknown-value", "predicates-disagree", "window-after", "window-before"])
def test_empty_selection_answers_as_no_rows(db, full, where):
    sql = (f"SELECT host, max(usage) FROM cpu WHERE {where} GROUP BY host")
    narrow, whole = both(db, full, sql)
    assert len(narrow) == 0 and len(whole) == 0
    one, whole = both(db, full,
                      f"SELECT count(usage), max(usage) FROM cpu WHERE {where}")
    pd.testing.assert_frame_equal(one, whole, check_exact=True)


def test_a_window_that_misses_one_host(db, full):
    """h06 has rows in the window, h30 does not exist, and a host whose
    rows all lie outside it drops out of the ranges."""
    sql = (f"SELECT host, count(usage) FROM cpu WHERE host IN ('h06', 'h30') "
           f"AND {WINDOW} GROUP BY host ORDER BY host")
    narrow, whole = both(db, full, sql)
    pd.testing.assert_frame_equal(narrow, whole, check_exact=True)
    assert list(narrow.host) == ["h06"] and list(narrow.iloc[:, 1]) == [300]
    assert "ranges=1" in db.stages(sql)["reduce"]


@pytest.mark.parametrize("hosts, path", [(1, "narrow"), (4, "narrow"),
                                         (5, "full"), (12, "full")])
def test_each_side_of_the_crossover(db, full, hosts, path):
    """19,200 rows: 4 ranges pad to 4 x 512, an eighth of 16,384, and run
    narrow; 5 pad to 8 x 512 and run full; the answers do not care."""
    picked = list(range(2, 2 + hosts))
    sql = (f"SELECT host, {EXACT} FROM cpu WHERE host IN ({in_list(picked)}) "
           f"AND {WINDOW} GROUP BY host ORDER BY host")
    n = db.ref.shape[0]
    sel_rows = 512 * (1 << (hosts - 1).bit_length())
    assert scan_narrow.scan_read_path(n, hosts, sel_rows) == path
    before = counter(path)
    got = db.sql(sql)
    assert counter(path) == before + 1
    detail = db.stages(sql)["reduce"]
    assert f"path={path}" in detail
    if path == "narrow":
        assert f"narrow_rows={hosts * 300}" in detail
        assert f"ranges={hosts}" in detail
    with full():
        whole = db.sql(sql)
    pd.testing.assert_frame_equal(got, whole, check_exact=True)
    assert list(got["count(usage)"]) == [300] * hosts


def test_scan_read_path_reads_only_counts():
    assert scan_narrow.scan_read_path(17_280_000, None, 0) == "full"
    assert scan_narrow.scan_read_path(17_280_000, 8, 8 * 4096) == "narrow"
    assert scan_narrow.scan_read_path(17_280_000, 0, 512) == "narrow"
    most = 17_280_000 // scan_narrow._NARROW_MAX_SHARE
    assert scan_narrow.scan_read_path(17_280_000, 512, most) == "narrow"
    assert scan_narrow.scan_read_path(17_280_000, 512, most + 1) == "full"


def test_no_tag_predicate_is_the_full_launch(db):
    before = counter("narrow"), counter("full")
    db.sql(f"SELECT host, max(usage) FROM cpu WHERE {WINDOW} GROUP BY host")
    db.sql("SELECT region, max(usage) FROM cpu WHERE region != 'r1' "
           "GROUP BY region")       # no point / IN conjunct
    assert (counter("narrow"), counter("full")) == (before[0], before[1] + 2)
    sql = "SELECT max(usage) FROM cpu"
    assert "path=full" in db.stages(sql)["reduce"]
    assert db.stages(sql)["dispatch"] == "device-resident (scan cache)"


def test_other_hosts_of_the_same_shape_compile_nothing(db):
    def sql(hosts, lo):
        return (f"SELECT date_bin(INTERVAL '1 minute', ts) AS minute, "
                f"max(usage), max(idle) FROM cpu WHERE host IN "
                f"({in_list(hosts)}) AND ts >= {T0 + lo * TICK_MS} AND "
                f"ts < {T0 + (lo + 120) * TICK_MS} GROUP BY minute")
    db.sql(sql([1, 2, 3], 0))
    compiled = scan_narrow._narrow_reduce._cache_size()
    for hosts, lo in (([7, 15, 22], 60), ([0, 9, 23, 12], 240), ([5, 6, 8], 6)):
        assert len(db.sql(sql(hosts, lo))) == 20
    assert scan_narrow._narrow_reduce._cache_size() == compiled


def test_narrow_builds_nothing_of_the_tables_length(db, monkeypatch):
    """The full launch's mask and run sweep are not called, and no numpy
    array the narrow path makes is as long as the table."""
    def never(*a, **k):
        raise AssertionError("the narrow path swept the table")
    monkeypatch.setattr(scan_full, "_scan_row_mask", never)
    monkeypatch.setattr(scan_full, "_scan_runs", never)
    monkeypatch.setattr(scan_full, "_launch_scan_kernel", never)
    seen = []
    real = scan_narrow.launch

    def spy(scan, schema, plan, sel, part):
        seen.append((scan.num_rows, sel.rows, sel.padded_rows))
        return real(scan, schema, plan, sel, part)
    monkeypatch.setattr(scan_narrow, "launch", spy)
    sql = (f"SELECT host, {EXACT} FROM cpu WHERE host IN ({in_list([1, 13])}) "
           f"AND region != 'r9' AND {WINDOW} GROUP BY host")
    stages = db.stages(sql)
    assert stages["dispatch"] == "device-resident (scan cache)"
    assert "path=narrow" in stages["reduce"]
    for part in ("reduce.mask", "reduce.runs", "reduce.upload",
                 "reduce.launch", "reduce.fetch", "reduce.collect"):
        assert "t0_ns=" in stages[part], part
    assert seen == [(db.ref.shape[0], 600, 1024)]


def test_lower_bound_is_searchsorted_per_range():
    rng = np.random.default_rng(3)
    lens = rng.integers(0, 40, 50)
    ts = np.concatenate([np.sort(rng.integers(0, 100, n)) for n in lens])
    hi = np.cumsum(lens)
    lo = hi - lens
    for value in (-1, 0, 37, 99, 100):
        want = [a + np.searchsorted(ts[a:b], value, side="left")
                for a, b in zip(lo, hi)]
        assert list(scan_cache._lower_bound(ts, lo, hi, value)) == want


def test_many_runs_take_the_high_cardinality_kernels(db, monkeypatch):
    """16 of 160 series by 10 s buckets: 16,000 runs, past the kernels'
    high-cardinality threshold, so the compact block carries per-row run
    ids; a synthetic scan stands in for a table of 160,000 rows."""
    from greptimedb_tpu.storage.series import SeriesDict
    hosts, ticks = 160, 1000
    captured = []
    real = agg_plan.plan_for
    picked = list(range(3, 80, 5))
    with monkeypatch.context() as m:
        m.setattr(agg_plan, "plan_for", lambda t, a, q: captured.append(
            (real(t, a, q), t.schema)) or captured[-1][0])
        db.sql(f"SELECT host, date_bin(INTERVAL '10 second', ts) AS b, "
               f"{EXACT} FROM cpu WHERE host IN ({in_list(picked)}) "
               "GROUP BY host, b")
    plan, schema = captured[-1]
    sd = SeriesDict.for_schema(schema)
    sd.encode_rows([[f"h{h:02d}" for h in range(hosts)],   # h00..h99, h100..
                    [f"r{h % 3}" for h in range(hosts)]])
    rng = np.random.default_rng(11)
    n = hosts * ticks
    scan = scan_cache.MergedScan(
        np.repeat(np.arange(hosts, dtype=np.int32), ticks),
        np.tile(T0 + np.arange(ticks, dtype=np.int64) * TICK_MS, hosts),
        {"usage": (rng.random(n) * 100, None),
         "idle": (rng.random(n) * 100, rng.random(n) > 0.2)}, sd, T0)
    sel = scan_narrow.select(scan, schema, plan)
    assert (sel.n_ranges, sel.rows, sel.padded_rows) == (16, 16_000, 16_384)
    narrow = tpu_exec._moment_frame_for_scan(scan, schema, plan)
    monkeypatch.setattr(scan_narrow, "_NARROW_MAX_SHARE", 10**12)
    whole = tpu_exec._moment_frame_for_scan(scan, schema, plan)
    assert len(narrow) == 16_000
    pd.testing.assert_frame_equal(narrow.reset_index(drop=True),
                                  whole.reset_index(drop=True),
                                  check_exact=True)


# ---------------------------------------------------------------------------
# the full launch's group axis: the statement's live runs, or the table's
# (ISSUE 36). By 20 s the table is 24 x 400 = 9,600 runs (bucket 16,384,
# past the kernels' high-cardinality threshold); 5 to 8 hosts' windows are
# 750 to 3,200 live runs (bucket 1,024 to 4,096) and run full, not narrow.
# ---------------------------------------------------------------------------

def axis_counter(axis: str) -> float:
    return total("greptime_scan_group_axis_total", f'axis="{axis}"')


@pytest.fixture
def on_axis(monkeypatch):
    """-> a context in which every full launch with a selection takes the
    named group axis, and every resident launch is a full one."""
    @contextlib.contextmanager
    def forced(axis):
        with monkeypatch.context() as m:
            m.setattr(scan_narrow, "_NARROW_MAX_SHARE", 10**12)
            m.setattr(scan_narrow, "scan_group_axis",
                      lambda n_runs, n_live: axis if n_live is not None
                      else "table")
            yield
    return forced


def both_axes(db, on_axis, sql):
    """-> (live answer, table answer), each by the axis it names."""
    before = axis_counter("live"), axis_counter("table")
    with on_axis("live"):
        live = db.sql(sql)
    assert (axis_counter("live"), axis_counter("table")) == \
        (before[0] + 1, before[1]), "the launch did not take the live axis"
    with on_axis("table"):
        table = db.sql(sql)
    assert axis_counter("table") == before[1] + 1
    return live, table


BY_20S = "date_bin(INTERVAL '20 second', ts) AS b"
#: a window whose edges lie inside 20 s runs (odd ticks)
INSIDE = f"ts >= {T0 + 31 * TICK_MS} AND ts < {T0 + 331 * TICK_MS}"

#: (id, WHERE)
LIVE_SELECTIONS = [
    ("eq", f"host = 'h05' AND {WINDOW}"),
    ("in-6", f"host IN ({in_list([2, 3, 8, 13, 21, 23])}) AND {WINDOW}"),
    ("in-no-window", f"host IN ({in_list([0, 7, 9, 16, 23])})"),
    ("edge-inside-a-run",
     f"host IN ({in_list([1, 4, 6, 11, 17, 22])}) AND {INSIDE}"),
    ("in-and-ne", f"host IN ({in_list(range(2, 10))}) AND region != 'r2' "
                  f"AND {WINDOW}"),
    ("field-filter", f"host IN ({in_list(range(3, 9))}) AND usage > 70 "
                     f"AND {INSIDE}"),
    ("one-series-outside-the-window",
     f"host IN ('h06', 'h10', 'h30') AND {WINDOW}"),
]


@pytest.mark.parametrize("group", [
    ("by-host-and-20s", f"host, {BY_20S}, ", "host, b"),
    ("by-20s", f"{BY_20S}, ", "b")], ids=lambda g: g[0])
@pytest.mark.parametrize("where", LIVE_SELECTIONS,
                         ids=[s[0] for s in LIVE_SELECTIONS])
def test_the_live_axis_answers_as_the_tables(db, on_axis, where, group):
    """The exact ops bit for bit; `sum` and `avg` take the edge-window
    form on the live axis (its bucket is under the threshold) where the
    table's takes prefix differences, whose rounding in a run of two
    rows is what `test_kernels.py` allows the high-cardinality form."""
    _, select, group_by = group
    sql = (f"SELECT {select}{EXACT}, sum(usage), avg(idle) FROM cpu "
           f"WHERE {where[1]} GROUP BY {group_by} ORDER BY {group_by}")
    live, table = both_axes(db, on_axis, sql)
    assert len(live) > 0
    loose = ["sum(usage)", "avg(idle)"]
    pd.testing.assert_frame_equal(live.drop(columns=loose),
                                  table.drop(columns=loose), check_exact=True)
    for col in loose:
        np.testing.assert_allclose(live[col], table[col], rtol=2e-4,
                                   atol=1e-3)


def test_live_runs_past_the_threshold_keep_the_prefix_form_bit_for_bit(
        db, on_axis):
    """20 of 24 hosts by 10 s: 16,000 live runs of 19,200, both buckets
    past the threshold, so `sum` and `avg` are the same prefix
    differences on either axis."""
    sql = (f"SELECT host, date_bin(INTERVAL '10 second', ts) AS b, {EXACT}, "
           f"sum(usage), avg(usage), avg(idle) FROM cpu WHERE host IN "
           f"({in_list(range(2, 22))}) GROUP BY host, b ORDER BY host, b")
    live, table = both_axes(db, on_axis, sql)
    assert len(live) == 20 * TICKS
    pd.testing.assert_frame_equal(live, table, check_exact=True)


@pytest.mark.parametrize("where", [
    "host IN ('never-seen', 'nor-this')",
    f"host IN ({in_list(range(4, 10))}) AND ts >= {T0 + TICKS * TICK_MS}",
], ids=["unknown-values", "window-after"])
def test_an_empty_selection_launches_nothing_on_either_axis(db, on_axis,
                                                            where):
    sql = f"SELECT host, {BY_20S}, max(usage) FROM cpu WHERE {where} " \
          "GROUP BY host, b"
    for axis in ("live", "table"):
        before = axis_counter("live")
        with on_axis(axis):
            assert len(db.sql(sql)) == 0
            assert "groups=table" in db.stages(sql)["reduce"]
        assert axis_counter("live") == before      # nothing was launched


def test_scan_group_axis_reads_only_counts():
    axis = scan_narrow.scan_group_axis
    assert axis(7_680_000, None) == "table"        # no ranges resolved
    assert axis(7_680_000, 816_000) == "live"      # the longrange panel
    # a quarter of the table's bucket, and the bucket past the threshold
    assert axis(7_680_000, 2_097_152) == "live"
    assert axis(7_680_000, 2_097_153) == "table"
    assert axis(16_384, 4_096) == "live"
    assert axis(8_192, 16) == "table"              # pickups are not the cost
    assert axis(9_600, 3_200) == "live" and axis(9_600, 4_097) == "table"
    assert axis(1, 1) == "table"


def test_run_spans_keep_every_run_a_range_touches():
    run_starts = np.array([0, 4, 8, 12, 20, 21, 30])
    sel = scan_narrow.Selection(
        np.arange(4, dtype=np.int32),
        np.array([0, 9, 12, 22], dtype=np.int64),       # starts
        np.array([4, 2, 9, 3], dtype=np.int64))         # lens
    lo, hi = scan_narrow.run_spans(run_starts, sel)
    # [0, 4) is run 0; [9, 11) lies inside run 2; [12, 21) is runs 3 and
    # 4; [22, 25) lies inside run 5
    assert list(zip(lo, hi)) == [(0, 1), (2, 3), (3, 5), (5, 6)]
    run_ends = np.append(run_starts[1:], 40).astype(np.int32)
    n_live, groups, starts, ends = scan_narrow.live_layout(
        run_starts, run_ends, lo, hi, 40)
    assert (n_live, groups) == (5, 256)
    assert list(starts[:6]) == [0, 8, 12, 20, 21, 40]
    assert list(ends[:6]) == [4, 12, 20, 21, 30, 40]
    # two ranges inside one run share it
    sel = scan_narrow.Selection(np.arange(2, dtype=np.int32),
                                np.array([13, 16], dtype=np.int64),
                                np.array([2, 3], dtype=np.int64))
    lo, hi = scan_narrow.run_spans(run_starts, sel)
    assert int((hi - lo).sum()) == 1


def test_the_live_axis_builds_nothing_of_the_tables_run_count(db,
                                                              monkeypatch):
    """Warm (the table's runs and layout are cached with the scan), a
    statement on the live axis cuts no layout and hands the launch, the
    fetch and `collect` arrays of the live runs' bucket."""
    sql = (f"SELECT host, {BY_20S}, {EXACT} FROM cpu WHERE host IN "
           f"({in_list([1, 5, 9, 13, 17, 21])}) AND {WINDOW} "
           "GROUP BY host, b")
    assert "groups=live" in db.stages(sql)["reduce"]       # warm

    def never(*a, **k):
        raise AssertionError("the live axis cut the table's runs again")
    monkeypatch.setattr(scan_launch, "_segment_layout", never)
    seen = {}
    real_layout, real_collect = scan_narrow.live_layout, \
        moment_fold._collect_moment_frame

    def layout(run_starts, run_ends, lo, hi, n, *pinned):
        out = real_layout(run_starts, run_ends, lo, hi, n, *pinned)
        seen["table_runs"] = len(run_starts)
        seen["layout"] = (out[0], len(out[2]), len(out[3]))
        return out

    def collect(launched, plan, counts, res_np):
        seen["collect"] = (launched.nruns, len(launched.run_sids),
                           len(launched.run_buckets), len(counts),
                           {len(r) for r in res_np})
        return real_collect(launched, plan, counts, res_np)
    monkeypatch.setattr(scan_narrow, "live_layout", layout)
    monkeypatch.setattr(moment_fold, "_collect_moment_frame", collect)
    before = axis_counter("live")
    detail = db.stages(sql)["reduce"]
    assert axis_counter("live") == before + 1
    assert seen["table_runs"] == HOSTS * TICKS // 2
    assert seen["layout"] == (900, 1024, 1024)
    assert seen["collect"] == (900, 900, 900, 1024, {1024})
    assert "path=full, groups=live, live_runs=900, table_runs=9600" in detail


def test_no_tag_conjunct_keeps_the_tables_axis_and_its_program(db):
    from greptimedb_tpu.ops.kernels import _sorted_grouped_aggregate_pre

    def sql(lo):
        return (f"SELECT host, {BY_20S}, max(usage), count(idle) FROM cpu "
                f"WHERE region != 'r1' AND ts >= {T0 + lo * TICK_MS} AND "
                f"ts < {T0 + (lo + 300) * TICK_MS} GROUP BY host, b")
    db.sql(sql(30))
    compiled = _sorted_grouped_aggregate_pre._cache_size()
    before = axis_counter("live"), axis_counter("table")
    assert len(db.sql(sql(100))) == 16 * 150
    detail = db.stages(sql(200))["reduce"]
    assert "path=full, groups=table" in detail and "live_runs" not in detail
    assert (axis_counter("live"), axis_counter("table")) == \
        (before[0], before[1] + 2)
    assert _sorted_grouped_aggregate_pre._cache_size() == compiled
    # other hosts of the same shape on the live axis: one program more,
    # then nothing
    def live(hosts, lo):
        return (f"SELECT host, {BY_20S}, max(usage), count(idle) FROM cpu "
                f"WHERE host IN ({in_list(hosts)}) AND ts >= "
                f"{T0 + lo * TICK_MS} AND ts < {T0 + (lo + 300) * TICK_MS} "
                "GROUP BY host, b")
    assert "groups=live" in db.stages(live(range(6), 30))["reduce"]
    compiled = _sorted_grouped_aggregate_pre._cache_size()
    for hosts, lo in ((range(6, 12), 100), ([1, 3, 5, 7, 20, 23], 260)):
        assert len(db.sql(live(hosts, lo))) == 6 * 150
    assert _sorted_grouped_aggregate_pre._cache_size() == compiled


# ---------------------------------------------------------------------------
# the full launch's time window: two scalars of its program (ISSUE 43). The
# host makes a row mask of the table's length where a tag predicate or a
# field filter needs one, never for the window.
# ---------------------------------------------------------------------------

def masks_made() -> tuple:
    return tuple(total("greptime_scan_row_mask_total", f'made="{made}"')
                 for made in ("none", "host"))


#: (id, WHERE beside the window, pandas filter, the mask the host makes)
WINDOWED = [
    ("time-only", "", lambda r: r.usage == r.usage, "none"),
    ("ne-tag", "region != 'r1' AND ", lambda r: r.region != "r1", "host"),
    ("field-filter", "usage > 60 AND ", lambda r: r.usage > 60, "host"),
    ("in-tag-run-full", f"host IN ({in_list(range(0, HOSTS, 2))}) AND ",
     lambda r: r.host.isin([f"h{h:02d}" for h in range(0, HOSTS, 2)]),
     "host"),
]


@pytest.mark.parametrize("case", WINDOWED, ids=[c[0] for c in WINDOWED])
def test_a_windowed_full_launch_answers_the_reference_and_names_its_mask(
        db, case):
    _, where, keep, made = case
    sql = (f"SELECT host, date_bin(INTERVAL '1 minute', ts) AS minute, "
           f"{EXACT}, sum(usage), avg(idle) FROM cpu WHERE {where}{WINDOW} "
           "GROUP BY host, minute ORDER BY host, minute")
    before = masks_made(), counter("full")
    got = db.sql(sql)
    assert counter("full") == before[1] + 1
    assert masks_made() == (before[0][0] + (made == "none"),
                            before[0][1] + (made == "host"))
    want = reference(db.ref, keep, ["host", "minute"])
    assert len(got) == len(want) > 0
    assert list(got.host) == list(want.host)
    assert np.array_equal(got["max(usage)"].to_numpy(),
                          want.mx.to_numpy().astype(np.float32))
    assert np.array_equal(got["min(usage)"].to_numpy(),
                          want.mn.to_numpy().astype(np.float32))
    assert np.array_equal(got["count(usage)"].to_numpy(), want.n)
    assert np.array_equal(got["count(idle)"].to_numpy(), want.ni)
    np.testing.assert_allclose(got["sum(usage)"], want.sm, rtol=AVG_RTOL)
    np.testing.assert_allclose(got["avg(idle)"], want.avi, rtol=AVG_RTOL)
    detail = db.stages(sql)["reduce"]
    assert f"path=full, groups=table, mask={made}" in detail


def test_a_narrow_launch_makes_no_mask_of_the_tables_length(db):
    sql = (f"SELECT host, max(usage) FROM cpu WHERE host = 'h05' AND "
           f"usage > 10 AND {WINDOW} GROUP BY host")
    before = masks_made(), counter("narrow")
    assert len(db.sql(sql)) == 1
    assert counter("narrow") == before[1] + 1
    assert masks_made() == (before[0][0] + 1, before[0][1])
    assert "mask=" not in db.stages(sql)["reduce"]


# ---------------------------------------------------------------------------
# the form of a launch's float sums (ISSUE 45, `ops/kernels.py:sum_form`):
# the prefix read at the bounds past the high-cardinality threshold, the
# edge windows under it. By 20 s the table is 9,600 runs (bucket 16,384).
# ---------------------------------------------------------------------------

SUM_FORMS = [
    ("table-by-20s", "", "20 second", "path=full, groups=table", "prefix"),
    ("table-by-hour", "", "1 hour", "path=full, groups=table", "edge"),
    ("one-host", "WHERE host = 'h05' ", "20 second", "path=narrow", "edge"),
]


@pytest.mark.parametrize("case", SUM_FORMS, ids=[c[0] for c in SUM_FORMS])
def test_the_reduce_row_names_the_form_of_its_float_sums(db, case):
    _, where, stride, path, form = case
    sql = (f"SELECT host, date_bin(INTERVAL '{stride}', ts) AS b, "
           f"sum(usage), avg(idle), count(idle) FROM cpu {where}"
           "GROUP BY host, b ORDER BY host, b")

    def forms():
        return [total("greptime_scan_sum_form_total", f'form="{f}"')
                for f in ("prefix", "edge")]

    before = forms()
    got = db.sql(sql)
    assert forms() == [before[0] + (form == "prefix"),
                       before[1] + (form == "edge")]
    detail = db.stages(sql)["reduce"]
    assert path in detail and f"passes=4, sums={form}" in detail
    ref = db.ref if not where else db.ref[db.ref.host == "h05"]
    ms = {"20 second": 20_000, "1 hour": 3_600_000}[stride]
    want = ref.assign(b=ref.ts // ms).groupby(["host", "b"]).agg(
        sm=("usage", "sum"), avi=("idle", "mean"), ni=("idle", "count"))
    assert len(got) == len(want) > 0
    assert np.array_equal(got["count(idle)"].to_numpy(), want.ni)
    np.testing.assert_allclose(got["sum(usage)"], want.sm, rtol=AVG_RTOL)
    keep = want.ni.to_numpy() > 0
    np.testing.assert_allclose(got["avg(idle)"][keep], want.avi[keep],
                               rtol=AVG_RTOL)


# ---------------------------------------------------------------------------
# the form of a launch's `first` / `last` (ISSUE 46,
# `ops/kernels.py:extreme_form`): rows of 128 lanes at a segment's bounds at
# or under the high-cardinality threshold, where the launch ships no run
# ids; the shift-doubling kernels past it. A launch of sums alone holds no
# extreme and counts none.
# ---------------------------------------------------------------------------

EXTREME_FORMS = [
    ("table-by-20s", "last(usage), first(idle)", "", "20 second",
     "path=full, groups=table", "doubling"),
    ("table-by-hour", "last(usage), first(idle)", "", "1 hour",
     "path=full, groups=table", "rows"),
    ("one-host", "last(usage), first(idle)", "WHERE host = 'h05' ",
     "20 second", "path=narrow", "rows"),
    ("sums-alone", "sum(usage), count(idle)", "", "1 hour",
     "path=full, groups=table", None),
]


@pytest.mark.parametrize("case", EXTREME_FORMS,
                         ids=[c[0] for c in EXTREME_FORMS])
def test_the_reduce_row_names_the_form_of_its_first_and_last(db, case):
    _, aggs, where, stride, path, form = case
    sql = (f"SELECT host, date_bin(INTERVAL '{stride}', ts) AS b, {aggs} "
           f"FROM cpu {where}GROUP BY host, b ORDER BY host, b")

    def forms():
        return [total("greptime_scan_extreme_form_total", f'form="{f}"')
                for f in ("rows", "doubling")]

    before = forms()
    got = db.sql(sql)
    assert forms() == [before[0] + (form == "rows"),
                       before[1] + (form == "doubling")]
    detail = db.stages(sql)["reduce"]
    assert path in detail and "sums=" in detail
    assert (f"ext={form}" in detail) if form else ("ext=" not in detail)
    if form is None:
        return
    ref = db.ref if not where else db.ref[db.ref.host == "h05"]
    ms = {"20 second": 20_000, "1 hour": 3_600_000}[stride]
    by = ref.assign(b=ref.ts // ms).sort_values(["host", "ts"]).groupby(
        ["host", "b"])
    want = pd.DataFrame({
        "last": by.usage.last(),
        "first": by.idle.first()})    # pandas skips a NULL, as SQL does
    assert len(got) == len(want) > 0
    assert np.array_equal(got["last(usage)"].to_numpy(),
                          want["last"].to_numpy().astype(np.float32))
    assert np.array_equal(got["first(idle)"].to_numpy(),
                          want["first"].to_numpy().astype(np.float32),
                          equal_nan=True)


H0 = T0 - T0 % 3_600_000                    # a whole hour


def passes_counter(kind: str) -> float:
    return total("greptime_scan_kernel_passes_total", f'kind="{kind}"')


@pytest.fixture(scope="module")
def wide(tmp_path_factory):
    """TSBS's shape, ten DOUBLE fields, 6 hosts x 3 h of one row a
    minute, twice: `wide` holds no NULL, `holes` one, in c3."""
    dn = DatanodeInstance(DatanodeOptions(
        data_home=str(tmp_path_factory.mktemp("wide")),
        register_numbers_table=False))
    dn.start()
    fe = FrontendInstance(dn)
    fe.start()
    for table in ("wide", "holes"):
        fe.do_query(f"CREATE TABLE {table} (hostname STRING, ts TIMESTAMP "
                    "TIME INDEX, " +
                    ", ".join(f"c{i} DOUBLE" for i in range(10)) +
                    ", PRIMARY KEY(hostname))")
        fe.do_query(f"INSERT INTO {table} VALUES " + ", ".join(
            f"('h{h}', {H0 + t * 60_000}, " + ", ".join(
                "NULL" if (table, h, t, c) == ("holes", 2, 70, 3)
                else repr(_wide_value(h, t, c)) for c in range(10))
            + ")" for h in range(6) for t in range(180)))
    db = Db.__new__(Db)
    db.dn, db.fe = dn, fe
    yield db
    fe.do_query("SET tpu_dispatch_min_rows = 131072")
    db.close()


def _wide_value(h, t, c):
    return float((h + 3 * t + 7 * c) % 101)


def _avg_by_hour(table):
    avgs = ", ".join(f"avg(c{i})" for i in range(10))
    return (f"SELECT hostname, date_bin(INTERVAL '1 hour', ts) AS hour, "
            f"{avgs} FROM {table} GROUP BY hostname, hour")


def test_a_launch_runs_a_pass_a_distinct_validity(wide):
    """ISSUE 41: avg of ten columns without a NULL is the row count and
    ten sums (11 passes over the rows where a pass a moment is 21); a
    column that holds a NULL keeps a count of its own."""
    sql = _avg_by_hour("wide")
    before = passes_counter("run"), passes_counter("shared")
    detail = wide.stages(sql)["reduce"]
    assert "path=full" in detail and "moments=21, passes=11" in detail
    assert (passes_counter("run"), passes_counter("shared")) == \
        (before[0] + 11, before[1] + 10)
    got = wide.sql(sql)
    assert len(got) == 6 * 3
    h2 = got[got["hostname"] == "h2"].sort_values("hour")
    for c in (3, 4):
        assert list(h2[f"avg(c{c})"]) == [
            float(np.mean([_wide_value(2, t, c) for t in range(lo, lo + 60)]))
            for lo in (0, 60, 120)]
    # two lasts and their time extremes: the row count and one arg-extreme
    assert "moments=5, passes=2" in wide.stages(
        "SELECT hostname, last(c0), last(c1) FROM wide GROUP BY hostname"
    )["reduce"]

    before = passes_counter("run"), passes_counter("shared")
    assert "moments=21, passes=12" in wide.stages(
        _avg_by_hour("holes"))["reduce"]
    assert (passes_counter("run"), passes_counter("shared")) == \
        (before[0] + 12, before[1] + 9)
    holes = wide.sql(_avg_by_hour("holes"))
    pd.testing.assert_frame_equal(
        holes.drop(columns="avg(c3)"), got.drop(columns="avg(c3)"),
        check_exact=True)
    differs = holes["avg(c3)"] != got["avg(c3)"]
    assert list(holes[differs]["hostname"]) == ["h2"]
    assert holes[differs]["avg(c3)"].iloc[0] == float(np.mean(
        [_wide_value(2, t, 3) for t in range(60, 120) if t != 70]))


def test_a_tail_that_meets_a_null_keeps_that_columns_count(wide):
    """The base holds no NULL and the row written after it one: the
    tail's launch counts c3 by itself, the base's does not."""
    sql = _avg_by_hour("wide")
    wide.sql(sql)
    wide.fe.do_query(
        f"INSERT INTO wide VALUES ('h2', {H0 + 180 * 60_000}, " +
        ", ".join("NULL" if c == 3 else "1.0" for c in range(10)) + ")")
    detail = wide.stages(sql)["reduce"]
    assert "moments=21, passes=11" in detail and "tail_rows=1" in detail \
        and "tail_passes=12" in detail
    got = wide.sql(sql)
    row = got[got["hostname"] == "h2"].sort_values("hour").iloc[-1]
    assert row["avg(c0)"] == 1.0 and row["avg(c9)"] == 1.0
    assert pd.isna(row["avg(c3)"])


def test_the_point_cell_runs_narrow_on_the_cpu_debug_run():
    """`benchmark/run.py --workload tsbs4k-point` at its CPU debug size:
    every family correct and device-resident, `path=narrow` on every
    `reduce` row but the priming `lastpoint`'s."""
    import json
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    run = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "tsbs4k-point",
         "--seed", "7", "--seconds", "3", "--trace", "1",
         "--debug-platform", "cpu"],
        cwd=root, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert run.returncode == 3, run.stderr[-2000:]     # a debug run's own
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    with open(os.path.join(root, ".bench_work", "tsbs4k-point-seed7-trace1",
                           "record.json")) as f:
        record = json.load(f)
    window = [s for s in record["statements"] if s["in_window"]]
    assert len(window) >= 8 and {s["family"] for s in window} == {
        "single-groupby-1-1-1", "single-groupby-1-1-12",
        "single-groupby-1-8-1", "single-groupby-5-1-1",
        "single-groupby-5-1-12", "single-groupby-5-8-1", "cpu-max-all-1",
        "cpu-max-all-8"}
    warm = [w for w in record["warm"]
            if w["explain"] and w["family"] != "lastpoint"]
    for s in window + warm:
        stages = s["stages"]
        assert stages["dispatch"]["detail"] == "device-resident (scan cache)"
        assert "path=narrow" in stages["reduce"]["detail"], s["family"]
    assert record["compiled_in_window"] in (0, None)
