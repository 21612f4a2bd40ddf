"""A statement's time window reaches the scan kernel as two scalars
(ISSUE 43, `query/scan_launch.py:_device_window`,
`ops/kernels.py:_sorted_grouped_aggregate_pre`): the host makes no row
mask of the table's length for a time predicate and uploads none.

The reference is the launch as it was: the window applied to the row mask
on the host (two passes over the times) and the program handed the open
bounds. Counts, extremes, `first` and `last` must equal it bit for bit;
float sums are the same numbers added by two compiled programs, and are
compared as `tests/test_kernels.py` compares such sums.
"""

import contextlib

import numpy as np
import pandas as pd
import pytest

from greptimedb_tpu.common.telemetry import registry_snapshot
from greptimedb_tpu.datanode.instance import DatanodeInstance, DatanodeOptions
from greptimedb_tpu.frontend.instance import FrontendInstance
from greptimedb_tpu.ops.kernels import (_SEG_HIGH_CARD_THRESHOLD,
                                        _sorted_grouped_aggregate_pre,
                                        distinct_arrays, moment_results,
                                        open_window, seg_len_bucket,
                                        shape_bucket)
from greptimedb_tpu.query import (agg_plan, scan_full, scan_launch,
                                  scan_narrow, tpu_exec)
from greptimedb_tpu.storage import scan_cache
from test_kernels import block_edge_lens

HOSTS, TICKS, TICK_MS = 40, 600, 10_000
T0 = 1_700_000_400_000                      # a whole ten minutes
SUM_TOL = dict(rtol=2e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# the program: a window handed as two scalars is the mask ANDed on the host
# ---------------------------------------------------------------------------

#: (id, groups, segments picked out of the layout or None, seg_len_k?)
LAYOUTS = [
    ("low", 300, None, False),
    ("high", 9_000, None, False),           # above _SEG_HIGH_CARD_THRESHOLD
    ("doubling", 9_000, None, True),        # the shift-doubling kernels
    ("live-runs", 12_000, 9_000, True),     # dense=False with `starts`
    # segments that meet the prefix form's 128-row blocks every way
    ("high-blocks", 9_000, None, False),
    ("live-runs-blocks", 12_000, 9_000, True),
]


@pytest.mark.parametrize("layout", LAYOUTS, ids=[c[0] for c in LAYOUTS])
def test_the_programs_window_is_the_mask_made_on_the_host(layout):
    name, groups, picked, with_k = layout
    rng = np.random.default_rng(groups + 1)
    longest = 70
    lens = rng.integers(1, 9, groups)
    lens[rng.integers(0, groups, 12)] = rng.integers(30, longest + 1, 12)
    if "blocks" in name:
        edge = [ln for ln in block_edge_lens() if ln]
        lens[:len(edge)], longest = edge, max(edge)
    n = int(lens.sum())
    nb = shape_bucket(groups, minimum=256)
    ends = np.full(nb, n, dtype=np.int32)
    ends[:groups] = np.cumsum(lens)
    starts = None
    gids = np.repeat(np.arange(groups, dtype=np.int32), lens)
    ts = rng.integers(0, 40, n).astype(np.int32)        # ties, unsorted
    mask = rng.random(n) > 0.15
    valid = rng.random(n) > 0.2
    col = (rng.random(n, dtype=np.float32) * 100) - 50
    if picked is not None:
        first = np.concatenate([[0], ends[:-1]]).astype(np.int32)
        live = rng.choice(groups, picked, replace=False)
        if "blocks" in name:          # the segments at the blocks' edges
            live = np.union1d(live[len(edge):], np.arange(len(edge)))
        live, picked = np.sort(live), len(live)
        nb = shape_bucket(picked, minimum=256)
        starts = np.full(nb, n, dtype=np.int32)
        starts[:picked] = first[live]
        live_ends = np.full(nb, n, dtype=np.int32)
        live_ends[:picked] = ends[live]
        ends = live_ends
    assert (nb > _SEG_HIGH_CARD_THRESHOLD) == (name != "low")
    ops = ("count", "sum", "avg", "min", "max", "first", "last", "min",
           "max", "count")
    values, value_ix = distinct_arrays(
        [ts if i in (7, 8) else col for i in range(len(ops))], ts)
    masks, mask_ix = distinct_arrays(
        [valid] * (len(ops) - 1) + [None], None)

    def run(row_mask, window):
        distinct, counts = _sorted_grouped_aggregate_pre(
            gids, row_mask, ts, window, values, masks, ends, starts,
            num_groups=nb, ops=ops, value_ix=value_ix, mask_ix=mask_ix,
            seg_len_k=seg_len_bucket(longest) if with_k else None)
        res, _ = moment_results(distinct, counts, ops, value_ix, mask_ix)
        return [np.asarray(r) for r in res], np.asarray(counts)

    compiled = None
    for lo, hi in ((10, 29), (0, 0), (39, 39), (-5, 12), (35, 10**6),
                   (17, 16)):
        window = np.asarray(lo, np.int32), np.asarray(hi, np.int32)
        got, got_counts = run(mask, window)
        want, want_counts = run(mask & (ts >= lo) & (ts <= hi),
                                open_window(np.int32))
        assert np.array_equal(got_counts, want_counts)
        assert (got_counts.sum() == 0) == (lo > hi)
        for op, g, w in zip(ops, got, want):
            if op in ("sum", "avg"):
                np.testing.assert_allclose(g, w, err_msg=op, **SUM_TOL)
            else:
                assert np.array_equal(g, w, equal_nan=True), (op, lo, hi)
        # the bounds are values: one program whatever the window
        if compiled is None:
            compiled = _sorted_grouped_aggregate_pre._cache_size()
        assert _sorted_grouped_aggregate_pre._cache_size() == compiled


def test_the_open_window_is_the_time_indexs_extremes():
    lo, hi = open_window(np.int32)
    assert (lo.shape, lo.dtype, int(lo), int(hi)) == \
        ((), np.int32, -2**31, 2**31 - 1)
    # the device's time index without x64: an int64 is an int32 there
    assert open_window(np.int64)[1].dtype == np.int32
    lo, hi = open_window(np.float32)
    assert lo == -np.inf and hi == np.inf


# ---------------------------------------------------------------------------
# the launch: plans of real statements over a synthetic scan
# ---------------------------------------------------------------------------

class Plans:
    """A frontend with an empty table: the plans of statements over it."""

    def __init__(self, data_home):
        self.fe = FrontendInstance(DatanodeInstance(DatanodeOptions(
            data_home=data_home, register_numbers_table=False)))
        self.fe.start()
        self.fe.do_query(
            "CREATE TABLE cpu (host STRING, region STRING, "
            "ts TIMESTAMP TIME INDEX, usage DOUBLE, idle DOUBLE, "
            "PRIMARY KEY(host, region))")
        self.schema = self.fe.catalog.table("greptime", "public",
                                            "cpu").schema

    def of(self, sql: str):
        captured = []
        real = agg_plan.plan_for
        with pytest.MonkeyPatch.context() as m:
            m.setattr(agg_plan, "plan_for", lambda t, a, q: captured.append(
                real(t, a, q)) or captured[-1])
            self.fe.do_query(sql)
        assert captured[-1] is not None, sql
        return captured[-1]


@pytest.fixture(scope="module")
def plans(tmp_path_factory):
    p = Plans(str(tmp_path_factory.mktemp("plans")))
    yield p
    p.fe.shutdown()


def series_dict(schema, hosts):
    from greptimedb_tpu.storage.series import SeriesDict
    sd = SeriesDict.for_schema(schema)
    sd.encode_rows([[f"h{h:02d}" for h in range(hosts)],
                    [f"r{h % 3}" for h in range(hosts)]])
    return sd


@pytest.fixture(scope="module")
def scan(plans):
    """40 hosts x 600 ticks of 10 s, sorted by (series, time); `idle`
    holds NULLs."""
    rng = np.random.default_rng(43)
    n = HOSTS * TICKS
    return scan_cache.MergedScan(
        np.repeat(np.arange(HOSTS, dtype=np.int32), TICKS),
        np.tile(T0 + np.arange(TICKS, dtype=np.int64) * TICK_MS, HOSTS),
        {"usage": (np.round(rng.random(n) * 100, 3), None),
         "idle": (np.round(rng.random(n) * 100, 3), rng.random(n) > 0.2)},
        series_dict(plans.schema, HOSTS), T0)


@contextlib.contextmanager
def host_masked(monkeypatch):
    """A context in which a launch is the parent's: the window ANDed into
    the row mask on the host, the program handed the open bounds."""
    real = scan_full._scan_row_mask

    def row_mask(scan, schema, plan, sel=None):
        mask = real(scan, schema, plan, sel)
        if mask is scan_full._NO_ROWS:
            return mask
        if mask is None:
            mask = np.zeros(scan.num_rows, dtype=bool)
            mask[:scan.valid_rows] = True
        if plan.time_lo is not None:
            mask &= scan.ts >= plan.time_lo
        if plan.time_hi is not None:
            mask &= scan.ts < plan.time_hi
        return mask if mask.any() else scan_full._NO_ROWS

    with monkeypatch.context() as m:
        m.setattr(scan_full, "_scan_row_mask", row_mask)
        m.setattr(scan_launch, "_device_window",
                  lambda plan, scan: open_window(np.int32))
        yield


def assert_frames_equal(got, want, plan):
    assert (got is None) == (want is None)
    if got is None:
        return
    assert list(got.columns) == list(want.columns) and len(got) == len(want)
    sums = {m.slot for m in plan.moments if m.op in ("sum", "sum_sq")}
    for c in got.columns:
        g, w = got[c].to_numpy(), want[c].to_numpy()
        if c in sums:
            np.testing.assert_allclose(g, w, err_msg=c, **SUM_TOL)
        else:
            assert g.dtype == w.dtype and \
                np.array_equal(g, w, equal_nan=g.dtype.kind == "f"), c


AGGS = ("max(usage), min(usage), count(usage), count(idle), max(idle), "
        "first(usage), last(idle), sum(usage), avg(idle)")
LO, HI = T0 + 95 * TICK_MS, T0 + 431 * TICK_MS      # inside two buckets
WINDOW = f"ts >= {LO} AND ts < {HI}"
BY_10M = "date_bin(INTERVAL '10 minute', ts) AS b"
BY_10S = "date_bin(INTERVAL '10 second', ts) AS b"  # 24,000 runs

#: (id, SELECT list, WHERE, GROUP BY, the mask the host makes)
STATEMENTS = [
    ("time-only", f"host, {BY_10M}, ", WINDOW, "host, b", "none"),
    ("time-only-plain", "", WINDOW, "", "none"),
    ("time-only-high-cardinality", f"host, {BY_10S}, ", WINDOW, "host, b",
     "none"),
    ("open-above", f"host, {BY_10M}, ", f"ts >= {LO}", "host, b", "none"),
    ("open-below", "host, ", f"ts < {HI}", "host", "none"),
    ("no-window", "host, ", "", "host", "none"),
    ("ne-tag", f"host, {BY_10M}, ", f"region != 'r1' AND {WINDOW}",
     "host, b", "host"),
    ("in-tag", f"host, {BY_10M}, ",
     f"host IN ('h03', 'h11', 'h12', 'h39') AND {WINDOW}", "host, b",
     "host"),
    ("field-filter", f"host, {BY_10M}, ", f"usage > 40 AND {WINDOW}",
     "host, b", "host"),
    ("in-tag-and-field-filter", "host, ",
     f"host IN ('h03', 'h11') AND idle <= 70 AND {WINDOW}", "host", "host"),
]


def statement(select, where, group_by):
    sql = f"SELECT {select}{AGGS} FROM cpu"
    if where:
        sql += f" WHERE {where}"
    return sql + (f" GROUP BY {group_by}" if group_by else "")


def total(metric: str, label: str) -> float:
    return sum(value for name, labels, value, _ in registry_snapshot()
               if name == metric and label in labels)


def masks_made() -> tuple:
    return (total("greptime_scan_row_mask_total", 'made="none"'),
            total("greptime_scan_row_mask_total", 'made="host"'))


@pytest.mark.parametrize("case", STATEMENTS, ids=[c[0] for c in STATEMENTS])
def test_a_full_launch_equals_the_host_masked_launch(plans, scan, case,
                                                     monkeypatch):
    _, select, where, group_by, made = case
    plan = plans.of(statement(select, where, group_by))
    assert (plan.time_lo, plan.time_hi) == (
        LO if f">= {LO}" in where else None,
        HI if f"< {HI}" in where else None)
    monkeypatch.setattr(scan_narrow, "_NARROW_MAX_SHARE", 10**12)
    uploads = []
    monkeypatch.setattr(scan_cache.MergedScan, "upload", lambda self, arr:
                        uploads.append(arr.dtype) or np.asarray(arr))
    before = masks_made()
    got = tpu_exec._moment_frame_for_scan(scan, plans.schema, plan)
    assert masks_made() == (before[0] + (made == "none"),
                            before[1] + (made == "host"))
    # a window alone sends nothing of the table's length for the rows
    assert (np.dtype(bool) in uploads) == (made == "host")
    with host_masked(monkeypatch):
        want = tpu_exec._moment_frame_for_scan(scan, plans.schema, plan)
    assert want is not None and want["__rowcount"].sum() > 0
    assert_frames_equal(got, want, plan)


def test_the_window_is_counted_against_the_rows(plans, scan):
    """Not only equal to the parent's: the rows a window keeps."""
    plan = plans.of(f"SELECT host, count(usage) FROM cpu WHERE {WINDOW} "
                    "GROUP BY host")
    got = tpu_exec._moment_frame_for_scan(scan, plans.schema, plan)
    assert list(got["__rowcount"]) == [431 - 95] * HOSTS


# ---------------------------------------------------------------------------
# the edges: the scan's span, the int32 coordinates, a tail's padding
# ---------------------------------------------------------------------------

SPAN = 2**31 - 1        # the last relative time `device_ts` admits


@pytest.fixture(scope="module")
def wide(plans):
    """One series whose rows reach the end of the int32 coordinates."""
    ts = T0 + np.array([0, 5, 1000, SPAN], dtype=np.int64)
    return scan_cache.MergedScan(
        np.zeros(4, dtype=np.int32), ts,
        {"usage": (np.array([1.0, 2.0, 3.0, 4.0]), None),
         "idle": (np.zeros(4), None)},
        series_dict(plans.schema, 1), T0)


#: (id, time_lo, time_hi, launches, rows counted or None: no answer)
EDGES = [
    ("before-the-span", T0 - 500, T0, False, None),
    ("ends-at-the-first-row", None, T0, False, None),
    ("after-the-span", T0 + SPAN + 1, None, False, None),
    ("far-after-the-span", T0 + 2**40, T0 + 2**41, False, None),
    ("a-gap-inside-the-span", T0 + 6, T0 + 1000, True, None),
    ("the-first-row", None, T0 + 1, True, 1),
    ("hi-at-the-last-coordinate", T0, T0 + SPAN, True, 3),
    ("hi-at-2^31", T0, T0 + 2**31, True, 4),
    ("hi-beyond-2^31", T0 + 5, T0 + 2**31 + 10**12, True, 3),
    ("lo-at-the-last-coordinate", T0 + SPAN, None, True, 1),
    ("lo-far-below", T0 - 2**40, T0 + 1001, True, 3),
    ("open", None, None, True, 4),
]


@pytest.mark.parametrize("edge", EDGES, ids=[e[0] for e in EDGES])
def test_the_edges_of_the_span_and_of_the_coordinates(plans, wide, edge,
                                                      monkeypatch):
    _, lo, hi, launches, rows = edge
    where = " AND ".join(
        ([f"ts >= {lo}"] if lo is not None else []) +
        ([f"ts < {hi}"] if hi is not None else []))
    plan = plans.of("SELECT count(usage), max(usage) FROM cpu"
                    + (f" WHERE {where}" if where else ""))
    assert (plan.time_lo, plan.time_hi) == (lo, hi)
    calls = []
    real = scan_launch._run_program
    monkeypatch.setattr(scan_launch, "_run_program", lambda *a, **k:
                        calls.append(1) or real(*a, **k))
    got = tpu_exec._moment_frame_for_scan(wide, plans.schema, plan)
    assert len(calls) == launches
    if rows is None:
        assert got is None
    else:
        assert list(got["__rowcount"]) == [rows]
        kept = wide.ts[(wide.ts >= (lo if lo is not None else -2**62))
                       & (wide.ts < (hi if hi is not None else 2**62))]
        assert len(kept) == rows
    if launches:
        w_lo, w_hi = scan_launch._device_window(plan, wide)
        assert (w_lo.shape, w_lo.dtype, w_hi.shape, w_hi.dtype) == \
            ((), np.int32, (), np.int32)


def test_a_tails_padding_inside_the_window_counts_no_row(plans, scan):
    """A tail's row axis is a capacity: the padding repeats the last
    row's time, which lies inside the window."""
    ticks = 3
    hosts = np.repeat(np.arange(HOSTS, dtype=np.int32), ticks)
    ts = np.tile(T0 + (TICKS + np.arange(ticks, dtype=np.int64)) * TICK_MS,
                 HOSTS)
    k = len(ts)
    tail = scan_cache._make_tail(scan_cache._Rows(
        hosts, ts, np.zeros(k, np.int64),
        {"usage": (np.arange(k, dtype=np.float64), None),
         "idle": (np.ones(k), None)}), scan)
    assert tail.num_rows == scan_cache.tail_capacity(scan.num_rows) > k
    assert tail.ts[-1] == ts[-1] and tail.valid_rows == k
    lo = T0 + (TICKS + 1) * TICK_MS
    plan = plans.of(f"SELECT host, count(usage), max(usage) FROM cpu "
                    f"WHERE ts >= {lo} GROUP BY host")
    before = masks_made()
    part = tpu_exec._moment_frame_for_scan(tail, plans.schema, plan,
                                           tail=True, runs=True)
    assert masks_made() == (before[0] + 1, before[1])
    assert list(part.rowcount) == [ticks - 1] * HOSTS
    top = next(r for m, r in zip(plan.moments, part.moments)
               if m.op == "max")
    assert top[-1] == k - 1


# ---------------------------------------------------------------------------
# the bounds are arguments of the program, never static
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
    rng = np.random.default_rng(43)
    table = fe.catalog.table("greptime", "public", "big")
    k = np.arange(BIG_TICKS)
    for h in range(BIG_HOSTS):
        table.insert({"host": [f"b{h:03d}"] * BIG_TICKS,
                      "ts": (T0 + k * TICK_MS).tolist(),
                      "usage": rng.random(BIG_TICKS).tolist()})
    yield fe, table
    fe.shutdown()


def test_two_windows_of_one_shape_are_one_program_and_a_tails_is_warmed(big):
    fe, table = big
    assert BIG_HOSTS * BIG_TICKS >= tpu_exec.TPU_DISPATCH_MIN_ROWS

    def sql(lo, hi):
        return ("SELECT host, date_bin(INTERVAL '10 minute', ts) AS b, "
                "max(usage) AS m, count(usage) AS c FROM big WHERE "
                f"ts >= {T0 + lo * TICK_MS} AND ts < {T0 + hi * TICK_MS} "
                "GROUP BY host, b")

    def answer(query):
        # the static floor as it is, and the adaptive one forgotten
        fe.do_query("SET tpu_dispatch_min_rows = 131072")
        out = fe.do_query(query)[-1]
        return pd.concat([pd.DataFrame(b.to_pydict()) for b in out.batches],
                         ignore_index=True)

    def reduce_detail(query):
        rows = answer("EXPLAIN ANALYZE " + query)
        return dict(zip(rows.stage, rows.detail))["reduce"]

    # the warm statements, before any write
    for _ in range(2):
        assert answer(sql(60, 400)).c.sum() == BIG_HOSTS * (BIG_TICKS - 60)
    compiled = _sorted_grouped_aggregate_pre._cache_size()
    region = next(iter(table.regions.values()))
    base = scan_cache.SCAN_CACHE.get_parts(region)[0]
    assert len(base.tail_programs) == 1
    before = masks_made()
    for lo, hi in ((0, 120), (61, 400), (137, 139), (300, 10**6)):
        got = answer(sql(lo, hi))
        assert got.c.sum() == BIG_HOSTS * (min(hi, BIG_TICKS) - lo)
    assert "path=full, groups=table, mask=none" in reduce_detail(sql(10, 70))
    assert masks_made() == (before[0] + 5, before[1])
    assert _sorted_grouped_aggregate_pre._cache_size() == compiled
    # a tick of every host: the tail's launch is the executable compiled
    # with the warm statements, whatever the window
    ran = []
    for key, program in list(base.tail_programs.items()):
        base.tail_programs[key] = lambda *a, _p=program: \
            ran.append(1) or _p(*a)
    table.insert({"host": [f"b{h:03d}" for h in range(BIG_HOSTS)],
                  "ts": [T0 + BIG_TICKS * TICK_MS] * BIG_HOSTS,
                  "usage": [2.0] * BIG_HOSTS})
    for i, (lo, hi) in enumerate(((200, 400), (329, 331), (330, 10**6))):
        got = answer(sql(lo, hi))
        assert got.c.sum() == BIG_HOSTS * (BIG_TICKS + 1 - lo)
        assert got.m.max() == 2.0
        assert len(ran) == i + 1
    detail = reduce_detail(sql(100, 400))
    assert "mask=none" in detail and "tail_mask=none" in detail
    assert len(ran) == 4 and len(base.tail_programs) == 1
    assert scan_cache.SCAN_CACHE.get_parts(region)[0] is base
    assert _sorted_grouped_aggregate_pre._cache_size() == compiled
