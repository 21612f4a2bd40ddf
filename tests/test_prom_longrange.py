"""A fleet overview at "Last 24 hours" (ISSUE 34): the node_exporter
deployment of `benchmark/configs/prom-node-1k-2h.json` at its debug size
(20 targets, 2 h), every family of the `prom-longrange` mix through
`do_query("TQL EVAL ...")` against the float64 reference
(`benchmark/benchlib/promref.py` + `promlong.py`). Every statement lowers
onto the plan IR and reduces on the device, counters included: the
window's growth over a derived mirror of per-sample differences
(`MergedScan.device_run_diffs`), where plain f32 mirrors have no digits
left. Also: resets, series that begin or end inside the span, one-sample
and empty windows, the `lower` span rows and the two counters, and the new
reference functions by hand.
"""

import os
import re
import sys

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchlib import check as chk  # noqa: E402
from benchlib import promlong  # noqa: E402
from benchlib import promref as ref  # noqa: E402
from benchlib.loops import family_rng  # noqa: E402
from benchlib.spec import (load_family, load_generator,  # noqa: E402
                           load_json)

from greptimedb_tpu.common import telemetry  # noqa: E402
from greptimedb_tpu.datanode.instance import (  # noqa: E402
    DatanodeInstance, DatanodeOptions)
from greptimedb_tpu.datatypes.record_batch import (  # noqa: E402
    arrow_to_ingest_columns)
from greptimedb_tpu.frontend.instance import FrontendInstance  # noqa: E402
from greptimedb_tpu.query import agg_plan, moment_fold, tpu_exec  # noqa: E402
from greptimedb_tpu.storage import scan_cache  # noqa: E402

SEED = 2147483659
CONFIG = load_json(BENCH, "configs", "prom-node-1k-2h.json")
MIX = load_json(BENCH, "traffic", "prom-longrange.json")
FAMILIES = MIX["families"]
RESIDENT = "device-resident (scan cache)"
T0_NS = re.compile(r"t0_ns=(\d+)$")


def dataset(seed=SEED):
    size = CONFIG["debug"]
    return load_generator(CONFIG)(
        CONFIG, seed, scale=size["scale"],
        ticks=size["duration_s"] // CONFIG["log_interval_s"])


def frontend(data_home: str) -> FrontendInstance:
    fe = FrontendInstance(DatanodeInstance(DatanodeOptions(
        data_home=data_home, register_numbers_table=False)))
    fe.start()
    return fe


def rows_of(out) -> list:
    return [list(r) for b in out.batches for r in b.rows()]


def metric(name: str, **labels) -> float:
    """A counter of this process, as /metrics would print it."""
    counter = telemetry._counters.get(name)
    if counter is None:
        return 0.0
    child = counter.labels(**labels) if labels else counter
    return child._value.get()


class Fleet:
    """One frontend with the debug-size deployment loaded the way the
    harness loads it; the dispatch floor pinned as the debug
    configuration's `before_each_statement` pins it (a table of 14,400
    rows lies under it)."""

    def __init__(self, data_home: str):
        self.ds = dataset()
        self.fe = frontend(data_home)
        self.fe.do_query(self.ds.create_table_sql())
        loaded = 0
        for name, tags, table in self.ds.arrow_chunks(
                CONFIG["debug"]["load_chunk_ticks"]):
            target = self.fe.catalog.table("greptime", "public", name)
            loaded += self.fe.handle_bulk_load(
                name, arrow_to_ingest_columns(table, target.schema,
                                              extra="keep"),
                tag_columns=tags, timestamp_column=self.ds.time_index)
        assert loaded == self.ds.rows

    def query(self, sql: str) -> list:
        self.fe.do_query(CONFIG["debug"]["before_each_statement"])
        return rows_of(self.fe.do_query(sql)[-1])

    def judge(self, fam, params) -> dict:
        got = fam.parse(self.query(fam.sql(params, self.ds)), self.ds)
        return chk.compare(got, fam.reference(params, self.ds),
                           fam.tolerance)

    def stages(self, sql: str) -> dict:
        rows = self.query("EXPLAIN ANALYZE " + sql)
        return {r[0]: (int(r[1]), float(r[3]), r[4] or "") for r in rows}

    def close(self):
        self.fe.do_query("SET tpu_dispatch_min_rows = 131072")
        self.fe.shutdown()


@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    f = Fleet(str(tmp_path_factory.mktemp("longrange")))
    yield f
    f.close()


def drawn(fleet, name, stream="window"):
    fam = load_family(name)
    return fam, fam.draw(family_rng(SEED, name, stream), fleet.ds)


# ---------------------------------------------------------------------------
# the six families against the reference, and where they executed
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", FAMILIES)
def test_family_answers_the_reference(fleet, name):
    fam = load_family(name)
    rng = family_rng(SEED, name, "window")
    for _ in range(3):
        params = fam.draw(rng, fleet.ds)
        res = fleet.judge(fam, params)
        assert res["ok"] and res["rows"] > 0, (params, res)


@pytest.mark.parametrize("name", FAMILIES)
def test_family_lowers_and_reduces_on_the_device(fleet, name):
    """`promql_statements{path}` counts the statement as `lowered`, its
    dispatch is the resident one without a `host-partial` suffix, and a
    resident launch read rows on the device."""
    warm_cpu_table(fleet)
    fam, params = drawn(fleet, name, "explain")
    before = {path: metric("promql_statements", path=path)
              for path in ("lowered", "row")}
    rows_before = metric("scan_device_rows")
    stages = fleet.stages(fam.sql(params, fleet.ds))
    assert stages["dispatch"][2] == fam.dispatch == RESIDENT
    assert "host-partial" not in stages["dispatch"][2]
    assert metric("promql_statements", path="lowered") \
        == before["lowered"] + 1
    assert metric("promql_statements", path="row") == before["row"]
    assert metric("scan_device_rows") > rows_before


def warm_cpu_table(fleet):
    """A statement over every series builds the table's scan-cache entry
    (cold, a point statement answers `indexed-point`); another test
    file's budget may have evicted it from the process's cache."""
    fam, params = drawn(fleet, "long-cpu-util-fleet", "warm")
    fleet.query(fam.sql(params, fleet.ds))


def test_one_nodes_panel_takes_the_narrowed_launch(fleet):
    warm_cpu_table(fleet)
    fam, params = drawn(fleet, "long-cpu-by-mode-1", "explain")
    stages = fleet.stages(fam.sql(params, fleet.ds))
    assert "path=narrow" in stages["reduce"][2], stages["reduce"]
    # 64 series x 101 windows of six samples
    assert "narrow_rows=38784, ranges=64" in stages["reduce"][2]


#: the group axis of each family's full launch (ISSUE 36); None: narrow
GROUP_AXIS = {"long-cpu-util-fleet": "live", "long-cpu-by-mode-1": None,
              "long-net-rx-fleet": "table",
              "long-load-max-by-instance": "table",
              "long-mem-available-fleet": "table",
              "long-fs-avail-min": "table"}


@pytest.mark.parametrize("name", FAMILIES)
def test_the_full_launch_takes_the_live_runs_where_ranges_say_which(fleet,
                                                                    name):
    """`mode="idle"` resolves to an eighth of the series: the fleet panel's
    launch picks up at their runs (16,240 of 155,584 here), counted once a
    launch; a `!=` matcher or none resolves no ranges and keeps the
    table's; either way the family answers its reference from the
    device."""
    warm_cpu_table(fleet)
    fam, params = drawn(fleet, name, "explain")
    before = {axis: metric("scan_group_axis", axis=axis)
              for axis in ("live", "table")}
    rows_before = metric("scan_device_rows")
    laid = metric("scan_selection_layouts")
    stages = fleet.stages(fam.sql(params, fleet.ds))
    detail = stages["reduce"][2]
    # a panel that ends on its step finds its grid laid out over the
    # table: never the selection's layout (`scan_full._selection_layout`)
    assert metric("scan_selection_layouts") == laid
    assert "runs=selection" not in detail
    bumped = {axis: metric("scan_group_axis", axis=axis) - before[axis]
              for axis in ("live", "table")}
    axis = GROUP_AXIS[name]
    if axis is None:
        assert "path=narrow" in detail and "groups=" not in detail
        assert bumped == {"live": 0, "table": 0}
    else:
        assert f"path=full, groups={axis}" in detail, detail
        assert bumped == {"live": int(axis == "live"),
                          "table": int(axis == "table")}
    found = re.search(r"live_runs=(\d+), table_runs=(\d+)", detail)
    if axis == "live":
        assert 0 < int(found[1]) < int(found[2]) // 4
        # the launch still reads every row of the table on the device
        table = fleet.fe.catalog.table("greptime", "public",
                                       "node_cpu_seconds_total")
        scan = scan_cache.SCAN_CACHE.get(next(iter(table.regions.values())))
        assert metric("scan_device_rows") - rows_before == scan.num_rows
    else:
        assert found is None
    assert stages["dispatch"][2] == RESIDENT
    res = fleet.judge(fam, params)
    assert res["ok"] and res["rows"] > 0, res


def test_no_moment_op_is_left_to_the_host_alone():
    assert not hasattr(agg_plan, "HOST_ONLY_MOMENT_OPS")
    plan = agg_plan.TpuPlan([], None, [agg_plan.Moment(
        "increase", "greptime_value", "__m0")], [], None, None, [], [])
    assert not agg_plan.plan_needs_host(plan)


# ---------------------------------------------------------------------------
# counters a month old: resets, series that begin or end, one-sample windows
# ---------------------------------------------------------------------------

def special_targets(ds) -> dict:
    ended = int(np.argmax(np.where(ds.last < ds.ticks, ds.last, -1)))
    began = int(np.argmax(ds.first))       # the last churn event's
    rebooted = int(np.nonzero(ds.reboot_tick >= 0)[0][0])
    oldest = int(np.argmax(np.where(
        (ds.first == 0) & (ds.last == ds.ticks) & (ds.reboot_tick < 0),
        ds.uptime_s, -1.0)))
    return {"ended": ended, "began": began, "rebooted": rebooted,
            "oldest": oldest}


@pytest.mark.parametrize("which", ["ended", "began", "rebooted", "oldest"])
def test_one_nodes_panel_of_a_target_that(fleet, which):
    """A target that churned out or in inside the span (its first window
    holds one sample, which `rate` does not answer; after its last
    window nothing), one whose counters restarted at 0 inside a window,
    and the one that has been up longest: `sum by (mode) (rate(...[1m]))`
    of each at the ends of the mix's range."""
    ds = fleet.ds
    target = special_targets(ds)[which]
    fam = load_family("long-cpu-by-mode-1")
    cpu = ds.samples("node_cpu_seconds_total")
    one = cpu.values[cpu.labels["instance"] == ds.instances[target]]
    if which == "rebooted":
        assert (np.diff(one, axis=1) < 0).any(axis=1).all()
    for end_s in (6060, 6660, 7200):
        params = {"end_s": end_s, "instance": ds.instances[target]}
        res = fleet.judge(fam, params)
        assert res["ok"], (which, end_s, res)
        points = fam.reference(params, ds)
        if which == "oldest":
            assert len(points) == 8 * 101
        if which == "began" and end_s == 7200:
            # the window that ends on the target's first sample holds
            # that sample alone: no point there, points right after
            t_first = ds.ms(int(ds.first[target]))
            stamps = {key[-1] for key in points}
            assert t_first not in stamps and t_first + 60_000 in stamps


def test_a_reset_inside_a_window_counts_the_growth_on_both_sides(fleet):
    """The rebooted target's idle counter falls from days of seconds to
    0 between two scrapes of one window: the window's rate stays a CPU's
    idle share, as the reference has it."""
    ds = fleet.ds
    target = special_targets(ds)["rebooted"]
    tick = int(ds.reboot_tick[target])
    end_s = min(7200, max(6060, (tick * 10 // 60 + 2) * 60))
    got = fleet.query(
        f"TQL EVAL ({end_s - 6000 + ds.t0_ms // 1000}, "
        f"{end_s + ds.t0_ms // 1000}, '60s') sum by (instance) (rate("
        f'node_cpu_seconds_total{{instance="{ds.instances[target]}", '
        'mode="idle"}[1m]))')
    values = np.array([float(r[-1]) for r in got])
    assert len(values) == 101
    # eight CPUs, each idle between 0.1 and 0.98 of a second a second
    assert (values > 0.8).all() and (values < 8.0).all(), values


@pytest.mark.parametrize("name", ["long-cpu-util-fleet",
                                  "long-net-rx-fleet"])
def test_plain_f32_mirrors_of_the_counters_would_not_be_correct(fleet, name):
    """What the host-only moment was there to avoid: the reference over
    float32 of the raw counters misses the family's tolerance, so a
    program that reduced plain f32 mirrors (last - first) would not be
    `correct`; the served path, on f32 differences, is."""
    import copy
    ds = fleet.ds
    fam, params = drawn(fleet, name)
    mirror = copy.copy(ds)
    mirror.data = ds.data.astype(np.float32).astype(np.float64)
    res = chk.compare(fam.reference(params, mirror),
                      fam.reference(params, ds), fam.tolerance)
    _, value, limit = chk.compared_number(res, fam.tolerance)
    assert not res["ok"] and value > 2 * limit, (value, limit)
    assert fleet.judge(fam, params)["ok"]


def test_the_fleet_has_counters_a_month_old(fleet):
    ds = fleet.ds
    assert ds.samples("node_cpu_seconds_total").values.max() > 1e6
    assert ds.samples("node_network_receive_bytes_total").values.max() > 1e12


BYTES = {
    # name: (start, growth a second, restart at sample or None, first
    # sample, one past the last)
    "bytes_2_6e14": (2.6e14, 98_765_432.1, None, 0, 180),
    "bytes_2_6e14_reset": (2.6e14, 98_765_432.1, 100, 0, 180),
    "cpu_2_6e6": (2.6e6, 0.93, None, 0, 180),
    "begins": (1e12, 1000.137, None, 60, 180),
    "ends": (1e12, 1000.137, None, 0, 91),
}


@pytest.fixture(scope="module")
def counters(tmp_path_factory):
    fe = frontend(str(tmp_path_factory.mktemp("bytes")))
    fe.do_query("CREATE TABLE c (name STRING, greptime_timestamp TIMESTAMP "
                "TIME INDEX, greptime_value DOUBLE, PRIMARY KEY(name))")
    values = []
    for name, (start, growth, reset, first, last) in BYTES.items():
        for k in range(first, last):
            v = start + growth * 10 * k if reset is None or k < reset \
                else growth * 10 * (k - reset + 1)
            values.append(f"('{name}', {k * 10_000}, {v!r})")
    fe.do_query("INSERT INTO c VALUES " + ", ".join(values))
    fe.do_query("SET tpu_dispatch_min_rows = 1")
    yield fe
    fe.do_query("SET tpu_dispatch_min_rows = 131072")
    fe.shutdown()


def tql(fe, query: str, start=600, end=1500, step="1m") -> dict:
    rows = rows_of(fe.do_query(
        f"TQL EVAL ({start}, {end}, '{step}') {query}")[-1])
    out = {}
    for r in rows:
        out.setdefault(r[0], {})[int(r[-2])] = float(r[-1])
    return out


@pytest.mark.parametrize("func, scale", [("rate", 1.0), ("increase", 60.0),
                                         ("delta", 60.0)])
@pytest.mark.parametrize("name", ["bytes_2_6e14", "cpu_2_6e6"])
def test_a_month_old_counter_keeps_its_digits_on_the_device(counters, name,
                                                            func, scale):
    """A byte counter at 2.6e14 growing 98.8 MB/s (f32 as it is steps by
    3.4e7 there, a third of a second's growth) and a CPU's seconds after
    30 days: lowered, reduced on the device, exact to 1e-6."""
    growth = BYTES[name][1]
    got = tql(counters, f'sum by (name) ({func}(c{{name="{name}"}}[1m]))')
    values = np.array(list(got[name].values()))
    assert len(values) == 16
    assert np.abs(values / (growth * scale) - 1).max() < 1e-6, values


def test_a_counter_at_2_6e14_that_restarts_inside_a_window(counters):
    """Sample 100 (1,000 s) restarts at one scrape's growth: the window
    (960 s, 1020 s] holds the reset; `increase` adds the value before
    it, `delta` does not."""
    growth = BYTES["bytes_2_6e14_reset"][1]
    rate = tql(counters, 'sum by (name) (rate(c{name="bytes_2_6e14_reset"}'
               '[1m]))')["bytes_2_6e14_reset"]
    assert len(rate) == 16
    for t, v in rate.items():
        # right after the restart the counter's zero point caps the
        # extrapolation: the window that holds it grows 5 scrapes + the
        # restarted value, extrapolated over less than a whole interval
        if t == 1_020_000:
            assert 0.9 * growth < v <= growth * (1 + 1e-6), (t, v)
        else:
            assert abs(v / growth - 1) < 1e-6, (t, v)
    delta = tql(counters, 'sum by (name) (delta(c{name="bytes_2_6e14_reset"}'
                '[1m]))')["bytes_2_6e14_reset"]
    assert delta[1_020_000] < -2e14


@pytest.mark.parametrize("name, stamps", [
    # samples 60..179: the window (540, 600] holds sample 60 alone
    ("begins", list(range(660_000, 1_500_001, 60_000))),
    # samples 0..90: the window (900, 960] is empty, (840, 900] is whole
    ("ends", list(range(600_000, 900_001, 60_000))),
])
def test_one_sample_and_empty_windows_answer_nothing(counters, name, stamps):
    got = tql(counters, f'sum by (name) (rate(c{{name="{name}"}}[1m]))')
    assert sorted(got[name]) == stamps
    growth = BYTES[name][1]
    assert np.abs(np.array(list(got[name].values())) / growth - 1).max() \
        < 1e-6
    # max_over_time needs one sample only
    top = tql(counters, f'max by (name) (max_over_time(c{{name="{name}"}}'
              '[1m]))')
    want = stamps if name == "ends" else [600_000] + stamps
    assert sorted(top[name]) == want


def test_the_instant_selector_carries_an_ended_series_for_the_lookback(
        counters):
    """`ends` has its last sample at 900 s: `sum(c)` sees it until the
    5 m lookback runs out (the program's far edge is closed)."""
    got = tql(counters, 'sum by (name) (c{name="ends"})', start=840,
              end=1500)["ends"]
    assert sorted(got) == list(range(840_000, 1_200_001, 60_000))
    last = BYTES["ends"][0] + BYTES["ends"][1] * 10 * 90
    assert got[1_200_000] == pytest.approx(last, rel=1e-6)


# ---------------------------------------------------------------------------
# the rebuild: series codes, not rendered strings per row
# ---------------------------------------------------------------------------

def test_series_codes_number_the_series_in_label_order():
    import pandas as pd
    from greptimedb_tpu.promql.lowering import _series_codes
    from greptimedb_tpu.query.planner import _group_slot
    df = pd.DataFrame({
        _group_slot("host"): np.array(["b", "a", "b", None, "a", ""],
                                      dtype=object),
        _group_slot("dc"): np.array(["x", "y", "x", "x", "z", "x"],
                                    dtype=object)})
    sids, uniq = _series_codes(df, ["host", "dc"])
    # a NULL label renders as "": one series with the empty one
    assert uniq == [("", "x"), ("a", "y"), ("a", "z"), ("b", "x")]
    assert sids.tolist() == [3, 1, 3, 0, 2, 0]
    sids, uniq = _series_codes(df, [])
    assert uniq == [()] and sids.tolist() == [0] * 6


def test_the_rebuild_calls_python_once_a_series_not_once_a_row(
        fleet, monkeypatch):
    from greptimedb_tpu.promql import lowering
    calls = []
    real = lowering._key_str
    monkeypatch.setattr(lowering, "_key_str",
                        lambda v: calls.append(1) or real(v))
    fam, params = drawn(fleet, "long-cpu-util-fleet")
    rows = fleet.query(fam.sql(params, fleet.ds))
    assert len(rows) == 101
    idle = 8 * len(fleet.ds.instances)          # series selected
    # four tags a series; the frame has 101 rows a series and more
    assert 0 < len(calls) <= 4 * idle


# ---------------------------------------------------------------------------
# spans and counters of the lowered path
# ---------------------------------------------------------------------------

TOP_LEVEL = ["plan", "scan_prep", "reduce", "finalize", "lower", "outer"]
PARTS = ["reduce.runs", "reduce.mask", "reduce.upload", "reduce.launch",
         "reduce.fetch", "reduce.collect", "finalize.partial_bytes",
         "lower.rebuild"]


def interval(stages: dict, name: str):
    found = T0_NS.search(stages[name][2])
    assert found, f"row {name!r} has no t0_ns: {stages[name][2]!r}"
    start = int(found.group(1))
    return start, start + int(stages[name][1] * 1e6)


@pytest.mark.parametrize("name", FAMILIES)
def test_span_rows_add_up_to_total(fleet, name):
    """The rows SQL's aggregate writes, then `lower` with its part, each
    with its wall-clock start; the rows directly under `total` do not
    overlap and add up to it but for what no span covers."""
    warm_cpu_table(fleet)
    fam, params = drawn(fleet, name)
    sql = fam.sql(params, fleet.ds)
    fleet.query(sql)                                  # warm

    def untimed_of(stages):
        return stages["total"][1] - sum(stages[row][1] for row in TOP_LEVEL)

    def cpu_ms(stages, row):
        # `outer` sums its pieces without a clock of the thread's: all
        # of its time counts as spent
        found = re.search(r"cpu_ms=([0-9.]+)", stages[row][2])
        return float(found.group(1)) if found else stages[row][1]

    def steady(stages):
        """What no span covers, on the wall clock; beside five other
        xdist workers a thread is taken off its processor between two
        rows for longer than the limit, so a try that fails it is held
        to the thread's CPU time instead (every row under `total`
        carries `cpu_ms=`), which a descheduled thread does not spend."""
        total = stages["total"][1]
        if -0.05 <= untimed_of(stages) < max(2.0, 0.05 * total):
            return True
        spent = cpu_ms(stages, "total") - sum(cpu_ms(stages, row)
                                              for row in TOP_LEVEL)
        return untimed_of(stages) >= -0.05 and \
            spent < max(2.0, 0.05 * cpu_ms(stages, "total"))

    tries = []
    for _ in range(8):      # the first steady one of up to eight tries
        tries.append(fleet.stages(sql))
        if steady(tries[-1]):
            break
    stages = tries[-1]
    for row in ["parse"] + TOP_LEVEL + PARTS:
        interval(stages, row)
    assert steady(stages), (untimed_of(stages), stages)
    assert stages["lower.rebuild"][1] <= stages["lower"][1] + 0.05
    assert re.search(r"path=(full|narrow)", stages["reduce"][2])
    # `lower` starts when `finalize` has ended, inside `total`
    assert interval(stages, "lower")[0] >= interval(stages, "finalize")[1] \
        - 50_000
    assert stages["lower"][0] > 0                     # (series, window) rows


def test_lowered_counters_count_windows_and_device_rows(fleet):
    fam, params = drawn(fleet, "long-load-max-by-instance")
    sql = fam.sql(params, fleet.ds)
    fleet.query(sql)
    windows = metric("promql_lowered_windows")
    rows = metric("scan_device_rows")
    reads = metric("scan_reads", path="full")
    answer = fleet.query(sql)
    # one series an instance, a row of the frame a point of the answer
    assert metric("promql_lowered_windows") - windows == len(answer)
    # one increment a launch: the whole table's rows, read on the device
    table = len(fleet.ds.instances) and int(
        (fleet.ds.last - fleet.ds.first).sum())
    assert metric("scan_device_rows") - rows == table
    assert metric("scan_reads", path="full") - reads == 1


def test_a_narrowed_launch_counts_the_rows_of_its_ranges(fleet):
    warm_cpu_table(fleet)
    fam, params = drawn(fleet, "long-cpu-by-mode-1")
    sql = fam.sql(params, fleet.ds)
    fleet.query(sql)
    rows = metric("scan_device_rows")
    fleet.query(sql)
    assert metric("scan_device_rows") - rows == 38784


# ---------------------------------------------------------------------------
# the derived mirror
# ---------------------------------------------------------------------------

def test_run_diffs_are_made_in_float64_and_only_when_asked_for(counters):
    table = counters.catalog.table("greptime", "public", "c")
    region = next(iter(table.regions.values()))
    scan = scan_cache.SCAN_CACHE.get(region)
    tql(counters, 'max by (name) (max_over_time(c[1m]))')
    built = {k for k in scan.device if k[:2] in ("c:", "g:")}
    tql(counters, 'sum by (name) (rate(c{name="bytes_2_6e14_reset"}[1m]))')
    assert {k for k in scan_cache.SCAN_CACHE.get(region).device
            if k[:2] in ("c:", "g:")} - built == {"c:greptime_value"}
    scan = scan_cache.SCAN_CACHE.get(region)
    d = np.asarray(scan.device_run_diffs("greptime_value", True))
    g = np.asarray(scan.device_run_diffs("greptime_value", False))
    assert d.dtype == np.float32
    vals = scan.fields["greptime_value"][0]
    sids = scan.series_ids
    same = sids[1:] == sids[:-1]
    plain = np.where(same, vals[1:] - vals[:-1], 0.0)
    assert np.array_equal(g[1:], plain.astype(np.float32))
    assert d[0] == 0.0 and (d >= 0).all()             # a counter's never fall
    fell = np.nonzero(same & (vals[1:] < vals[:-1]))[0] + 1
    assert len(fell) == 1 and d[fell[0]] == np.float32(vals[fell[0]])


def test_growth_folds_across_partials_of_one_window():
    """Time-disjoint partials of one (series, window): their growths add,
    plus the difference across the boundary, reset-aware for a counter."""
    import pandas as pd
    plan = agg_plan.TpuPlan(
        [agg_plan.TagGroup("name", 0)], None,
        [agg_plan.Moment("first", "v", "f"), agg_plan.Moment("last", "v", "l"),
         agg_plan.Moment("min_ts", "v", "t0"),
         agg_plan.Moment("max_ts", "v", "t1"),
         agg_plan.Moment("increase", "v", "inc"),
         agg_plan.Moment("delta", "v", "dlt")],
        [("inc", "moment", ["inc"]), ("dlt", "moment", ["dlt"])],
        None, None, [], [])
    from greptimedb_tpu.query.planner import _group_slot
    key = _group_slot("name")
    # a: 10 -> 30 | 35 -> 50 (grows across the boundary by 5)
    # b: 10 -> 30 | 2 -> 9 (restarted across the boundary)
    df = pd.DataFrame({
        key: ["a", "b", "a", "b"],
        "f": [35.0, 2.0, 10.0, 10.0], "l": [50.0, 9.0, 30.0, 30.0],
        "t0": [40, 40, 0, 0], "t1": [70, 70, 30, 30],
        "inc": [15.0, 7.0, 20.0, 20.0], "dlt": [15.0, 7.0, 20.0, 20.0],
        "__rowcount": [4, 4, 4, 4]})
    out = moment_fold._finalize(df, plan).set_index(key)
    assert out.loc["a", "inc"] == 40.0 and out.loc["a", "dlt"] == 40.0
    assert out.loc["b", "inc"] == 29.0 and out.loc["b", "dlt"] == -1.0


# ---------------------------------------------------------------------------
# the new reference functions, by hand
# ---------------------------------------------------------------------------

class Hand:
    """Two series on a 10 s grid: a zigzag, and one that exists for
    samples 10..19 only."""

    def __init__(self):
        self.times = np.arange(60, dtype=np.int64) * 10_000
        a = np.where(np.arange(60) % 2 == 0, 1.0, -1.0) * np.arange(60)
        self.values = np.stack([a, 5.0 * np.arange(60)])
        self.first = np.array([0, 10])
        self.last = np.array([60, 20])
        self.labels = {"host": np.array(["a", "b"], dtype=object)}


@pytest.mark.parametrize("op, want_a, want_b", [
    # (140, 200]: samples 15..20 of a (-15, 16, -17, 18, -19, 20), 15..19
    # of b (75..95); (190, 250]: 20..25 of a, none of b (its last is 19)
    ("max", [20.0, 24.0], [95.0, None]),
    ("min", [-19.0, -25.0], [75.0, None]),
])
def test_reference_over_time_reads_the_left_open_window(op, want_a, want_b):
    s, all_ = Hand(), np.ones(2, dtype=bool)
    steps = np.array([200_000, 250_000], dtype=np.int64)
    v, ok = promlong.over_time(op, s, all_, steps, 60_000)
    assert v[0].tolist() == want_a and ok[0].all()
    assert v[1, 0] == want_b[0] and ok[1].tolist() == [True, False]
    # one sample is enough: (180, 190] holds b's last
    v, ok = promlong.over_time(op, s, all_, np.array([190_000]), 10_000)
    assert ok[1, 0] and v[1, 0] == 95.0


@pytest.mark.parametrize("op, want", [
    ("sum", [[3.0, 11.0], [3.0, np.nan]]),
    ("avg", [[1.5, 5.5], [3.0, np.nan]]),
    ("min", [[1.0, 5.0], [3.0, np.nan]]),
    ("max", [[2.0, 6.0], [3.0, np.nan]]),
])
def test_reference_aggregate_by_a_label_and_by_none(op, want):
    values = np.array([[1.0, 5.0], [3.0, 4.0], [2.0, 6.0]])
    ok = np.array([[True, True], [True, False], [True, True]])
    by, out, present = promlong.aggregate(
        op, values, ok, [np.array(["x", "y", "x"], dtype=object)])
    assert by[0].tolist() == ["x", "y"]
    assert np.array_equal(out, np.array(want), equal_nan=True)
    assert present.tolist() == [[True, True], [True, False]]
    by, out, present = promlong.aggregate(op, values, ok, [])
    whole = {"sum": [6.0, 11.0], "avg": [2.0, 5.5], "min": [1.0, 5.0],
             "max": [3.0, 6.0]}[op]
    assert by == [] and out.tolist() == [whole] and present.all()


def test_reference_aggregates_agree_with_promref_where_both_exist():
    rng = np.random.default_rng(7)
    values = rng.normal(size=(12, 5))
    ok = rng.random((12, 5)) > 0.3
    by = [np.array(list("abcabcabcabc"), dtype=object)]
    for op in ("sum", "avg"):
        _, mine, present = promlong.aggregate(op, values, ok, by)
        _, theirs, there = ref.aggregate(op, values, ok, by)
        assert np.array_equal(present, there)
        assert np.allclose(mine, theirs, equal_nan=True, rtol=1e-15)


@pytest.mark.parametrize("name", FAMILIES)
def test_family_files_state_what_the_cell_is_held_to(name):
    fam = load_family(name)
    assert fam.dispatch == RESIDENT and fam.via == "http"
    assert fam.range_ms == CONFIG["query"]["step_s"] * 1000
    assert not getattr(fam, "full_scan_fields", None)
    text = open(os.path.join(BENCH, "families", name + ".py")).read()
    assert "Tolerance" in text and "bf16" in text
    if name in ("long-cpu-util-fleet", "long-net-rx-fleet"):
        assert "float32 of" in text and "raw counters" in text


def test_the_configuration_states_its_scale_and_cuts():
    assert CONFIG["duration_s"] == 7200 and CONFIG["scale"] == 1000
    assert CONFIG["query"] == {"span_s": 6000, "step_s": 60,
                               "end_from_s": 6060, "lookback_s": 300}
    assert sorted(CONFIG["reduced"]) == ["duration_s", "metrics",
                                         "panel_range"]
    assert CONFIG["server_options"] == []
    assert len(CONFIG["source"]) <= 200
    ds = dataset()
    # eleven churn events of one target each at the debug size
    assert len(ds.instances) == 20 + 11
    assert ds.rows == 20 * 77 * 720
    fam = load_family("long-cpu-util-fleet")
    ends = {fam.draw(family_rng(s, fam.name, "window"), ds)["end_s"]
            for s in range(200)}
    assert min(ends) == 6060 and max(ends) == 7200
    assert all(e % 60 == 0 for e in ends)


# ---------------------------------------------------------------------------
# the cell's entries and its three new readers, on hand-made records
# ---------------------------------------------------------------------------

from benchlib.spec import Cell, load_layer_reader  # noqa: E402

CELL = "prom1k-longrange"
T0 = 1_790_000_000_000_000_000
ROWS_COUNTER = "greptime_scan_device_rows_total"


def _stage(ms, start_ms, detail=""):
    lead = detail + ", " if detail else ""
    return {"rows": 0, "elapsed_ms": float(ms),
            "detail": f"{lead}t0_ns={T0 + int(start_ms * 1e6)}"}


def _statement(family, scale, spans=True):
    """A traced lowered statement: a 100 ms `total` with plan 1, reduce
    40, finalize 30, lower 20 (rebuild 15), outer 8; times `scale`d.
    Without `spans`: the parent's rows, which have no `lower`."""
    def s(ms, start, detail=""):
        return _stage(ms * scale, start * scale, detail)
    rec = {"family": family, "in_window": True, "ok": True,
           "client_ms": 130.0 * scale, "t_send_ns": T0,
           "t_done_ns": T0 + int(130e6 * scale)}
    rec["stages"] = {
        "parse": s(1, 0), "plan": s(1, 2),
        "dispatch": {"rows": 0, "elapsed_ms": 0.0, "detail": RESIDENT},
        "reduce": s(40, 3, "path=full"), "finalize": s(30, 43),
        "outer": s(8, 93),
        "total": _stage(100 * scale, 2 * scale, "trace_id=ab"),
        "render": s(25, 102)}
    if spans:
        rec["stages"]["lower"] = s(20, 73)
        rec["stages"]["lower.rebuild"] = s(15, 74)
    return rec


class _FakeTrace:
    """10 ms of device time inside every statement."""
    planes = {"/device:TPU:0": []}

    @staticmethod
    def busy_ns_between(lo, hi):
        return 10e6


def _traced_run(spans=True, counters=True, trace=True):
    run = {"statements": [_statement("a", 1, spans),
                          _statement("b", 3, spans)],
           "device": {"device_kind": "TPU v5 lite"},
           "counters": {"before": {}, "after": {}}}
    if counters:
        run["counters"] = {"before": {ROWS_COUNTER: 1e6},
                           "after": {ROWS_COUNTER: 1e6 + 819e3}}
    if trace:
        run["trace"] = _FakeTrace()
    return run


NEW_READERS = {
    # mean over families of family means: family a at scale 1, b at 3
    "prom_lower_ms": 2 * 20,
    "prom_lower_rebuild_ms": 2 * 15,
    # 819e3 rows x 8 B over 2 x 10 ms of device time, of 819 GB/s
    "lowered_scan_roofline": 100 * 819e3 * 8 / 0.02 / 819e9,
}


@pytest.mark.parametrize("name", sorted(NEW_READERS))
def test_reader_reads_its_rows(name):
    assert load_layer_reader(name)(_traced_run()) == pytest.approx(
        NEW_READERS[name])


@pytest.mark.parametrize("name", sorted(NEW_READERS))
def test_reader_on_the_parent_program_reads_nothing(name):
    """The parent has no `lower` row and no such counter: nothing to
    read, and nothing raised."""
    assert load_layer_reader(name)(
        _traced_run(spans=False, counters=False)) is None


def test_untimed_counts_the_lower_row_among_the_spans():
    # 100 - plan 1 - reduce 40 - finalize 30 - lower 20 - outer 8
    assert load_layer_reader("untimed_ms")(_traced_run()) \
        == pytest.approx(2 * 1.0)


def test_roofline_needs_a_device_plane_and_the_counter():
    read = load_layer_reader("lowered_scan_roofline")
    assert read(_traced_run(trace=False)) is None
    assert read(_traced_run(counters=False)) is None
    run = _traced_run()
    run.pop("counters")
    assert read(run) is None


def test_floor_bytes_is_a_floor():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "lowered_scan_roofline",
        os.path.join(BENCH, "layers", "lowered_scan_roofline.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    # a timestamp and one value column a row; a counter launch reads two
    assert module.floor_bytes(1e6) == 8e6
    assert module.floor_bytes(1e6, 2) == 12e6


def test_the_cell_reports_what_its_entries_say():
    import json
    cell = Cell(CELL)
    assert cell.chips == 1 and cell.mix["loop"] == "statements"
    assert cell.entry["config"] == "prom-node-1k-2h"
    assert cell.entry["traffic"] == "prom-longrange"
    assert [m["name"] for m in cell.metrics("end_to_end")] == [
        "stmt_geomean_ms", "stmt_per_s", "setup_s"]
    layers = {m["name"]: m for m in cell.metrics("per_layer")}
    assert set(NEW_READERS) <= set(layers)
    assert {"warm_compile_s", "cache_build_s", "bulk_load_rows_per_s",
            "wire_ms", "render_ms", "parse_ms", "plan_ms", "untimed_ms",
            "mask_ms", "fetch_ms", "collect_ms", "reduce_host_ms",
            "finalize_ms", "kernel_ms", "device_outside_reduce_ms",
            "prom_outer_ms"} <= set(layers)
    assert "scan_kernels_roofline" not in layers
    for name in NEW_READERS:
        # and the cell that sends two of the panels while the agent
        # writes (ISSUE 44)
        assert layers[name]["workloads"] == [
            CELL, "prom1k-remote-write-while-read"]
        assert layers[name]["moves"] == "stmt_geomean_ms"
    assert layers["lowered_scan_roofline"]["unit"] == "%"
    assert layers["lowered_scan_roofline"]["layer"] == "scan kernels"
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        spec = json.load(f)
    (entry,) = [c for c in spec["configs"] if c["name"] == "prom-node-1k-2h"]
    assert entry["source"] == cell.config["source"]
    assert sorted(entry["reduced"]) == sorted(cell.config["reduced"])
    # appended where PR 34 found the lists' ends; later PRs append after
    configs = [c["name"] for c in spec["configs"]]
    cells = [w["name"] for w in spec["workloads"]]
    assert configs.index("prom-node-1k-2h") == \
        configs.index("prom-node-1k") + 1
    assert cells.index(CELL) == cells.index("prom1k-dashboard") + 1


def test_the_mix_sends_six_families_of_one_shape_each():
    ds = dataset()
    assert MIX["families"] == [
        "long-cpu-util-fleet", "long-cpu-by-mode-1", "long-net-rx-fleet",
        "long-load-max-by-instance", "long-mem-available-fleet",
        "long-fs-avail-min"]
    assert MIX["warm_statements"] == 3 and MIX["max_statements"] == 4000
    assert "reports" not in MIX and "sum by (mode)" in MIX["about"]
    for name in MIX["families"]:
        fam = load_family(name)
        rng = family_rng(SEED, name, "window")
        texts = {re.sub(r"\d{10}", "T", re.sub(r'host_\d+', "H", fam.sql(
            fam.draw(rng, ds), ds))) for _ in range(8)}
        assert len(texts) == 1, texts
        assert "'60s'" in next(iter(texts))


# ---------------------------------------------------------------------------
# the kernel op: a segment's growth
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("groups, longest, share_valid", [
    (300, 7, 1.0),          # low cardinality, every row valid
    (300, 40, 0.6),         # NULLs and filtered rows inside the segments
    (9000, 6, 0.8),         # above the high-cardinality threshold
    (5, 3000, 0.5),         # long segments: 12 doubling passes
])
def test_growth_sums_a_segments_valid_rows_but_the_first(groups, longest,
                                                         share_valid):
    from greptimedb_tpu.ops.kernels import (seg_len_bucket, shape_bucket,
                                            sorted_grouped_aggregate)
    rng = np.random.default_rng(groups)
    lens = rng.integers(0, longest + 1, groups)
    lens[0] = longest
    n = int(lens.sum())
    gids = np.repeat(np.arange(groups, dtype=np.int32), lens)
    x = rng.normal(size=n).astype(np.float32)
    mask = rng.random(n) < 0.9 if share_valid < 1 else np.ones(n, bool)
    valid = rng.random(n) < share_valid / 0.9 if share_valid < 1 \
        else np.ones(n, bool)
    nb = shape_bucket(groups, minimum=256)
    ends = np.full(nb, n, dtype=np.int32)
    ends[:groups] = np.cumsum(lens)
    ts = np.arange(n, dtype=np.int32)
    (got,), counts = sorted_grouped_aggregate(
        gids, mask, ts, (x,), (valid,), num_groups=nb, ops=("growth",),
        has_col_masks=True, ends=ends, seg_len_k=seg_len_bucket(longest))
    want = np.zeros(nb)
    start = 0
    for g, ln in enumerate(lens):
        keep = np.nonzero((mask & valid)[start:start + ln])[0]
        want[g] = x[start + keep[1:]].astype(np.float64).sum()
        start += ln
    assert np.allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    assert np.asarray(counts)[:groups].tolist() == [
        int(mask[a:b].sum()) for a, b in zip(np.cumsum(lens) - lens,
                                             np.cumsum(lens))]


def test_growth_needs_run_ids():
    from greptimedb_tpu.ops.kernels import sorted_grouped_aggregate
    n = 64
    with pytest.raises(ValueError, match="growth needs run ids"):
        sorted_grouped_aggregate(
            np.zeros(n, np.int32), np.ones(n, bool),
            np.arange(n, dtype=np.int32), (np.ones(n, np.float32),),
            (np.ones(n, bool),), num_groups=256, ops=("growth",),
            has_col_masks=True, ends=np.full(256, n, np.int32))


# ---------------------------------------------------------------------------
# sizing a partial frame: a column at a time
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("labels", [
    ["host_1:9100", "host_22:9100", "idle"],
    ["a", None, "", "été"],          # a missing label counts 8 B
])
def test_partial_frames_are_sized_as_the_row_by_row_count(labels):
    import pandas as pd
    df = pd.DataFrame({"tag": np.array(labels, dtype=object),
                       "v": np.arange(len(labels), dtype=np.float32),
                       "sketch": pd.Series([b"abc"] * len(labels),
                                           dtype=object)})
    assert isinstance(df["tag"].dtype, pd.StringDtype)
    by_row = sum(len(v) if isinstance(v, str) else 8 for v in labels) \
        + 4 * len(labels) + 3 * len(labels)
    assert moment_fold.frames_nbytes([df, df]) == 2 * by_row


# ---------------------------------------------------------------------------
# the full launch's row mask from the ranges the predicates resolved to
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("matchers, lo_s, hi_s", [
    ('mode="idle"', 1200, 7200),
    ('mode="idle", cpu!="3"', 3000, 3600),
    ('instance="host_3:9100"', 0, 7200),
    ('mode="nope"', 1200, 7200),
])
def test_the_row_mask_from_ranges_is_the_mask_over_every_row(fleet, matchers,
                                                             lo_s, hi_s):
    from greptimedb_tpu.query import (agg_plan, scan_full, scan_launch,
                                      scan_narrow)
    from greptimedb_tpu.storage import scan_cache
    from greptimedb_tpu.sql.ast import BinaryOp, Column, Literal
    warm_cpu_table(fleet)
    table = fleet.fe.catalog.table("greptime", "public",
                                   "node_cpu_seconds_total")
    scan = scan_cache.SCAN_CACHE.get(next(iter(table.regions.values())))
    preds = []
    for m in matchers.split(", "):
        name, op, value = re.match(r'(\w+)(!?=)"(.*)"', m).groups()
        preds.append(BinaryOp(op, Column(name), Literal(value)))
    t0 = fleet.ds.t0_ms
    plan = agg_plan.TpuPlan([], None, [], [], t0 + lo_s * 1000,
                            t0 + hi_s * 1000, preds, [])
    sel = scan_narrow.select(scan, table.schema, plan)
    assert sel is not None
    by_rows = scan_full._scan_row_mask(scan, table.schema, plan)
    by_ranges = scan_full._scan_row_mask(scan, table.schema, plan, sel)
    if by_rows is scan_full._NO_ROWS:
        assert by_ranges is scan_full._NO_ROWS
        return
    # the rows a launch keeps: the host's mask under the program's window.
    # The ranges hold the window already; the mask over every row leaves
    # it to the program
    lo, hi = scan_launch._device_window(plan, scan)
    rel = scan.ts - scan.ts_base
    window = (rel >= lo) & (rel <= hi)
    assert by_ranges.sum() > 0 and not (by_ranges & ~window).any()
    assert np.array_equal(by_rows & window, by_ranges)
