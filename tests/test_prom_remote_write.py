"""The fleet remote-writes while its dashboards and rules read (ISSUE 44):
the node_exporter deployment of
`benchmark/configs/prom-node-1k-remote-write.json` at its debug size (20
targets, 2 h loaded over the bulk path), the scrapes after the load posted
as remote-write blocks through the HTTP handler between statements, and
every family of the `remote-write-while-read` mix against the float64
reference after every block. No PromQL reader merges a written table: the
row path cuts its selection from the scan cache's base and tail
(`promql/lowering.py:_matrix_from_runs`), a lowered window's growth is two
launches and a seam made in float64 on the host
(`MergedScan.device_run_diffs`, `moment_fold._fold_runs`). Also: a counter
at 2.6e14 and a reset exactly at the seam, a series that exists only in
the tail, a late row under a growth plan (the counted fallback), the
narrowed launch over a tail, the new span rows, timers and counters, and
the read-back after a restart.
"""

import os
import re
import sys
import urllib.request

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchlib import check as chk  # noqa: E402
from benchlib import promlive, promlong  # noqa: E402
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
from greptimedb_tpu.query import scan_full, scan_launch  # noqa: E402
from greptimedb_tpu.storage import scan_cache  # noqa: E402
from greptimedb_tpu.servers import prometheus as prom  # noqa: E402
from greptimedb_tpu.servers.http import HttpServer  # noqa: E402

SEED = 2147483693
CONFIG = load_json(BENCH, "configs", "prom-node-1k-remote-write.json")
MIX = load_json(BENCH, "traffic", "remote-write-while-read.json")
FAMILIES = MIX["families"]
ROUNDS = 8              # live scrape rounds made: 80 blocks
STEPS, STEP = 24, 3     # blocks posted three at a time, then every family
BLOCKS = STEPS * STEP   # 72 s of the schedule: past the first live minute
T0_NS = re.compile(r"cpu_ms=[0-9.]+, t0_ns=(\d+)$")


def dataset(seed=SEED, extra=ROUNDS):
    size = CONFIG["debug"]
    return load_generator(CONFIG)(
        CONFIG, seed, extra_ticks=extra, scale=size["scale"],
        ticks=size["duration_s"] // CONFIG["log_interval_s"])


def frontend(data_home: str) -> FrontendInstance:
    fe = FrontendInstance(DatanodeInstance(DatanodeOptions(
        data_home=data_home, register_numbers_table=False)))
    fe.start()
    return fe


def rows_of(out) -> list:
    return [list(r) for b in out.batches for r in b.rows()]


def metric(name: str, **labels) -> float:
    counter = telemetry._counters.get(name)
    if counter is None:
        return 0.0
    child = counter.labels(**labels) if labels else counter
    return child._value.get()


def post(port: int, body: bytes) -> None:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/prometheus/write", data=body,
        method="POST")
    with urllib.request.urlopen(req, timeout=120) as r:
        assert r.status == 204


class Fleet:
    """One frontend and its HTTP server with the debug-size deployment
    loaded the way the harness loads it, the live rounds encoded, and the
    dispatch floor pinned as the debug configuration pins it."""

    def __init__(self, data_home: str):
        self.data_home = data_home
        self.ds = dataset()
        self.blocks = self.ds.blocks()
        self.fe = frontend(data_home)
        self.http = HttpServer(self.fe, addr="127.0.0.1:0")
        self.http.start()
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
        self.posted = 0

    def post_next(self) -> int:
        post(self.http.port, self.blocks[self.posted][0])
        self.posted += 1
        return self.posted

    def frontier_s(self) -> int:
        return (self.ds.block_start_ms(self.posted) - self.ds.t0_ms) \
            // 1000 - 1

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

    def region(self, table: str):
        t = self.fe.catalog.table("greptime", "public", table)
        return next(iter(t.regions.values()))

    def close(self):
        self.fe.do_query("SET tpu_dispatch_min_rows = 131072")
        self.http.shutdown()
        self.fe.shutdown()


@pytest.fixture(scope="module")
def written(tmp_path_factory):
    """The fleet, three warm statements a family before any write, then
    `BLOCKS` blocks posted `STEP` at a time with one statement of every
    family after each step, its range ending at the frontier. -> (fleet,
    {family: [comparison a step]}, the counters' movement)."""
    f = Fleet(str(tmp_path_factory.mktemp("remote_write")))
    fams = [load_family(n) for n in FAMILIES]
    rngs = {fam.name: family_rng(SEED, fam.name, "window") for fam in fams}
    for fam in fams:
        for _ in range(3):
            assert f.judge(fam, fam.draw(rngs[fam.name], f.ds))["ok"]
    def counted() -> dict:
        return {"merges": metric("scan_cache_merges"),
                "seams": metric("scan_seam_pairs"),
                "with_tail": metric("promql_select_parts", tail="yes"),
                "incremental": metric("scan_cache_incremental")}

    before = counted()
    results = {fam.name: [] for fam in fams}
    for _ in range(STEPS):
        for _ in range(STEP):
            f.post_next()
        for fam in fams:
            params = dict(fam.draw(rngs[fam.name], f.ds),
                          **fam.frontier(f.frontier_s(), f.ds))
            results[fam.name].append((params, f.judge(fam, params)))
    moved = {k: v - before[k] for k, v in counted().items()}
    yield f, results, moved
    f.close()


# ---------------------------------------------------------------------------
# the six families after every block, and no merge
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", FAMILIES)
def test_family_answers_the_reference_after_every_block(written, name):
    _fleet, results, _moved = written
    assert len(results[name]) == STEPS
    for params, res in results[name]:
        assert res["ok"] and res["rows"] > 0, (params, res)
    # every panel ends at the frontier, the lowered ones too: any second
    # of their one-minute step, so from the first block on their newest
    # window lies across the seam between the load and the written rows
    ends = [p["end_s"] for p, _ in results[name]]
    assert ends == sorted(ends) and ends[0] > 7200
    assert len({e % 60 for e in ends}) > STEPS // 2


def test_no_statement_merged_a_written_table(written):
    _fleet, _results, moved = written
    assert moved["merges"] == 0
    assert moved["incremental"] > 0         # the tables were refreshed
    assert moved["with_tail"] > 0           # the row path read a tail
    assert moved["seams"] > 0               # a growth crossed the seam


def test_both_tables_of_the_matching_family_hold_a_tail(written):
    fleet, _results, _moved = written
    for table in (promlive.promfam.MEM_AVAILABLE,
                  promlive.promfam.MEM_TOTAL):
        base, tail = scan_cache.SCAN_CACHE.get_parts(fleet.region(table))
        assert tail is not None and tail.valid_rows > 0
        ds = fleet.ds       # the base is the load: nothing was merged
        assert base.num_rows == int((np.minimum(ds.last, ds.ticks)
                                     - np.minimum(ds.first, ds.ticks)).sum())


def test_a_series_that_exists_only_in_the_tail_is_selected(written):
    """The churn event due at the first live round: its targets are in
    no base. The row path selects one by its label; the lowered path
    answers it among the instances."""
    fleet, _results, _moved = written
    ds = fleet.ds
    new = np.flatnonzero(ds.first == ds.ticks)
    assert len(new) and fleet.posted >= 10      # its round is posted
    inst = ds.instances[int(new[0])]
    end = ds.t0_ms // 1000 + fleet.frontier_s()
    rows = fleet.query(f"TQL EVAL ({end - 60}, {end}, '15s') "
                       f'node_load1{{instance="{inst}"}}')
    assert rows and all(r[1] == inst for r in rows)
    s = ds.samples("node_load1")
    k = int(np.flatnonzero(s.labels["instance"] == inst)[0])
    assert np.abs(s.values[k, ds.ticks:] - float(rows[-1][-1])).min() < 1e-5
    end -= end % 60
    rows = fleet.query(f"TQL EVAL ({end - 600}, {end}, '60s') "
                       "max by (instance) (max_over_time(node_load1[1m]))")
    assert inst in {r[0] for r in rows}


# ---------------------------------------------------------------------------
# the narrowed launch over a tail under a growth plan
# ---------------------------------------------------------------------------

class OneNodeOverviewLive(promlive.LongLive, promlong.CpuByModeOne):
    def draw(self, rng, ds):
        p = promlive.Live.draw(self, rng, ds)
        whole = promlive._whole_targets(ds)
        p["instance"] = ds.instances[int(whole[rng.integers(0, len(whole))])]
        return p

    def _reference(self, p, ds):
        s, steps = ds.samples(promlive.promfam.CPU), self.steps(p, ds)
        keep = ref.matches(s, [("instance", "=", p["instance"])])
        rate, ok = promlive.extrapolated_rate(s, keep, steps, self.range_ms)
        by, total, present = promlong.aggregate(
            "sum", rate, ok, promlive.promfam._columns(s, keep, ["mode"]))
        return ref.points(by, steps, total, present)


def test_one_nodes_overview_panel_narrows_its_base_beside_a_tail(
        written, monkeypatch):
    fleet, _results, _moved = written
    fam = OneNodeOverviewLive("long-cpu-by-mode-1-live",
                              dict(rtol=5e-6, atol=0.0))
    rng = family_rng(SEED, fam.name, "window")
    merges = metric("scan_cache_merges")
    for _ in range(3):
        params = dict(fam.draw(rng, fleet.ds),
                      **fam.frontier(fleet.frontier_s(), fleet.ds))
        res = fleet.judge(fam, params)
        assert res["ok"] and res["rows"] > 0, (params, res)
    stages = fleet.stages(fam.sql(params, fleet.ds))
    # 64 ranges of the base narrow; those of the tail (a sixteenth of
    # the rows, the same ranges) pad to more than an eighth of it
    assert "path=narrow" in stages["reduce"][2]
    assert "tail_path=full" in stages["reduce"][2]
    # and where the tail narrows too, the seam rides the narrowed launch
    from greptimedb_tpu.query import scan_narrow
    monkeypatch.setattr(scan_narrow, "_NARROW_MAX_SHARE", 1)
    res = fleet.judge(fam, params)
    assert res["ok"] and res["rows"] > 0, res
    assert "tail_path=narrow" in fleet.stages(
        fam.sql(params, fleet.ds))["reduce"][2]
    assert metric("scan_cache_merges") == merges


# ---------------------------------------------------------------------------
# the new rows, timers and counters
# ---------------------------------------------------------------------------

def test_the_row_path_says_what_it_took_from_the_tail(written):
    fleet, _results, _moved = written
    fam = load_family("prom-cpu-busy-all-live")
    params = dict(fam.draw(family_rng(SEED, fam.name, "rows"), fleet.ds),
                  **fam.frontier(fleet.frontier_s(), fleet.ds))
    stages = fleet.stages(fam.sql(params, fleet.ds))
    names = list(stages)
    assert "select.tail" in names
    assert names.index("select") < names.index("select.tail") \
        < names.index("window")
    assert T0_NS.search(stages["select.tail"][2])
    assert re.search(r"tail_rows=\d+", stages["select"][2])
    assert stages["select.tail"][1] <= stages["select"][1]


def test_the_lowered_path_makes_the_seam_in_a_row_of_its_own(written):
    fleet, _results, _moved = written
    fam = load_family("long-cpu-util-fleet-live")
    fleet.post_next()           # a new tail: its derived mirror is to make
    params = dict(fam.draw(family_rng(SEED, fam.name, "rows"), fleet.ds),
                  **fam.frontier(fleet.frontier_s(), fleet.ds))
    seams = metric("scan_seam_pairs")
    stages = fleet.stages(fam.sql(params, fleet.ds))
    names = list(stages)
    assert stages["dispatch"][2] == "device-resident (scan cache)"
    assert "reduce.seam" in names
    assert names.index("reduce") < names.index("reduce.seam") \
        < names.index("finalize")
    assert T0_NS.search(stages["reduce.seam"][2])
    assert "seam=merged" not in stages["scan_prep"][2]
    assert re.search(r"tail_rows=\d+", stages["reduce"][2])
    assert metric("scan_seam_pairs") > seams
    # the same tail again: its mirror is there, no seam row
    assert "reduce.seam" not in fleet.stages(fam.sql(params, fleet.ds))


def test_a_panel_off_the_minute_lays_out_its_selection_not_the_table(
        written, monkeypatch):
    """Every statement of the fleet panel ended at another second of its
    one-minute step: the base holds the one table layout its first warm
    statement made, and each later grid was laid out from the selection,
    its run ids made on the device. The table's layout of the same grid
    gives the same numbers."""
    fleet, _results, _moved = written
    base, _tail = scan_cache.SCAN_CACHE.get_parts(
        fleet.region(promlive.promfam.CPU))
    assert len([k for k in base.device if k.startswith("__runs:")]) == 1
    assert "__sids" in base.device
    fam = load_family("long-cpu-util-fleet-live")
    params = dict(fam.draw(family_rng(SEED, fam.name, "rows"), fleet.ds),
                  **fam.frontier(fleet.frontier_s(), fleet.ds))
    laid = metric("scan_selection_layouts")
    sql = fam.sql(params, fleet.ds)
    detail = fleet.stages(sql)["reduce"][2]
    assert "runs=selection" in detail and "groups=live" in detail
    assert metric("scan_selection_layouts") == laid + 1
    got = fleet.query(sql)
    monkeypatch.setattr(scan_full, "_selection_layout",
                        lambda *a: None)
    assert "runs=selection" not in fleet.stages(sql)["reduce"][2]
    assert fleet.query(sql) == got


def test_a_program_without_the_seam_is_not_correct_at_the_frontier(
        written, monkeypatch):
    """The control the cell's comparison has to fail: a tail's derived
    mirror made without its base (a series' first difference 0, so a
    window across the seam is the sum of its two parts and no more).
    At the frontier the fleet panel's newest window holds the last
    loaded sample and the first written one of five series in six, and
    the answer is off by what the harness compares; what the program
    answers is what `without_seam` makes of the reference."""
    fleet, _results, _moved = written
    fam = load_family("long-cpu-util-fleet-live")
    params = dict(fam.draw(family_rng(SEED, fam.name, "seam"), fleet.ds),
                  **fam.frontier(fleet.frontier_s(), fleet.ds))
    assert params["end_s"] % 60
    assert fleet.judge(fam, params)["ok"]
    monkeypatch.setattr(scan_cache, "_seam", lambda *a: None)
    fleet.post_next()           # a new tail: its mirror is made anew
    got = fam.parse(fleet.query(fam.sql(params, fleet.ds)), fleet.ds)
    res = chk.compare(got, fam.reference(params, fleet.ds), fam.tolerance)
    assert not res["ok"] and res["max_abs_err"] > 1e-3, res
    left_out = fam.reference(dict(params, without_seam=1), fleet.ds)
    assert chk.compare(got, left_out, fam.tolerance)["ok"]
    monkeypatch.undo()
    fleet.post_next()
    assert fleet.judge(fam, params)["ok"]


def test_the_write_path_is_timed_and_counted_on_metrics(written):
    fleet, _results, _moved = written
    with urllib.request.urlopen(
            f"http://127.0.0.1:{fleet.http.port}/metrics", timeout=60) as r:
        text = r.read().decode()
    for name in ("greptime_prom_write_decode_seconds_count",
                 "greptime_prom_write_insert_seconds_sum",
                 "greptime_prom_write_decode_cpu_seconds_total",
                 'greptime_promql_select_parts_total{tail="yes"}',
                 "greptime_scan_seam_pairs_total",
                 "greptime_ingest_parse_wait_seconds_count",
                 'greptime_http_request_seconds_count{route='
                 '"/v1/prometheus/write"}'):
        assert name in text, name
    decoded = re.search(r"greptime_prom_write_decode_seconds_count (\S+)",
                        text)
    assert float(decoded.group(1)) >= BLOCKS


def test_the_decoder_offers_the_lock_after_every_series():
    ds = dataset(extra=1)
    body, _first, rows = ds.blocks()[0]
    calls = []
    inserts, tags = prom.write_request_to_inserts(
        body, lambda: calls.append(1))
    assert len(calls) == rows
    assert sorted(inserts) == sorted(ds.tables)
    assert sum(len(c[prom.GREPTIME_VALUE]) for c in inserts.values()) == rows
    for name, (stamps, values) in ds.block_samples(0).items():
        assert sorted(inserts[name][prom.GREPTIME_TIMESTAMP]) == \
            sorted(stamps.tolist())
        assert sorted(inserts[name][prom.GREPTIME_VALUE]) == \
            sorted(values.tolist())
        assert tags[name] == sorted(ds.tables[name].label_names)


# ---------------------------------------------------------------------------
# the read-back after a restart
# ---------------------------------------------------------------------------

def test_exactly_the_posted_samples_are_read_back_after_a_restart(
        tmp_path_factory):
    f = Fleet(str(tmp_path_factory.mktemp("restart")))
    for _ in range(12):
        f.post_next()
    want = {name: ([], []) for name in f.ds.tables}
    for b in range(12):
        for name, (stamps, values) in f.ds.block_samples(b).items():
            want[name][0].extend(stamps.tolist())
            want[name][1].extend(values.tolist())

    def read(fe):
        out = {}
        for name in f.ds.tables:
            rows = rows_of(fe.do_query(
                f"SELECT {f.ds.time_index}, {f.ds.value_field} FROM {name} "
                f"WHERE {f.ds.time_index} >= {f.ds.end_ms}")[-1])
            out[name] = (sorted(int(r[0]) for r in rows),
                         sorted(float(r[1]) for r in rows))
        return out

    expected = {n: (sorted(s), sorted(v)) for n, (s, v) in want.items()}
    assert read(f.fe) == expected
    f.http.shutdown()
    f.fe.shutdown()
    again = frontend(f.data_home)
    try:
        assert read(again) == expected
    finally:
        again.shutdown()


# ---------------------------------------------------------------------------
# the seam by hand: 2.6e14, a reset exactly at it, a late row
# ---------------------------------------------------------------------------

#: name: (start, growth a second, resets at sample or None); 90 samples
#: of history at 10 s, then remote write
COUNTERS = {"bytes_2_6e14": (2.6e14, 98.8e6, None),
            "cpu_2_6e6": (2.6e6, 0.37, None),
            "reboots_at_the_seam": (2.6e14, 98.8e6, 90)}
HISTORY = 90
#: a fourth series, loaded without these two samples of its history
HOLE = (50, 51)


def counter_value(name: str, k: int) -> float:
    start, growth, reset = COUNTERS[name]
    return start + growth * 10 * k if reset is None or k < reset \
        else growth * 10 * (k - reset + 1)


def write(port: int, samples) -> None:
    """[(name, sample number)] through /v1/prometheus/write."""
    post(port, prom.encode_write_request([
        prom.TimeSeries({"__name__": "c", "name": name},
                        [(counter_value(name, k), k * 10_000)])
        for name, k in samples]))


@pytest.fixture(scope="module")
def counters(tmp_path_factory):
    fe = frontend(str(tmp_path_factory.mktemp("seam")))
    http = HttpServer(fe, addr="127.0.0.1:0")
    http.start()
    fe.do_query("CREATE TABLE c (name STRING, greptime_timestamp TIMESTAMP "
                "TIME INDEX, greptime_value DOUBLE, PRIMARY KEY(name))")
    fe.do_query("INSERT INTO c VALUES " + ", ".join(
        [f"('{name}', {k * 10_000}, {counter_value(name, k)!r})"
         for name in COUNTERS for k in range(HISTORY)]
        + [f"('holed', {k * 10_000}, {2.0 * k!r})"
           for k in range(HISTORY) if k not in HOLE]))
    fe.do_query("SET tpu_dispatch_min_rows = 1")
    yield fe, http.port
    fe.do_query("SET tpu_dispatch_min_rows = 131072")
    http.shutdown()
    fe.shutdown()


def tql(fe, query: str, start=600, end=1200, step="1m") -> dict:
    rows = rows_of(fe.do_query(
        f"TQL EVAL ({start}, {end}, '{step}') {query}")[-1])
    out = {}
    for r in rows:
        out.setdefault(r[0], {})[int(r[-2])] = float(r[-1])
    return out


def test_a_counter_keeps_its_growth_across_the_seam(counters):
    """The base ends at sample 89 (890 s); samples 90..119 arrive by
    remote write, three at a time. The window (840 s, 900 s] holds
    samples 85..90: five of the base, one of the tail, and their growth
    is the sum of five differences of the base's mirror and the seam.
    A counter at 2.6e14 growing 98.8 MB/s is held to 1e-6: f32 `first` /
    `last` of two partials step by 3.4e7 there, a third of a second's
    growth. The third series restarts exactly at the seam: sample 90 is
    one scrape's growth, and `rate` adds the value before it."""
    fe, port = counters
    query = "sum by (name) (rate(c[1m]))"
    assert len(tql(fe, query, end=840)["bytes_2_6e14"]) == 5   # a base
    merges, seams = metric("scan_cache_merges"), metric("scan_seam_pairs")
    for k in range(HISTORY, 120, 3):
        write(port, [(name, j) for name in COUNTERS
                     for j in range(k, k + 3)])
        got = tql(fe, query, end=(k + 2) * 10 - (k + 2) * 10 % 60)
        for name, (_start, growth, reset) in COUNTERS.items():
            for t, v in got[name].items():
                if reset is not None and t == 960_000:
                    # (900, 960] starts on the restarted counter: its
                    # zero point caps the extrapolation
                    assert 0.9 * growth < v <= growth * (1 + 1e-6), (t, v)
                else:
                    assert abs(v / growth - 1) < 1e-6, (name, k, t, v)
        assert max(got["bytes_2_6e14"]) >= 900_000
    assert metric("scan_cache_merges") == merges
    assert metric("scan_seam_pairs") - seams >= len(COUNTERS)
    base, tail = scan_cache.SCAN_CACHE.get_parts(
        next(iter(fe.catalog.table("greptime", "public",
                                   "c").regions.values())))
    assert base.num_rows == HISTORY * (len(COUNTERS) + 1) - len(HOLE)
    assert tail.valid_rows == 30 * len(COUNTERS)


def test_a_late_row_under_a_growth_plan_merges_and_is_still_right(counters):
    """Sample 50 of the series loaded without it arrives by remote write:
    the tail then holds a row before its series' last row in the base,
    whose mirror already holds the difference across the hole. A growth
    plan takes one scan (counted), and is right; a gauge plan over such
    a tail folds two partials as before."""
    fe, port = counters
    query = 'sum by (name) (rate(c{name="holed"}[1m]))'
    merges = metric("scan_cache_merges")
    post(port, prom.encode_write_request([prom.TimeSeries(
        {"__name__": "c", "name": "holed"}, [(100.0, 500_000)])]))
    got = tql(fe, query, end=840)["holed"]
    assert metric("scan_cache_merges") == merges + 1    # counted
    assert len(got) == 5
    for t, v in got.items():    # 0.2 a second all along, the hole too
        assert abs(v / 0.2 - 1) < 1e-6, (t, v)
    post(port, prom.encode_write_request([prom.TimeSeries(
        {"__name__": "c", "name": "holed"}, [(1000.0, 510_000)])]))
    merges = metric("scan_cache_merges")
    top = tql(fe, 'max by (name) (max_over_time(c{name="holed"}[1m]))',
              start=480, end=840)["holed"]
    assert top[540_000] == 1000.0 and top[600_000] == 120.0
    assert metric("scan_cache_merges") == merges


def test_the_seam_is_made_in_float64():
    """`_seam` on arrays: the first difference of a tail's series is
    `v - prev` against the base's last sample (`v` itself after a
    reset), 0 for a series the base has never seen."""
    base = scan_cache.MergedScan(
        np.array([0, 0, 2, 2], np.int32), np.array([0, 10, 0, 10]),
        {"v": (np.array([2.6e14, 2.6e14 + 988.0, 5.0, 7.0]), None)},
        None, 0)
    sids = np.array([0, 0, 1, 2], np.int32)
    v = np.array([2.6e14 + 1976.0, 2.6e14 + 2964.0, 3.0, 1.0])
    d = np.array([0.0, 988.0, 0.0, 0.0])
    pairs = metric("scan_seam_pairs")
    scan_cache._seam(base, "v", True, sids, v, d)
    assert d.tolist() == [988.0, 988.0, 0.0, 1.0]
    assert metric("scan_seam_pairs") == pairs + 2
    d = np.zeros(4)
    scan_cache._seam(base, "v", False, sids, v, d)
    assert d.tolist() == [988.0, 0.0, 0.0, -6.0]


def test_growth_folds_across_the_seam_by_run():
    """`_fold_runs`: a run both partials hold is the base's growth, the
    tail's, and the tail's first difference; a run of the tail alone
    keeps its growth."""
    from greptimedb_tpu.query.agg_plan import BucketGroup, Moment, TpuPlan
    from greptimedb_tpu.query.moment_fold import _fold_runs, _RunPartial
    plan = TpuPlan.__new__(TpuPlan)
    plan.tag_groups = [object()]
    plan.bucket = BucketGroup(60_000, 0, "w")
    plan.moments = [Moment("count", "v", "n"),
                    Moment("increase", "v", "g")]
    a = _RunPartial(np.array([0, 0, 1]), np.array([0, 1, 1]),
                    [np.array([6, 5, 6]), np.array([50., 40., 30.],
                                                   np.float32)],
                    np.array([6, 5, 6]), None)
    b = _RunPartial(np.array([0, 0, 2]), np.array([1, 2, 2]),
                    [np.array([1, 6, 3]), np.array([0., 55., 7.],
                                                   np.float32)],
                    np.array([1, 6, 3]), None,
                    {1: np.array([10., 11., np.nan], np.float32)})
    out = _fold_runs(a, b, plan)
    keys = list(zip(out.sids.tolist(), out.buckets.tolist()))
    growth = dict(zip(keys, out.moments[1].tolist()))
    count = dict(zip(keys, out.moments[0].tolist()))
    assert growth == {(0, 0): 50.0, (0, 1): 50.0, (1, 1): 30.0,
                      (0, 2): 55.0, (2, 2): 7.0}
    assert count[(0, 1)] == 6


def test_a_tails_group_axis_follows_its_base():
    """A panel by the minute cuts a run every six scrapes: the tail's
    axis is an eighth of its base's (a tail holds up to an eighth of its
    base's rows), whatever it holds today, and the axis kind is the
    base's."""
    shape = scan_launch._LaunchShape
    assert scan_launch._tail_groups(shape("full", 8192, "live", 1 << 20)) \
        == 1 << 17
    assert scan_launch._tail_groups(shape("narrow", 8, None, 0)) == 0
    assert scan_launch._tail_groups(None) == 0


def test_the_live_reference_is_the_shared_grids_at_every_offset():
    """`promlive.extrapolated_rate` against `promref.extrapolated_rate`
    a target at a time (`shifted`), and the two ways of `shifted` for a
    function that reads no distance."""
    ds = dataset(extra=4)
    s = ds.samples(promlive.promfam.CPU)
    keep = ref.matches(s, [("mode", "=", "idle")])
    steps = ds.t0_ms + np.arange(7000, 7241, 15, dtype=np.int64) * 1000
    fast, ok = promlive.extrapolated_rate(s, keep, steps, 300_000)
    slow, ok2 = promlive.shifted(ref.extrapolated_rate, s, keep, steps,
                                 300_000)
    assert (ok == ok2).all() and ok.any() and not ok.all()
    assert np.array_equal(fast[ok], slow[ok])
    load = ds.samples("node_load1")
    every = np.ones(len(load.first), dtype=bool)
    a = promlive.shifted(ref.instant, load, every, steps, 300_000)
    b = promlive.shifted(ref.instant, load, every, steps, 300_000,
                         by="second")
    assert np.array_equal(a[1], b[1]) and np.array_equal(a[0][a[1]],
                                                         b[0][b[1]])
    # a slot a live target, a successor its predecessor's
    assert len(np.unique(ds.offset_ms)) == ds.hosts
    assert (ds.offset_ms % 1000 != 0).all()
