"""Unit tests of `prom1k-remote-write-while-read`'s own pieces, no server
and no chip (seconds), and its whole-run controls on the CPU backend at
the debug size (a minute; `selftest.py`'s `PERTURBATIONS` is keyed by loop
kind and does not know `remote-write`, so they run from here).

    python3 -m pytest benchmark/test_remote_write.py -q

What the cell's entries say, what a block carries and in what order the
schedule is cut, the frontier a statement's range ends at, the live
reference against `promref.py`'s, the readers of the new timers and rows
on synthetic records (and on a record of a program without them), and the
three controls: a lost block, an answer one block stale, bf16 mirrors with
the live samples in.
"""

import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

from benchlib import check as chk  # noqa: E402
from benchlib import promlive  # noqa: E402
from benchlib import promref as ref  # noqa: E402
from benchlib.loops import family_rng  # noqa: E402
from benchlib.spec import (Cell, load_family, load_generator,  # noqa: E402
                           load_layer_reader, load_loop)

CELL = "prom1k-remote-write-while-read"
LOOP = load_loop("remote-write")
SEED = 2147483783
EXTRA = 8           # live scrape rounds: 80 blocks
NEW_READERS = ("prom_write_decode_ms", "prom_write_insert_ms",
               "prom_write_server_ms", "prom_write_wait_ms",
               "prom_wal_fsync_ms", "prom_region_write_ms",
               "select_tail_ms", "seam_growth_ms")


@pytest.fixture(scope="module")
def cell():
    return Cell(CELL)


@pytest.fixture(scope="module")
def ds(cell):
    size = cell.config["debug"]
    return load_generator(cell.config)(
        cell.config, SEED, extra_ticks=EXTRA, scale=size["scale"],
        ticks=size["duration_s"] // cell.config["log_interval_s"])


# ---------------------------------------------------------------------------
# the deployment, the mix, the entries
# ---------------------------------------------------------------------------

def test_the_configuration_is_the_two_hour_fleet_written_durably(cell):
    loaded = Cell("prom1k-longrange").config
    config = cell.config
    for key in ("scale", "log_interval_s", "duration_s", "start",
                "time_index", "value_field", "job", "churn", "tables", "cpus",
                "modes", "net_devices", "filesystems", "uptime_days",
                "reboot_share", "receive_bytes_per_s", "query",
                "load_chunk_ticks", "debug"):
        assert config[key] == loaded[key], key
    assert config["generator"] == "node-exporter-live"
    assert config["server_options"] == ["--wal-sync-on-write"]
    assert config["panels"]["overview"] == loaded["query"]
    assert config["panels"]["dashboard"] == Cell(
        "prom1k-dashboard").config["query"]
    assert set(config["guarantees"]) == {"durability", "consistency",
                                         "answers"}
    assert config["assumed"][:len(loaded["assumed"])] == loaded["assumed"]
    assert sorted(config["reduced"]) == sorted(loaded["reduced"])
    assert "_TAIL_SHARE" in config["reduced"]["duration_s"]
    entry = next(c for c in cell.benchmark["configs"]
                 if c["name"] == "prom-node-1k-remote-write")
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert cell.benchmark["configs"][-1] is entry


def test_the_cell_reports_what_its_entries_say(cell):
    assert cell.chips == 1 and cell.mix["loop"] == "remote-write"
    assert cell.benchmark["workloads"][-1] is cell.entry
    assert len(cell.entry["why"]) <= 200
    mix = cell.mix
    assert mix["families"] == [
        "prom-cpu-busy-all-live", "prom-cpu-by-mode-1-live",
        "prom-mem-used-ratio-live", "prom-net-rx-topk-live",
        "long-cpu-util-fleet-live", "long-load-max-by-instance-live"]
    assert (mix["workers"], mix["prefill_batches"], mix["extra_ticks"],
            mix["warm_statements"], mix["live_statement_limit_s"]) == \
        (4, 12, 40, 3, 10.0)
    reported = [m["name"] for m in cell.metrics("end_to_end")]
    assert reported == ["stmt_geomean_ms", "stmt_per_s", "ingest_rows_per_s",
                        "setup_s"]
    assert set(reported) == set(mix["reports"].values()) | {"setup_s"}
    layers = {m["name"]: m for m in cell.metrics("per_layer")}
    for name, m in layers.items():
        assert os.path.isfile(os.path.join(
            HERE, "layers", name.split(".", 1)[0] + ".py")), name
        assert m["moves"] in reported, name
        assert m["workloads"][-1] == CELL, name     # appended, not inserted
    for name in NEW_READERS:
        assert layers[name]["workloads"] == [CELL]
    assert {layers[n]["moves"] for n in NEW_READERS[:6]} == \
        {"ingest_rows_per_s"}
    assert {"prom_select_ms", "prom_lower_ms", "window_kernels_roofline",
            "lowered_scan_roofline", "tail_merges", "compiled_in_window",
            "cache_refreshes", "batch_ack_ms", "gc_full_ms",
            "loop_lag_ms", "host_off_cpu_ms"} <= set(layers)
    # their readers divide by the line-protocol route's requests, or
    # bound a `live` family's answer: nothing to read here
    assert not {"wal_fsync_ms", "region_write_ms", "ingest_parse_ms",
                "visible_lag_ms", "scan_kernels_roofline"} & set(layers)


def test_the_families_are_their_parents_with_an_end_of_their_own(cell, ds):
    parents = {"prom-cpu-busy-all-live": "prom-cpu-busy-all",
               "prom-cpu-by-mode-1-live": "prom-cpu-by-mode-1",
               "prom-mem-used-ratio-live": "prom-mem-used-ratio",
               "prom-net-rx-topk-live": "prom-net-rx-topk",
               "long-cpu-util-fleet-live": "long-cpu-util-fleet",
               "long-load-max-by-instance-live": "long-load-max-by-instance"}
    for name, parent in parents.items():
        fam, old = load_family(name), load_family(parent)
        assert fam.tolerance == old.tolerance and fam.via == old.via
        assert fam._dispatch == old._dispatch
        p = fam.draw(family_rng(SEED, name, "window"), ds)
        q = dict(p, instance=p.get("instance"))
        assert fam.query(p, ds) == old.query(q, ds)
        lowered = name.startswith("long-")
        assert fam.frontier(7277, ds) == {"end_s": 7277}
        span, step = (6000, 60) if lowered else (900, 15)
        end = ds.t0_ms // 1000 + p["end_s"]
        assert fam.sql(p, ds).startswith(
            f"TQL EVAL ({end - span}, {end}, '{step}s') ")
        assert len(fam.steps(p, ds)) == span // step + 1


# ---------------------------------------------------------------------------
# the generator: offsets, blocks, the schedule
# ---------------------------------------------------------------------------

def test_every_second_of_the_schedule_holds_a_tenth_of_the_fleet(ds):
    assert ds.ticks == 720 and ds.total_ticks == 720 + EXTRA
    assert ds.rows == 20 * 77 * 720         # a target replaced is a target
    alive = (ds.first <= ds.ticks) & (ds.ticks < ds.last)
    assert alive.sum() == ds.hosts
    assert sorted(ds.slot[alive]) == list(range(ds.hosts))
    assert (ds.offset_ms % 1000 != 0).all() and ds.offset_ms.max() < 10_000
    new = np.flatnonzero(ds.first == ds.ticks)
    assert len(new) == 1                    # the event due at minute 120
    seen = {}
    for b in range(EXTRA * 10):
        series = ds.block_series(b)
        assert sum(len(s) for s, _ in series.values()) == 2 * 77
        lo = ds.block_start_ms(b)
        for name, (stamps, values) in ds.block_samples(b).items():
            assert ((stamps > lo) & (stamps < lo + 1000)).all()
            assert len(values) == len(series[name][0])
        for s in series["node_load1"][0].tolist():
            seen.setdefault(s, []).append(b)
    # a series is in every tenth block: no sample is late with four in flight
    assert all(np.diff(v).tolist() == [10] * (EXTRA - 1)
               for v in seen.values())
    assert any(ds.first[ds.tables["node_load1"].instance_of[s]] == ds.ticks
               for s in seen)


def test_a_block_is_a_remote_write_request_of_its_samples(ds):
    import pyarrow as pa
    blocks = ds.blocks()
    assert len(blocks) == EXTRA * 10
    assert [b[1] for b in blocks] == [154 * i for i in range(len(blocks))]
    body, _first, rows = blocks[3]
    raw = pa.Codec("snappy").decompress(body, asbytes=True,
                                        decompressed_size=1 << 20)
    want = ds.block_samples(3)
    assert rows == sum(len(v) for _, v in want.values()) == 154
    # every value and every timestamp lies in the body as prompb lays them
    for stamps, values in want.values():
        for t, v in zip(stamps.tolist(), values.tolist()):
            ts = bytearray()
            n = int(t)
            while n > 0x7F:
                ts.append((n & 0x7F) | 0x80)
                n >>= 7
            ts.append(n)
            assert b"\x09" + np.float64(v).tobytes() + b"\x10" + bytes(ts) \
                in raw
    assert raw.count(b"__name__") == rows
    assert raw.count(b"node_cpu_seconds_total") == 2 * 64


def test_the_live_rounds_continue_the_loaded_ones(ds, cell):
    """One pass over loaded and live rounds: a counter's first live
    sample goes on from its last loaded one, and the loaded part is what
    a dataset without live rounds loads."""
    size = cell.config["debug"]
    alone = load_generator(cell.config)(
        cell.config, SEED, scale=size["scale"], ticks=ds.ticks)
    assert alone.rows == ds.rows and alone.total_ticks == ds.ticks
    s = ds.samples(promlive.promfam.CPU)
    whole = (s.first == 0) & (s.last == ds.total_ticks)
    step = s.values[whole, ds.ticks] - s.values[whole, ds.ticks - 1]
    assert ((step > 0) & (step < 10.0)).mean() > 0.98    # but the reboots
    chunks = list(ds.arrow_chunks(360))
    assert sum(t.num_rows for _, _, t in chunks) == ds.rows
    name, _tags, table = chunks[0]
    stamps = table.column(ds.time_index).to_numpy()
    assert stamps.max() < ds.end_ms + 10_000 and (stamps % 1000 != 0).all()


def test_without_a_block_its_targets_end_before_its_round(ds):
    assert ds.newest_block_at(ds.end_ms + 999) is None
    assert ds.newest_block_at(ds.end_ms + 1000) == 0
    assert ds.newest_block_at(ds.end_ms + 13_500) == 12
    assert ds.without_block(None) is ds
    stale = ds.without_block(12)
    cut = np.flatnonzero(stale.last != ds.last)
    assert len(cut) == 2 and (stale.last[cut] == ds.ticks + 1).all()
    assert sorted(ds.slot[cut] * 10 // ds.hosts) == [2, 2]


# ---------------------------------------------------------------------------
# the frontier
# ---------------------------------------------------------------------------

class _Writers(LOOP.__init__.__globals__["BlockWriters"]):
    def __init__(self, ds, acked):
        import threading
        self.ctx = type("Ctx", (), {"ds": ds})()
        self.lock = threading.Lock()
        self.acked = np.asarray(acked, dtype=bool)


def test_the_frontier_is_below_the_oldest_block_not_acknowledged(ds):
    load_end = (ds.end_ms - ds.t0_ms) // 1000
    none = _Writers(ds, [False] * 8)
    assert none.frontier_s() == load_end - 1
    some = _Writers(ds, [True, True, True, False, True, True, False, False])
    assert some.frontier_s() == load_end + 3 - 1    # block 3 is in flight
    every = _Writers(ds, [True] * 8)
    assert every.frontier_s() == load_end + 8 - 1
    # every sample at or before it lies in an acknowledged block
    for b in range(8):
        for stamps, _ in ds.block_samples(b).values():
            visible = stamps <= ds.t0_ms + some.frontier_s() * 1000
            assert not visible.any() or some.acked[b]


# ---------------------------------------------------------------------------
# the live reference
# ---------------------------------------------------------------------------

def test_the_live_rate_is_promrefs_a_target_at_a_time(ds):
    s = ds.samples(promlive.promfam.NET)
    keep = ref.matches(s, [("device", "!=", "lo")])
    steps = ds.t0_ms + np.arange(6900, 7261, 15, dtype=np.int64) * 1000
    fast, ok = promlive.extrapolated_rate(s, keep, steps, 300_000)
    slow, ok2 = promlive.shifted(ref.extrapolated_rate, s, keep, steps,
                                 300_000)
    assert (ok == ok2).all() and ok.any()
    assert np.array_equal(fast[ok], slow[ok])
    # and with no offsets it is promref's own
    flat = copy.copy(s)
    flat.offset = np.zeros_like(s.offset)
    a, _ = promlive.extrapolated_rate(flat, keep, steps, 300_000)
    b, okb = ref.extrapolated_rate(flat, keep, steps, 300_000)
    assert np.array_equal(a[okb], b[okb])


# ---------------------------------------------------------------------------
# the readers, on a synthetic record and on the parent's
# ---------------------------------------------------------------------------

ROUTE = '{route="/v1/prometheus/write"}'


def _run(program_has_them: bool = True) -> dict:
    before = {"greptime_http_request_seconds_count" + ROUTE: 12.0,
              "greptime_http_request_seconds_sum" + ROUTE: 24.0}
    after = {"greptime_http_request_seconds_count" + ROUTE: 112.0,
             "greptime_http_request_seconds_sum" + ROUTE: 274.0}
    if program_has_them:
        before.update({"greptime_prom_write_decode_seconds_sum": 3.0,
                       "greptime_prom_write_insert_seconds_sum": 1.0,
                       "greptime_wal_fsync_seconds_sum": 9.0,
                       "greptime_region_write_seconds_sum": 10.0})
        after.update({"greptime_prom_write_decode_seconds_sum": 43.0,
                      "greptime_prom_write_insert_seconds_sum": 16.0,
                      # observed by the first block that waited: absent
                      # from the scrape before the window
                      "greptime_ingest_parse_wait_seconds_sum": 190.0,
                      "greptime_wal_fsync_seconds_sum": 17.0,
                      "greptime_region_write_seconds_sum": 22.0})

    def statement(family, rows):
        stages = {name: {"rows": 0, "elapsed_ms": ms,
                         "detail": f"cpu_ms=1.000, t0_ns={1_000 + i}"}
                  for i, (name, ms) in enumerate(rows.items())}
        return {"family": family, "in_window": True, "ok": True,
                "stages": stages}

    tail = {"select.tail": 8.0} if program_has_them else {}
    seam = {"reduce.seam": 40.0} if program_has_them else {}
    return {"batches": [{}], "counters": {"before": before, "after": after},
            "statements": [
                statement("prom-cpu-busy-all-live", {"select": 90.0, **tail}),
                statement("prom-cpu-busy-all-live", {"select": 70.0}),
                statement("long-cpu-util-fleet-live", {"reduce": 500.0,
                                                       **seam}),
                statement("long-cpu-util-fleet-live", {"reduce": 450.0})]}


def test_the_new_readers_read_their_timers_and_rows():
    run = _run()
    values = {n: load_layer_reader(n)(run) for n in NEW_READERS}
    assert values == pytest.approx({
        "prom_write_decode_ms": 400.0, "prom_write_insert_ms": 150.0,
        "prom_write_server_ms": 2500.0, "prom_write_wait_ms": 1900.0,
        "prom_wal_fsync_ms": 80.0, "prom_region_write_ms": 120.0,
        "select_tail_ms": 8.0, "seam_growth_ms": 40.0})


def test_the_new_readers_read_nothing_from_the_parent_program():
    run = _run(program_has_them=False)
    values = {n: load_layer_reader(n)(run) for n in NEW_READERS}
    # the request timer is the program's since PR 24: the parent has it
    assert values.pop("prom_write_server_ms") == pytest.approx(2500.0)
    assert set(values.values()) == {None}
    for name in NEW_READERS:
        assert load_layer_reader(name)({}) is None
        assert load_layer_reader(name)({"statements": []}) is None


# ---------------------------------------------------------------------------
# the controls
# ---------------------------------------------------------------------------

def live_control(ds, draws: int = 2) -> dict:
    """The bf16 control with the live samples in: `control.py`'s draws end
    inside the loaded history; here every range ends in the live rounds,
    so the float64 reference and the one over bf16 mirrors both hold the
    samples remote write brought. -> family: (the smallest number of the
    draws, its limit)."""
    mirror = copy.copy(ds)
    mirror.data = chk.bf16_round(ds.data.reshape(-1)).reshape(ds.data.shape)
    last = (ds.ms(ds.total_ticks) - ds.t0_ms) // 1000 - 10
    out = {}
    for name in Cell(CELL).mix["families"]:
        fam = load_family(name)
        rng = family_rng(SEED, name, "control")
        smallest = None
        for k in range(draws):
            params = dict(fam.draw(rng, ds),
                          **fam.frontier(last - 17 * k, ds))
            res = chk.compare(fam.reference(params, mirror),
                              fam.reference(params, ds), fam.tolerance)
            _number, value, limit = chk.compared_number(res, fam.tolerance)
            smallest = value if smallest is None else min(smallest, value)
        out[name] = (smallest, limit)
    return out


def test_the_bf16_control_fails_with_the_live_samples_in(ds):
    for name, (value, limit) in live_control(ds).items():
        assert value > limit, (name, value, limit)


def test_an_answer_one_block_stale_is_not_the_reference(ds):
    """What `stale-lastpoint` compares: the float64 reference over the
    table as it was one block behind the statement's end is off the
    reference by more than the family's tolerance (where the block holds
    a series the family reads)."""
    off = {}
    for name in Cell(CELL).mix["families"]:
        fam = load_family(name)
        rng = family_rng(SEED, name, "stale")
        worst = 0.0
        for k in range(20):     # twenty frontiers, a second apart
            params = dict(fam.draw(rng, ds),
                          **fam.frontier(7200 + 61 + k, ds))
            res = chk.compare(
                fam.reference(dict(params, without_newest_block=1), ds),
                fam.reference(params, ds), fam.tolerance)
            _number, value, limit = chk.compared_number(res, fam.tolerance)
            worst = max(worst, np.inf if value is None else value)
        off[name] = (worst, limit)
    # (a `max` moves only where the missing sample was the window's
    # largest, `topk` where its target is among the five: at the debug
    # size a block holds two targets, so those two are not held to it)
    for name in ("prom-cpu-busy-all-live", "prom-mem-used-ratio-live",
                 "long-cpu-util-fleet-live"):
        assert off[name][0] > off[name][1], (name, off[name])


def test_an_answer_without_the_seam_is_not_the_reference(ds):
    """What `seam-left-out` compares: the fleet panel's growth as the
    sum of what the load and the written rows give apart is off the
    reference by far more than the tolerance wherever the panel ends off
    the minute (a window then holds a series' last loaded sample and its
    first written one), and is the reference itself on the minute, where
    the grid's edge lies between the two: why the cell's panels end at
    the frontier and not at the last whole minute below it."""
    fam = load_family("long-cpu-util-fleet-live")
    params = fam.draw(family_rng(SEED, fam.name, "seam"), ds)

    def off(end_s: int) -> float:
        p = dict(params, **fam.frontier(end_s, ds))
        res = chk.compare(fam.reference(dict(p, without_seam=1), ds),
                          fam.reference(p, ds), fam.tolerance)
        return chk.compared_number(res, fam.tolerance)[1]

    limit = fam.tolerance["atol"]
    for end_s in (7201, 7213, 7247, 7259, 7261, 7279):
        assert off(end_s) > 1000 * limit, end_s
    for end_s in (7260, 7320):
        assert off(end_s) == 0.0, end_s


def run_control(*args):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "control.py"), "--workload", CELL,
         "--seed", str(SEED), "--debug", *args],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        timeout=900, cwd=os.path.dirname(HERE))
    lines = [x for x in proc.stdout.splitlines() if x.startswith("{")]
    return proc.returncode, json.loads(lines[-1]), proc.stdout


def test_the_bf16_control_fails_every_family():
    rc, out, text = run_control("--draws", "2")
    assert rc == 0, text[-2000:]
    assert set(out["control"]) == set(Cell(CELL).mix["families"])
    assert all(v["fails"] for v in out["control"].values())


def test_a_run_held_to_answers_without_the_seam_fails_the_fleet_panel():
    """`seam-left-out` as a whole run through the harness: the program's
    answers (the seam in) against the reference without it. Every
    statement of the fleet panel is off and no other family's is."""
    code = ("import json, sys; sys.path.insert(0, %r); "
            "from benchlib.harness import run_cell; "
            "print(json.dumps(run_cell(%r, %d, 4.0, False, 'cpu', "
            "perturb='seam-left-out')))" % (HERE, CELL, SEED))
    proc = subprocess.run(
        [sys.executable, "-c", code], stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, timeout=900,
        cwd=os.path.dirname(HERE))
    lines = [x for x in proc.stdout.splitlines() if x.startswith("{")]
    assert proc.returncode == 0 and lines, proc.stdout[-3000:]
    result = json.loads(lines[-1])
    assert result["correct"] is False
    wrong = {name: result["compared"][f"{name}.wrong_answers"]["value"]
             for name in Cell(CELL).mix["families"]}
    assert wrong.pop("long-cpu-util-fleet-live") > 0
    assert set(wrong.values()) == {0}, wrong


def test_a_stale_answer_and_a_lost_block_fail_the_run():
    """`stale-lastpoint`: every answer held to the table one block behind
    its end; `lost-batch`: an acknowledgement booked for a block the
    server never got. Each part has to be off in its own numbers."""
    rc, out, text = run_control("--seconds", "4", "--perturb",
                                "stale-lastpoint+lost-batch")
    assert rc == 0, text[-3000:]
    assert out["result"]["correct"] is False
    assert out["parts_off"] == ["answers", "read_back"]
    compared = out["result"]["compared"]
    assert sum(compared[f"{name}.wrong_answers"]["value"]
               for name in Cell(CELL).mix["families"]) > 0
    assert compared["read_back.before_crash.count_max_abs_err"]["value"] != 0
    assert compared["read_back.after_restart.count_max_abs_err"]["value"] != 0
    assert compared["scan_cache.tail_merges"] == {"value": 0, "limit": 0}
