"""Unit tests of the benchmark's own pieces, each fed synthetic records:
no server, no chip, seconds. (`selftest.py` drives whole runs.)

    python3 -m pytest benchmark/test_benchmark.py -q

The per-layer readers added with the cell that reads while it writes
(rows / counters present -> the number; absent -> None, so the metric is
left out of the line), the lookups by name, the bounds of a live answer,
and that the four cells accepted before still send what they sent (a
digest recorded from the tree before the lookups: the first 40 statements
or the first 12 bodies, two seeds, the debug size).
"""

import hashlib
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

from benchlib import loops  # noqa: E402
from benchlib.spec import (Cell, load_family, load_generator,  # noqa: E402
                           load_layer_reader, load_loop)

T0 = 1_790_000_000_000_000_000


def stage(ms, start_ms, detail=""):
    return {"rows": 0, "elapsed_ms": float(ms),
            "detail": f"{detail}, t0_ns={T0 + int(start_ms * 1e6)}"}


def statement(family, scale, lag_ms=None):
    """A traced statement sent at T0: a 100 ms `total` from +2 ms with
    scan_prep 20 and reduce 60 (upload 3, launch 40, fetch 10), render
    30 ms after it, 140 ms at the client; all times `scale`d."""
    def s(ms, start):
        return stage(ms * scale, start * scale)
    rec = {"family": family, "in_window": True, "ok": True,
           "client_ms": 140.0 * scale, "t_send_ns": T0,
           "t_done_ns": T0 + int(140e6 * scale),
           "stages": {"plan": s(4, 2), "scan_prep": s(20, 6),
                      "reduce": s(60, 26), "reduce.runs": s(1, 26),
                      "reduce.mask": s(2, 27), "reduce.upload": s(3, 29),
                      "reduce.launch": s(40, 32), "reduce.fetch": s(10, 72),
                      "total": stage(100 * scale, 2 * scale),
                      "render": s(30, 102)}}
    if lag_ms is not None:
        rec["visible_lag_ms"] = lag_ms
    return rec


def mixed_run(spans=True, writes=True):
    """Two families at scale 1 and 3, and a write window of 100 batches."""
    run = {"statements": [statement("a", 1, 500.0), statement("b", 3)],
           "compiled_in_window": 2}
    if not spans:
        for rec in run["statements"]:
            rec["stages"] = {k: {**v, "detail": ""}
                             for k, v in rec["stages"].items()
                             if "." not in k and k != "render"}
            rec.pop("visible_lag_ms", None)
    if writes:
        route = '{route="/v1/influxdb/write"}'
        run["batches"] = [{"in_window": True, "ok": True, "ack_ms": ms}
                          for ms in (10.0, 30.0, 50.0)]
        run["counters"] = {
            "before": {"greptime_http_request_seconds_count" + route: 10.0,
                       "greptime_scan_cache_incremental_total": 4.0,
                       "greptime_ingest_parse_seconds_sum": 2.0,
                       "greptime_region_write_seconds_sum": 5.0},
            "after": {"greptime_http_request_seconds_count" + route: 110.0,
                      "greptime_scan_cache_incremental_total": 9.0,
                      "greptime_scan_cache_miss_total": 1.0,
                      "greptime_ingest_parse_seconds_sum": 52.0,
                      "greptime_region_write_seconds_sum": 105.0,
                      "greptime_flush_files_total": 2.0}}
    return run


# mean over families of family means: family a at scale 1, b at scale 3
READERS = {
    "launch_ms.live": 2 * 40,
    "mask_ms.live": 2 * (1 + 2 + 3),
    "scan_prep_ms.live": 2 * 20,
    "wire_ms": 2 * (140 - 100 - 30),
    "compiled_in_window": 2,
    "cache_refreshes": 5 + 1,
    "visible_lag_ms": 500.0,
    "batch_ack_ms.live": 30.0,
    "ingest_parse_ms.live": 500.0,
    "region_write_ms.live": 1000.0,
    "flushes_in_window.live": 2.0,
    "write_stalls.live": 0.0,
}
NEED_SPANS = ["launch_ms.live", "mask_ms.live", "visible_lag_ms"]
NEED_WRITES = ["batch_ack_ms.live", "ingest_parse_ms.live",
               "region_write_ms.live", "flushes_in_window.live",
               "write_stalls.live"]


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_reads_its_rows(metric):
    assert load_layer_reader(metric)(mixed_run()) == pytest.approx(
        READERS[metric])


@pytest.mark.parametrize("metric", NEED_SPANS)
def test_reader_without_spans_reads_nothing(metric):
    assert load_layer_reader(metric)(mixed_run(spans=False)) is None


@pytest.mark.parametrize("metric", NEED_WRITES + ["cache_refreshes"])
def test_reader_outside_a_write_window_reads_nothing(metric):
    assert load_layer_reader(metric)(mixed_run(writes=False)) is None


def test_wire_ms_of_a_program_without_a_render_row_is_what_it_was():
    run = mixed_run(spans=False)
    assert load_layer_reader("wire_ms")(run) == pytest.approx(2 * 40)


def test_every_per_layer_entry_has_its_reader():
    """In BENCHMARK.json and in every queued cell's file; a queued cell's
    entries name only that cell, and its end-to-end metrics are what its
    mix reports."""
    import glob
    import json
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"] for w in spec["workloads"]}
    names = set()
    for m in spec["per_layer"]:
        assert callable(load_layer_reader(m["name"])), m["name"]
        assert set(m["workloads"]) <= cells
        names.add(m["name"])
    assert not any(n.startswith("dispatch_ms") for n in names)
    for path in glob.glob(os.path.join(HERE, "queued", "*.json")):
        with open(path) as f:
            queued = json.load(f)
        (cell,) = queued["workloads"]
        assert cell["name"] not in cells
        with open(os.path.join(HERE, "traffic",
                               cell["traffic"] + ".json")) as f:
            reports = json.load(f)["reports"]
        assert {m["name"] for m in queued["end_to_end"]} == set(
            reports.values())
        for m in queued["per_layer"] + queued["end_to_end"]:
            assert m["workloads"] == [cell["name"]]
        for m in queued["per_layer"]:
            assert callable(load_layer_reader(m["name"])), m["name"]
            assert m["moves"] in reports.values()
            names.add(m["name"])
    assert set(READERS) <= names


# ---- lookups by name ------------------------------------------------------

def test_loop_kinds_and_generators_are_found_by_name():
    assert load_loop("statements") is loops.StatementLoop
    assert load_loop("ingest") is loops.IngestLoop
    assert load_loop("mixed").__name__ == "MixedLoop"
    with pytest.raises(FileNotFoundError):
        load_loop("no-such-kind")
    from benchlib.data import Dataset
    assert load_generator({}) is Dataset
    with pytest.raises(FileNotFoundError):
        load_generator({"generator": "no-such-generator"})


# ---- what the accepted cells send -----------------------------------------

class NoWire:
    """A Sender that reaches no server: warm statements answer nothing."""
    http = None

    def __init__(self, ctx):
        pass

    def send(self, via, sql):
        return [], []

    def close(self):
        pass


#: recorded from the parent of the PR that added `load_loop` and
#: `load_generator` (PR 26), with this same function
SENT_BEFORE = {
    "tsbs4k-scan/7": "2f850ea17876a8d0",
    "tsbs4k-scan/2147483659": "2f850ea17876a8d0",
    "tsbs4k-point/7": "eb1c59969b628ee1",
    "tsbs4k-point/2147483659": "b8bda45fab7d0bb5",
    "tsbs100k-groupby/7": "ba6469ae6fbac8e0",
    "tsbs100k-groupby/2147483659": "ba6469ae6fbac8e0",
    "tsbs4k-ingest/7": "d5966ca35d982150",
    "tsbs4k-ingest/2147483659": "c3dc6e7a64c284da",
}


def debug_dataset(cell, seed):
    config, mix = cell.config, cell.mix
    size = config["debug"]
    extra = int(mix.get("debug_extra_ticks", mix.get("extra_ticks", 0)))
    return load_generator(config)(
        config, seed, extra_ticks=extra, scale=size["scale"],
        ticks=size["duration_s"] // config["log_interval_s"])


def sent_digest(workload, seed, monkeypatch):
    cell = Cell(workload)
    ds = debug_dataset(cell, seed)
    h = hashlib.sha256()
    if cell.mix["loop"] == "statements":
        monkeypatch.setattr(loops, "Sender", NoWire)
        monkeypatch.setattr(loops, "log", lambda msg: None)
        loop = load_loop("statements")(
            loops.Context(cell, ds, None, {}, seed, False, False))
        loop.prepare()
        for _fam, _params, sql in loop.plan[:40]:
            h.update(sql.encode() + b"\n")
    else:
        batches = ds.line_protocol_batches(int(cell.mix["batch_rows"]))
        for body, first, rows in batches[:12]:
            h.update(body + f"\n{first} {rows}\n".encode())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("key", sorted(SENT_BEFORE))
def test_an_accepted_cell_sends_what_it_sent(key, monkeypatch):
    workload, seed = key.split("/")
    assert sent_digest(workload, int(seed), monkeypatch) == SENT_BEFORE[key]


# ---- the bounds of a live answer -------------------------------------------

class FakeDataset:
    """4 hosts, 3 loaded ticks, 5 written: host h at tick t holds
    10 t + h, so an answer names its tick."""
    hosts, ticks, extra_ticks = 4, 3, 5
    rows = 12
    hostnames = [f"host_{h}" for h in range(4)]
    data = (10.0 * np.arange(8)[:, None, None]
            + np.arange(4)[None, :, None] + np.zeros((1, 1, 2)))


def batch(i, t_send, t_ack, error=None):
    """Batches of 2 rows in file order: batch i holds rows 2i, 2i + 1."""
    return {"i": i, "first_row": 2 * i, "rows": 2, "error": error,
            "t_send_ns": T0 + t_send, "t_ack_ns": T0 + t_ack}


def mixed_loop(records, perturb=None, traced=False):
    class Cell_:
        mix = {"batch_rows": 2, "families": []}
    ctx = loops.Context(Cell_, FakeDataset, None, {}, 1, traced, False,
                        perturb)
    loop = load_loop("mixed")(ctx)
    loop.writers.records = records
    return loop


def answer(ticks):
    return {f"host_{h}": [10.0 * k + h] for h, k in enumerate(ticks)}


def test_newest_tick_per_host():
    loop = mixed_loop([])
    assert loop._newest_tick([]).tolist() == [2, 2, 2, 2]
    # batches 0, 1 are tick 3 (hosts 0-1, 2-3), batch 2 tick 4 (hosts 0-1)
    got = loop._newest_tick([batch(0, 0, 1), batch(1, 0, 1), batch(2, 2, 3)])
    assert got.tolist() == [4, 4, 3, 3]


@pytest.mark.parametrize("ticks, ok", [
    ([4, 4, 3, 3], True),       # all that was acknowledged before the send
    ([4, 4, 4, 3], True),       # host 2's next row: sent before the answer
    ([3, 4, 3, 3], False),      # host 0 a tick older than acknowledged
    ([4, 4, 5, 3], False),      # host 2 a row that no body had carried yet
])
def test_a_live_answer_lies_between_acknowledged_and_sent(ticks, ok):
    fam = load_family("lastpoint-live")
    k_lo, k_hi = np.array([4, 4, 3, 3]), np.array([4, 4, 4, 3])
    matched = fam.match(answer(ticks), FakeDataset, k_lo, k_hi)
    want = fam.reference({"ticks": tuple(matched.tolist())}, FakeDataset)
    from benchlib.check import compare
    res = compare(answer(ticks), want, fam.tolerance)
    assert res["ok"] is ok, res
    assert ((k_lo <= matched) & (matched <= k_hi)).all()


def test_visible_lag_is_counted_from_the_oldest_shown_row():
    records = [batch(0, 100, 200), batch(1, 300, 400), batch(2, 500, 600)]
    loop = mixed_loop(records)
    rec = {"t_send_ns": T0 + 2_000_000}
    # hosts 2, 3 show tick 3 from batch 1 (sent at +300 ns), hosts 0, 1
    # tick 4 from batch 2: the oldest shown row went out with batch 1
    loop._lag(rec, np.array([4, 4, 3, 3]))
    assert rec["visible_lag_ms"] == pytest.approx((2_000_000 - 300) / 1e6)
    rec = {"t_send_ns": T0}
    loop._lag(rec, np.array([2, 2, 2, 2]))     # only loaded rows shown
    assert "visible_lag_ms" not in rec
