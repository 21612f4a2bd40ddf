"""Unit tests of what the `prom1k-dashboard` cell added (PR 28), each fed
synthetic records or the generator at a tiny size: no server, no chip,
seconds. (`selftest.py cells` and `controls` drive the whole cell at the
debug size: they go over every entry of `workloads`.)

    python3 -m pytest benchmark/test_prom.py -q

The six per-layer readers (rows / counter present -> the number; absent,
as on the parent program -> None, so the metric is left out of the line),
the entries of BENCHMARK.json, what the cell sends for a seed, and the
bf16 control of every family at the debug size.
"""

import hashlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

from benchlib import loops  # noqa: E402
from benchlib.spec import (Cell, load_family, load_generator,  # noqa: E402
                           load_layer_reader, load_loop)

CELL = "prom1k-dashboard"
T0 = 1_790_000_000_000_000_000


def stage(ms, start_ms, detail=""):
    return {"rows": 0, "elapsed_ms": float(ms),
            "detail": f"{detail}, t0_ns={T0 + int(start_ms * 1e6)}"}


def statement(family, scale, spans=True):
    """A traced TQL statement sent at T0: a 100 ms `total` from +2 ms
    with plan 1, select 50 (matrix 8), window 30 (upload 4, launch 6,
    fetch 20) and outer 18, render 25 ms after it, 130 ms at the client;
    all times `scale`d. Without `spans`: what the parent answers to
    EXPLAIN ANALYZE of a TQL statement (it runs nothing)."""
    def s(ms, start):
        return stage(ms * scale, start * scale)
    rec = {"family": family, "in_window": True, "ok": True,
           "client_ms": 130.0 * scale, "t_send_ns": T0,
           "t_done_ns": T0 + int(130e6 * scale)}
    if not spans:
        rec["stages"] = {
            "parse": stage(0.1, 0), "plan": stage(0.0, 1, "Tql"),
            "dispatch": {"rows": 0, "elapsed_ms": 0.0, "detail": "n/a"},
            "total": {"rows": 0, "elapsed_ms": 0.014, "detail": ""}}
        return rec
    rec["stages"] = {
        "parse": s(1, 0), "plan": s(1, 2),
        "dispatch": {"rows": 0, "elapsed_ms": 0.0,
                     "detail": "promql-row-path (window kernel on tpu)"},
        "select": s(50, 3), "select.scan": s(2, 3),
        "select.filter": s(25, 5), "select.labels": s(15, 30),
        "select.matrix": s(8, 45), "window": s(30, 53),
        "window.upload": s(4, 53), "window.launch": s(6, 57),
        "window.fetch": s(20, 63), "outer": s(18, 83),
        "total": stage(100 * scale, 2 * scale, "trace_id=ab"),
        "render": s(25, 102)}
    return rec


class FakeTrace:
    """10 ms of device time inside every statement."""
    planes = {"/device:TPU:0": []}

    @staticmethod
    def busy_ns_between(lo, hi):
        return 10e6


def traced_run(spans=True, counters=True, trace=True):
    run = {"statements": [statement("a", 1, spans), statement("b", 3, spans)],
           "device": {"device_kind": "TPU v5 lite"}}
    if counters:
        run["counters"] = {
            "before": {"greptime_promql_matrix_cells_total": 1e6},
            "after": {"greptime_promql_matrix_cells_total": 1e6 + 819e3}}
    else:
        run["counters"] = {"before": {}, "after": {}}
    if trace:
        run["trace"] = FakeTrace()
    return run


# mean over families of family means: family a at scale 1, b at scale 3
READERS = {
    "prom_select_ms": 2 * 50,
    "prom_matrix_ms": 2 * (8 + 4),
    "prom_launch_ms": 2 * 6,
    "prom_fetch_ms": 2 * 20,
    "prom_outer_ms": 2 * 18,
    # 819e3 cells x 8 B over 2 x 10 ms of device time, of 819 GB/s
    "window_kernels_roofline": 100 * 819e3 * 8 / 0.02 / 819e9,
    # and the accepted readers the cell joins
    "untimed_ms": 2 * (100 - 1 - 50 - 30 - 18),
    "wire_ms": 2 * (130 - 100 - 25),
    "render_ms": 2 * 25,
    "parse_ms": 2 * 1,
    "kernel_ms": 10.0,
}
NEW = [n for n in READERS if n.startswith(("prom_", "window_"))]


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_reads_its_rows(metric):
    assert load_layer_reader(metric)(traced_run()) == pytest.approx(
        READERS[metric])


@pytest.mark.parametrize("metric", NEW)
def test_reader_on_the_parent_program_reads_nothing(metric):
    """The parent answers four rows to EXPLAIN ANALYZE of a TQL statement
    and has no promql counter: nothing to read, and nothing raised."""
    assert load_layer_reader(metric)(
        traced_run(spans=False, counters=False)) is None


def test_roofline_needs_a_device_plane_and_the_counter():
    read = load_layer_reader("window_kernels_roofline")
    assert read(traced_run(trace=False)) is None
    assert read(traced_run(counters=False)) is None
    run = traced_run()
    run.pop("counters")
    assert read(run) is None


def test_the_cell_reports_what_its_entries_say():
    cell = Cell(CELL)
    assert cell.chips == 1 and cell.mix["loop"] == "statements"
    assert [m["name"] for m in cell.metrics("end_to_end")] == [
        "stmt_geomean_ms", "stmt_per_s", "setup_s"]
    layers = {m["name"]: m for m in cell.metrics("per_layer")}
    assert set(NEW) <= set(layers)
    assert {"kernel_ms", "render_ms", "wire_ms", "parse_ms", "untimed_ms",
            "warm_compile_s", "bulk_load_rows_per_s"} <= set(layers)
    for name in NEW:
        assert layers[name]["workloads"] == [CELL]
        assert layers[name]["moves"] == "stmt_geomean_ms"
    assert layers["window_kernels_roofline"]["unit"] == "%"
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        spec = json.load(f)
    (entry,) = [c for c in spec["configs"] if c["name"] == "prom-node-1k"]
    assert entry["source"] == cell.config["source"]
    assert len(entry["source"]) <= 200
    assert set(entry["reduced"]) == set(cell.config["reduced"])
    assert spec["workloads"][-1]["name"] == CELL


def debug_dataset(seed):
    config = Cell(CELL).config
    size = config["debug"]
    return load_generator(config)(
        config, seed, scale=size["scale"],
        ticks=size["duration_s"] // config["log_interval_s"])


class NoWire:
    """A Sender that reaches no server: warm statements answer nothing."""
    http = None

    def __init__(self, ctx):
        pass

    def send(self, via, sql):
        return [], []

    def close(self):
        pass


def sent(seed, monkeypatch) -> list:
    monkeypatch.setattr(loops, "Sender", NoWire)
    monkeypatch.setattr(loops, "log", lambda msg: None)
    cell = Cell(CELL)
    loop = load_loop("statements")(loops.Context(
        cell, debug_dataset(seed), None, {}, seed, False, True))
    loop.prepare()
    return [sql for _fam, _params, sql in loop.plan[:36]]


def test_a_seed_replays_the_same_statements(monkeypatch):
    a, b = sent(2147483659, monkeypatch), sent(2147483659, monkeypatch)
    assert a == b and a != sent(7, monkeypatch)
    assert len({s.split(") ", 1)[1] for s in a}) > 6   # drawn instances
    for sql in a:
        head, _ = sql.split(") ", 1)
        start, end, step = head[len("TQL EVAL ("):].split(", ")
        assert int(end) - int(start) == 900 and step == "'15s'"
        assert int(end) % 15 == 0
    digest = hashlib.sha256("\n".join(a).encode()).hexdigest()[:16]
    assert digest == SENT[2147483659], digest


#: recorded on the tree that added the cell (PR 28), with `sent`
SENT = {2147483659: "88e2fd85530d74e0"}


def test_families_state_the_chip_and_swap_the_debug_platform():
    ds = debug_dataset(7)
    for name in Cell(CELL).mix["families"]:
        fam = load_family(name)
        assert fam.dispatch == "promql-row-path (window kernel on tpu)"
        fam.draw(loops.family_rng(7, name, "window"), ds)
        assert fam.dispatch == "promql-row-path (window kernel on cpu)"
        assert fam.via == "http"


def test_the_bf16_control_fails_every_family():
    from control import control
    out = control(CELL, 77, True, 2)
    assert set(out) == set(Cell(CELL).mix["families"])
    for name, c in out.items():
        assert c["fails"] and c["control_smallest"] > 20 * c["limit"], (
            name, c)
