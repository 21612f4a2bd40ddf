#!/usr/bin/env python3
"""The benchmark's own tests. They need no chip: every cell is driven end
to end on the CPU backend at its configuration's debug size, behind the
explicit `--debug-platform cpu` that the real command never assumes.

    python3 benchmark/selftest.py            # all of them, a few minutes
    python3 benchmark/selftest.py trace      # one by name
    python3 -m pytest benchmark/selftest.py  # the same, under pytest

What each shows is in its docstring. They are not part of the repo's
tier-1 tests (those live in tests/, which a benchmark PR may not touch).
"""

from __future__ import annotations

import contextlib
import glob
import json
import os
import shutil
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

SECONDS = 3
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "compared"}
#: what must turn `correct` false, by the mix's loop kind: the timed
#: path's output broken on the benchmark's side, one perturbation a run
PERTURBATIONS = {"statements": ["bf16-answers"], "ingest": ["lost-batch"],
                 "mixed": ["stale-lastpoint", "lost-batch"]}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def run_command(args, cwd=ROOT, env=None):
    """The command as the driver starts it -> (exit code, stdout lines)."""
    proc = subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                          text=True, timeout=900)
    return proc.returncode, proc.stdout.splitlines()


def parse_result_line(line: str, cell, traced: bool) -> dict:
    """The contract's last line, strictly: the keys, their types, and
    exactly the metrics this cell reports."""
    result = json.loads(line)
    extra = {"breakdown"} if traced else set()
    assert set(result) - extra == RESULT_KEYS, sorted(result)
    assert list(result)[-1] == "compared" and result["compared"]
    for name, c in result["compared"].items():
        assert set(c) == {"value", "limit"}, (name, c)
    assert isinstance(result["correct"], bool)
    assert isinstance(result["attempted"], int) and result["attempted"] > 0
    assert isinstance(result["failed"], int)
    device = result["device"]
    more = {"busy_s", "window_s"} if traced else set()
    assert set(device) == DEVICE_KEYS | more, sorted(device)
    section = "per_layer" if traced else "end_to_end"
    wanted = {m["name"]: m["unit"] for m in cell.metrics(section)}
    for name, m in result["metrics"].items():
        assert set(m) == {"value", "unit"}, (name, m)
        assert m["unit"] == wanted[name], (name, m)
        assert isinstance(m["value"], (int, float)), (name, m)
    if traced:
        # a reader that finds nothing returns nothing: the CPU backend has
        # no device plane, so the trace's metrics are left out
        assert set(result["metrics"]) <= set(wanted)
        assert result["metrics"], "no per-layer metric at all"
        for key in ("device_ops", "idle_gaps"):
            assert len(result["breakdown"][key]) <= 10
    else:
        assert set(result["metrics"]) == set(wanted), sorted(
            result["metrics"])
        assert all(m["value"] > 0 for m in result["metrics"].values())
    return result


def benchmark_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def workloads() -> list:
    return [w["name"] for w in benchmark_json()["workloads"]]


def queued_cells() -> dict:
    """benchmark/queued/<cell>.json: a cell kept for later with every
    entry of BENCHMARK.json it needs -> {cell name: BENCHMARK.json with
    those entries merged in} (a bound not set yet reads 0.25)."""
    out = {}
    for path in sorted(glob.glob(os.path.join(HERE, "queued", "*.json"))):
        with open(path) as f:
            queued = json.load(f)
        spec = benchmark_json()
        spec["workloads"] += queued["workloads"]
        spec["end_to_end"][-1:-1] = [       # `setup_s` stays last
            dict(m, bound=m["bound"] or 0.25) for m in queued["end_to_end"]]
        spec["per_layer"] += queued["per_layer"]
        for w in queued["workloads"]:
            out[w["name"]] = spec
    return out


@contextlib.contextmanager
def copy_of_the_checkout(spec: dict = None):
    """-> a temporary checkout: benchmark/ copied, the program linked,
    `spec` as its BENCHMARK.json (or left for the caller to write)."""
    work = os.path.join(ROOT, ".bench_work")
    os.makedirs(work, exist_ok=True)
    with tempfile.TemporaryDirectory(dir=work, prefix="copy_") as tmp:
        shutil.copytree(HERE, os.path.join(tmp, "benchmark"),
                        ignore=shutil.ignore_patterns("__pycache__"))
        os.symlink(os.path.join(ROOT, "greptimedb_tpu"),
                   os.path.join(tmp, "greptimedb_tpu"))
        if spec is not None:
            write_spec(tmp, spec)
        yield tmp


def write_spec(checkout: str, spec: dict) -> None:
    with open(os.path.join(checkout, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)


def test_cells_end_to_end():
    """Every cell, --trace 0 and --trace 1, through the real command line:
    exit code 3 (a debug run is never a result), `correct` true, the last
    line strict. A queued cell runs in a copy of the checkout whose
    BENCHMARK.json has its entries."""
    from benchlib.spec import Cell

    def drive(name, root):
        for traced in (0, 1):
            rc, out = run_command(
                ["benchmark/run.py", "--workload", name, "--seed",
                 "2147483659", "--seconds", str(SECONDS), "--trace",
                 str(traced), "--debug-platform", "cpu"], cwd=root)
            assert rc == 3, (name, traced, rc, out[-15:])
            result = parse_result_line(out[-1], Cell(name, root),
                                       bool(traced))
            assert result["correct"] is True, (name, traced, out[-15:])
            assert result["failed"] == 0
            assert result["device"]["platform"] == "cpu"
            print(f"ok: {name} --trace {traced}: {out[-1][:160]}")

    for name in workloads():
        drive(name, ROOT)
    for name, spec in queued_cells().items():
        with copy_of_the_checkout(spec) as tmp:
            drive(name, tmp)


def test_refuses_without_a_chip():
    """The real command (no --debug-platform) on a machine without a TPU
    exits non-zero and prints no result line."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    rc, out = run_command(["benchmark/run.py", "--workload", workloads()[0],
                           "--seed", "1", "--seconds", "1", "--trace", "0"],
                          env=env)
    assert rc != 0, out[-5:]
    for line in out:
        assert not line.startswith('{"correct"'), line
    print(f"ok: no chip -> exit {rc}, no result line")


def test_refuses_outside_a_checkout():
    """In a directory that holds only BENCHMARK.json and benchmark/ the
    command exits non-zero and prints no result."""
    with tempfile.TemporaryDirectory() as tmp:
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp)
        shutil.copytree(HERE, os.path.join(tmp, "benchmark"),
                        ignore=shutil.ignore_patterns("__pycache__"))
        rc, out = run_command(
            ["benchmark/run.py", "--workload", workloads()[0], "--seed",
             "1", "--seconds", "1", "--trace", "0"], cwd=tmp)
        assert rc != 0 and not any(
            line.startswith('{"correct"') for line in out), (rc, out)
    print(f"ok: bare directory -> exit {rc}, no result line")


def test_trace():
    """The trace reduction against a small trace recorded on a TPU v5e:
    the busy union by an independent count, each statement's device time
    against its XLA Modules event, the gap labels."""
    from benchlib.trace import DeviceTrace
    with open(os.path.join(HERE, "fixtures", "trace_small.json")) as f:
        fx = json.load(f)
    spans = [tuple(s) for s in fx["spans"]]
    trace = DeviceTrace(fx["events"], fx["anchor_wall_ns"],
                        tuple(fx["window_wall_ns"]), spans)
    plane = fx["events"]["planes"]["/device:TPU:0"]
    # independent busy count: mark every covered ns boundary in a set of
    # elementary segments between all event endpoints
    events = [(s, s + d) for line in ("XLA Ops", "Async XLA Ops")
              for _n, s, d in plane[line]]
    points = sorted({p for e in events for p in e})
    starts = sorted(a for a, _ in events)
    ends = sorted(b for _, b in events)
    busy, i, j, depth = 0, 0, 0, 0
    for k, p in enumerate(points[:-1]):
        while i < len(starts) and starts[i] <= p:
            depth, i = depth + 1, i + 1
        while j < len(ends) and ends[j] <= p:
            depth, j = depth - 1, j + 1
        if depth > 0:
            busy += points[k + 1] - p
    assert abs(trace.busy_s - busy / 1e9) < 1e-12, (trace.busy_s, busy)
    assert 0 < trace.busy_s < trace.window_s
    # one jit program per statement: its XLA Modules event spans the ops
    offset = fx["anchor_wall_ns"] - fx["events"]["anchor_ns"]
    modules = plane["XLA Modules"]
    assert len(modules) == len(spans) == 3
    for (label, a, b), (_name, start, dur) in zip(spans, modules):
        assert a < start + offset < b, label
        got = trace.busy_ns_between(a, b)
        assert 0.98 * dur <= got <= dur, (label, got, dur)
    gaps = dict(trace.idle_gaps(top=100))
    assert abs(sum(gaps.values()) + trace.busy_s - trace.window_s) < 1e-9
    assert gaps["between_statements"] > 0
    for label, _a, _b in spans:
        assert gaps[f"{label}:after_last_device_op"] > 0
    top = trace.device_ops()
    assert len(top) == 10 and top[0][1] >= top[-1][1] > 0
    print(f"ok: trace fixture: busy {trace.busy_s * 1e3:.3f} ms of "
          f"{trace.window_s * 1e3:.1f} ms; top op {top[0]}")


def test_peaks():
    """An unknown device kind is an error, not a default."""
    from benchlib.peaks import peak_of
    assert peak_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    try:
        peak_of("TPU v9 imaginary")
    except KeyError:
        print("ok: unknown device kind refused")
    else:
        raise AssertionError("an unknown device kind got a peak")


def test_negative_controls():
    """The timed path's output broken on the benchmark's side: every query
    cell with its answers rounded to bf16 before the comparison, the
    ingest cell with an acknowledgement for rows that were never stored,
    the cell that reads while it writes with every `lastpoint-live` answer
    one tick older than what had been acknowledged when it was sent, and
    again with the lost batch. The rest of the run is driven as always;
    `correct` must come out false, and the numbers outside their limits
    must be the perturbed part's. And the reference itself over bf16
    mirrors (control.py) fails every family."""
    from benchlib.harness import run_cell
    from benchlib.spec import Cell
    from control import SHOWS_IN, control, parts_off
    for name in workloads():
        mix = Cell(name).mix
        for perturb in PERTURBATIONS[mix["loop"]]:
            result = run_cell(name, 77, SECONDS, False, "cpu",
                              perturb=perturb)
            assert result["correct"] is False, (name, perturb, result)
            assert SHOWS_IN[perturb] in parts_off(result["compared"]), (
                name, perturb, result["compared"])
            print(f"ok: {name} with {perturb} -> correct false")
        if "families" in mix:
            out = control(name, 77, True, 3)
            assert all(v["fails"] for v in out.values()), out
    for name, spec in queued_cells().items():
        with copy_of_the_checkout(spec) as tmp:
            mix = Cell(name, tmp).mix
            for perturb in PERTURBATIONS[mix["loop"]] + [None]:
                # control.py exits 0 when its control comes out as not
                # correct, in the perturbed part (None: the bf16 mirrors)
                rc, out = run_command(
                    ["benchmark/control.py", "--workload", name, "--seed",
                     "77", "--debug", "--seconds", str(SECONDS)]
                    + (["--perturb", perturb] if perturb else []), cwd=tmp)
                assert rc == 0, (name, perturb, rc, out[-15:])
                print(f"ok: {name} with {perturb or 'bf16 mirrors'} -> "
                      "not correct")


def test_extensible():
    """A configuration, a generator, a traffic mix, a loop kind, a family
    and a per-layer metric, each added as new files plus new entries of
    BENCHMARK.json, in a temporary copy; no file that was there is
    edited."""
    with copy_of_the_checkout() as tmp:
        bench = os.path.join(tmp, "benchmark")
        spec = benchmark_json()
        with open(os.path.join(bench, "configs", "tsbs-cpu-4000.json")) as f:
            config = json.load(f)
        config["name"] = "throwaway-config"
        config["debug"]["scale"] = 300
        config["generator"] = "throwaway-generator"
        os.makedirs(os.path.join(bench, "generators"), exist_ok=True)
        with open(os.path.join(bench, "generators",
                               "throwaway-generator.py"), "w") as f:
            f.write("from benchlib.data import Dataset as Tsbs\n\n\n"
                    "class Dataset(Tsbs):\n"
                    "    def __init__(self, config, seed, **size):\n"
                    "        super().__init__(config, seed, **size)\n"
                    "        self.table = 'cpu_of_a_generator'\n")
        with open(os.path.join(bench, "loops", "throwaway-loop.py"),
                  "w") as f:
            f.write("from benchlib.loops import StatementLoop\n\n\n"
                    "class LOOP(StatementLoop):\n"
                    "    def prepare(self):\n"
                    "        super().prepare()\n"
                    "        self.ctx.run['table'] = self.ctx.ds.table\n")
        with open(os.path.join(bench, "configs", "throwaway-config.json"),
                  "w") as f:
            json.dump(config, f)
        with open(os.path.join(bench, "families", "throwaway-family.py"),
                  "w") as f:
            f.write("from benchlib.tsbs import SingleGroupby\n\n"
                    "FAMILY = SingleGroupby('throwaway-family', 2, 3, 1, "
                    "'mysql')\n")
        with open(os.path.join(bench, "traffic", "throwaway-mix.json"),
                  "w") as f:
            json.dump({"loop": "throwaway-loop", "clients": 1,
                       "prime": "lastpoint",
                       "families": ["throwaway-family", "lastpoint"],
                       "warm_statements": 2, "max_statements": 2000}, f)
        with open(os.path.join(bench, "layers", "throwaway_count.py"),
                  "w") as f:
            f.write("def read(run):\n"
                    "    if run.get('table') != 'cpu_of_a_generator':\n"
                    "        return None\n"
                    "    return len(run.get('statements', ()))\n")
        spec["configs"].append({
            "name": "throwaway-config", "source": config["source"],
            "file": "benchmark/configs/throwaway-config.json",
            "reduced": ["duration_s"], "why": "selftest"})
        spec["workloads"].append({
            "name": "throwaway-cell", "config": "throwaway-config",
            "traffic": "throwaway-mix", "chips": 1, "why": "selftest"})
        for m in spec["end_to_end"]:
            if m["name"] in ("stmt_geomean_ms", "stmt_per_s"):
                m["workloads"].append("throwaway-cell")
        spec["per_layer"].append({
            "name": "throwaway_count", "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "protocol servers",
            "moves": "stmt_per_s", "workloads": ["throwaway-cell"]})
        write_spec(tmp, spec)
        for traced in (0, 1):
            rc, out = run_command(
                ["benchmark/run.py", "--workload", "throwaway-cell",
                 "--seed", "5", "--seconds", "2", "--trace", str(traced),
                 "--debug-platform", "cpu"], cwd=tmp)
            assert rc == 3, out[-15:]
            result = json.loads(out[-1])
            assert result["correct"] is True, out[-15:]
        assert result["metrics"]["throwaway_count"]["value"] > 0
        print("ok: a throw-away config, generator, mix, loop kind, family "
              "and per-layer metric ran as files of their own: "
              f"{sorted(result['metrics'])}")


TESTS = {"cells": test_cells_end_to_end,
         "nochip": test_refuses_without_a_chip,
         "bare": test_refuses_outside_a_checkout,
         "trace": test_trace, "peaks": test_peaks,
         "controls": test_negative_controls,
         "extensible": test_extensible}


def main() -> int:
    names = sys.argv[1:] or list(TESTS)
    for name in names:
        print(f"---- {name}", flush=True)
        TESTS[name]()
    print("selftest: all passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
