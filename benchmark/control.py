#!/usr/bin/env python3
"""The negative controls, at the cell's own size. Each has to come out as
NOT correct: the exit code is 0 when it does, 1 when the control passes.

A query cell: the float64 reference put in the program's place, computed
over bf16 mirrors of the columns (the nearest precision below the f32
mirrors the deployment states), with the statements the seed draws. It
needs no server and no chip. For every family it prints the number the
cell compares beside its limit.

A write cell (`"loop": "ingest"`) states no precision, so its control
breaks the guarantee its configuration states, durability of every
acknowledged row: a whole run on the chip with a short window, in which
the benchmark books one acknowledgement for a batch the server never got
(`--perturb lost-batch`, the default there).

A cell that reads while it writes (`"loop": "mixed"`) has both: without
`--perturb` the bf16 control of its families, and with `--perturb
stale-lastpoint+lost-batch` one whole run in which every `live` answer is
one tick older than what had been acknowledged when it was sent and one
acknowledged batch was never stored; each part has to fail its own
numbers.

    python3 benchmark/control.py --workload <cell> --seed <n> [--debug]
                    [--draws <k>] [--seconds <s>] [--perturb <a>[+<b>]]
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def control(workload: str, seed: int, debug: bool, draws: int) -> dict:
    from benchlib import check as chk
    from benchlib.loops import family_rng
    from benchlib.spec import Cell, load_family, load_generator
    cell = Cell(workload)
    size = cell.config["debug"] if debug else cell.config
    ds = load_generator(cell.config)(
        cell.config, seed, scale=size["scale"],
        ticks=size["duration_s"] // cell.config["log_interval_s"])
    mirror = copy.copy(ds)      # the same deployment over bf16 mirrors
    mirror.data = chk.bf16_round(ds.data.reshape(-1)).reshape(ds.data.shape)
    out = {}
    for name in cell.mix["families"]:
        fam = load_family(name)
        rng = family_rng(seed, name, "window")
        smallest = None
        for _ in range(draws):
            params = fam.draw(rng, ds)
            res = chk.compare(fam.reference(params, mirror),
                              fam.reference(params, ds), fam.tolerance)
            number, value, limit = chk.compared_number(res, fam.tolerance)
            smallest = value if smallest is None else min(smallest, value)
            if not fam.draw(rng, ds):       # no parameters: one draw is all
                break
        out[name] = {"number": number, "control_smallest": smallest,
                     "limit": limit, "fails": smallest > limit}
        print(f"control {workload} seed {seed} {name}: {number} "
              f"{smallest:.4g} (limit {limit:g}) -> "
              f"{'not correct' if smallest > limit else 'PASSES: no control'}",
              flush=True)
    return out


#: where a perturbation has to show among a run's compared numbers
SHOWS_IN = {"bf16-answers": "answers", "stale-lastpoint": "answers",
            "lost-batch": "read_back"}


def parts_off(compared: dict) -> set:
    """Which parts of a run's compared numbers are outside their limits:
    `answers` (a family's statements), `read_back` (a count that is off,
    or ticks that differ, which leaves no number)."""
    return {"read_back" if name.startswith("read_back.") else "answers"
            for name, c in compared.items()
            if c["value"] is None or c["value"] > c["limit"]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--debug", action="store_true",
                    help="the configuration's debug size")
    ap.add_argument("--draws", type=int, default=6)
    ap.add_argument("--seconds", type=float, default=10.0,
                    help="the window of a whole perturbed run")
    ap.add_argument("--perturb", default=None,
                    help="a whole run with the timed path's output broken")
    args = ap.parse_args()
    from benchlib.spec import Cell
    perturb = args.perturb or {"ingest": "lost-batch"}.get(
        Cell(args.workload).mix["loop"])
    if perturb:
        from benchlib.harness import run_cell
        result = run_cell(args.workload, args.seed, args.seconds, False,
                          "cpu" if args.debug else None, perturb=perturb)
        off = parts_off(result["compared"])
        print(json.dumps({"workload": args.workload, "seed": args.seed,
                          "control": perturb, "parts_off": sorted(off),
                          "result": result}))
        wanted = {SHOWS_IN[p] for p in perturb.split("+")}
        return 0 if not result["correct"] and wanted <= off else 1
    out = control(args.workload, args.seed, args.debug, args.draws)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "control": out}))
    return 0 if all(v["fails"] for v in out.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
