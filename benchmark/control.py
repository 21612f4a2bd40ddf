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
the benchmark books one acknowledgement for a batch the server never got.

    python3 benchmark/control.py --workload <cell> --seed <n>
                                 [--debug] [--draws <k>] [--seconds <s>]
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
    from benchlib.data import Dataset
    from benchlib.loops import family_rng
    from benchlib.spec import Cell, load_family
    cell = Cell(workload)
    size = cell.config["debug"] if debug else cell.config
    ds = Dataset(cell.config, seed, scale=size["scale"],
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


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--debug", action="store_true",
                    help="the configuration's debug size")
    ap.add_argument("--draws", type=int, default=6)
    ap.add_argument("--seconds", type=float, default=10.0,
                    help="the write cell's window")
    args = ap.parse_args()
    from benchlib.spec import Cell
    if Cell(args.workload).mix["loop"] == "ingest":
        from benchlib.harness import run_cell
        result = run_cell(args.workload, args.seed, args.seconds, False,
                          "cpu" if args.debug else None,
                          perturb="lost-batch")
        print(json.dumps({"workload": args.workload, "seed": args.seed,
                          "control": "lost-batch", "result": result}))
        return 1 if result["correct"] else 0
    out = control(args.workload, args.seed, args.debug, args.draws)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "control": out}))
    return 0 if all(v["fails"] for v in out.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
