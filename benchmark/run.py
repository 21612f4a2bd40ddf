#!/usr/bin/env python3
"""The benchmark's command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s>
                             --trace <0|1>

One run of one cell of BENCHMARK.json on the machine it is started on.
This process never imports jax: it starts ONE `standalone start` child
that owns the chip, refuses to go on unless that child reports a TPU with
as many chips as the cell asks for, makes the data from --seed, loads it,
warms only the cell's own statements (all of that is `setup_s`), measures
for --seconds, checks the answers outside the window, stops the server and
prints the result as the last line of its standard output (its last key,
`compared`, holds every number the check compared beside its limit; the
same go out as the last lines of standard error). With --trace 1
the window is also the profiler's window and the metrics are the cell's
per-layer metrics.

Exit codes: 0 a result was printed; 2 not a checkout of the program, or a
bad argument; 3 no chip (or a --debug-platform run, which prints its line
for the selftest but is never a result); 1 anything else.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--debug-platform", default=None,
        help="drive every phase against this JAX platform at the "
             "configuration's debug size (cpu, for the selftest); the line "
             "it prints names that platform and the exit code is 3")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "greptimedb_tpu")):
        print("benchmark: no greptimedb_tpu/ beside benchmark/: this is "
              "not a checkout of the program", file=sys.stderr)
        return 2
    sys.path.insert(0, HERE)
    from benchlib.harness import NoChip, run_cell
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), args.debug_platform)
    except NoChip as e:
        print(e, file=sys.stderr)
        return 3
    if "jax" in sys.modules:
        raise AssertionError("the benchmark's parent imported jax")
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 3 if args.debug_platform else 0


if __name__ == "__main__":
    sys.exit(main())
