#!/usr/bin/env python3
"""A traced run of one cell, with the device's idle time laid against the
program's own stage rows.

    python3 benchmark/stage_idle.py --workload <name> --seed <n>
                                    --seconds <s>

The same run as `run.py --trace 1` (same harness, same result line), plus
`breakdown.idle_by_stage_row`: every idle interval of the first device
plane inside the window, cut at the borders of the statements and of
their EXPLAIN ANALYZE rows (`t0_ns=` + elapsed, the wall clock the trace
is anchored to) and summed under the innermost row that covers it —
`reduce.fetch`, `project.sort`, `render` ... — or under
`statement_outside_rows` (the wire, the event loop, what lies between
`total` and `render`) or `between_statements`. With it go
`breakdown.family_stage_ms` (per family, the mean of every row) and
`breakdown.stop_trace_s`.

`benchlib/trace.py:idle_gaps` labels the same gaps by the statement
family the client had in flight; a later benchmark issue is to fold this
labelling into it. Until then this command is how `PERF.md` section 5 is
taken. A program without `t0_ns` rows puts every gap inside a statement
under `statement_outside_rows`.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from benchlib import harness  # noqa: E402
from benchlib.layerlib import window_statements  # noqa: E402
from benchlib.spanlib import interval_ns  # noqa: E402


def idle_by_stage_row(run: dict) -> list:
    """[[label, idle seconds], ...], largest first; the seconds add up to
    the window's idle time."""
    trace = run.get("trace")
    if trace is None or not trace.planes:
        return []
    busy = next(iter(trace.planes.values()))
    gaps, at = [], trace.lo
    for a, b in busy:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if trace.hi > at:
        gaps.append((at, trace.hi))
    statements = []
    for rec in sorted(window_statements(run), key=lambda r: r["t_send_ns"]):
        rows = []
        for name in rec["stages"]:
            found = interval_ns(rec, name)
            if found is not None:
                rows.append((found[0] - trace.offset,
                             found[1] - trace.offset, name))
        statements.append((rec["t_send_ns"] - trace.offset,
                           rec["t_done_ns"] - trace.offset, rows))
    totals = {}

    def add(label, ns):
        if ns > 0:
            totals[label] = totals.get(label, 0) + ns

    for glo, ghi in gaps:
        at = glo
        for slo, shi, rows in statements:
            if shi <= at or slo >= ghi:
                continue
            add("between_statements", slo - at)
            lo, hi = max(at, slo), min(ghi, shi)
            cuts = sorted({lo, hi} | {t for a, b, _ in rows
                                      for t in (a, b) if lo < t < hi})
            for a, b in zip(cuts, cuts[1:]):
                inside = [(rb - ra, name) for ra, rb, name in rows
                          if ra <= a and b <= rb]
                add(min(inside)[1] if inside else "statement_outside_rows",
                    b - a)
            at = hi
        add("between_statements", ghi - at)
    ranked = sorted(totals.items(), key=lambda kv: (-kv[1], kv[0]))
    return [[name, ns / 1e9] for name, ns in ranked]


def family_stage_ms(run: dict) -> dict:
    """{family: {row: mean elapsed ms, "client_ms": ...}} of the window."""
    by_family = {}
    for rec in window_statements(run):
        rows = by_family.setdefault(rec["family"], {})
        rows.setdefault("client_ms", []).append(rec["client_ms"])
        for name, row in rec["stages"].items():
            rows.setdefault(name, []).append(row["elapsed_ms"])
    return {family: {name: statistics.fmean(v) for name, v in rows.items()}
            for family, rows in sorted(by_family.items())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--debug-platform", default=None)
    args = ap.parse_args(argv)
    save_record = harness.save_record

    def save(work, run, result):
        marks = run.get("trace_marks")
        result["breakdown"].update(
            idle_by_stage_row=idle_by_stage_row(run),
            family_stage_ms=family_stage_ms(run),
            stop_trace_s=(marks[1]["stopped_ns"] - marks[1]["call_ns"])
            / 1e9 if marks else None)
        save_record(work, run, result)

    harness.save_record = save
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  True, args.debug_platform)
    except harness.NoChip as e:
        print(e, file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 3 if args.debug_platform else 0


if __name__ == "__main__":
    sys.exit(main())
