"""Layer: process start, compile cache (the interpreter every layer
shares). What a statement's thread stood off a processor for without
waiting for the device: over the `total` and `render` rows, elapsed less
the thread's CPU time (`cpu_ms=` in a row's detail,
`common/exec_stats.py:Timed`), less the same over the rows inside them
that sleep until the device answers (`DEVICE_WAITS`). What is left is the
wait for the interpreter lock and the scheduler. Mean over families of
family means; None where `total` or `render` carries no `cpu_ms` (a
program without it). EXPLAIN ANALYZE."""

import re

from benchlib.layerlib import mean_of_family_means

_CPU = re.compile(r"\bcpu_ms=([0-9.]+)")

#: rows whose thread is asleep while the device works or a transfer runs
DEVICE_WAITS = ("reduce.fetch", "reduce.launch", "scan_prep.upload",
                "window.fetch", "window.upload", "window.launch")


def off_cpu_ms(rec: dict, stage: str):
    """A row's elapsed time less its thread's CPU time, or None."""
    row = rec["stages"].get(stage)
    found = row and _CPU.search(row.get("detail") or "")
    return row["elapsed_ms"] - float(found.group(1)) if found else None


def read(run):
    def value(rec):
        whole = [off_cpu_ms(rec, s) for s in ("total", "render")]
        if None in whole:
            return None
        waits = [off_cpu_ms(rec, s) for s in DEVICE_WAITS
                 if s in rec["stages"]]
        if None in waits:
            return None
        return sum(whole) - sum(waits)
    return mean_of_family_means(run, value)
