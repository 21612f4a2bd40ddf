"""Shared arithmetic of the per-layer readers under `benchmark/layers/`."""

from __future__ import annotations

import statistics


def window_statements(run: dict) -> list:
    return [r for r in run.get("statements", ())
            if r.get("in_window") and r.get("ok") and r.get("stages")]


def stage_ms(rec: dict, *stages) -> float:
    return sum(rec["stages"].get(s, {}).get("elapsed_ms", 0.0)
               for s in stages)


def device_ms(run: dict, rec: dict):
    """The device time inside one statement, from the trace."""
    trace = run.get("trace")
    if trace is None or not trace.planes:
        return None
    return trace.busy_ns_between(rec["t_send_ns"], rec["t_done_ns"]) / 1e6


def mean_of_family_means(run: dict, value_of, families=None):
    """Per statement value_of(rec) -> number or None; the mean within a
    family, then the plain mean over families (every family has the same
    count), so that the layers of one cell add up to its mean latency.
    None when nothing was read."""
    by_family = {}
    for rec in window_statements(run):
        if families is not None and rec["family"] not in families:
            continue
        v = value_of(rec)
        if v is not None:
            by_family.setdefault(rec["family"], []).append(v)
    if not by_family:
        return None
    return statistics.fmean(statistics.fmean(v) for v in by_family.values())


def counter_delta(run: dict, name: str):
    c = run.get("counters")
    if not c:
        return None
    return c["after"].get(name, 0.0) - c["before"].get(name, 0.0)
