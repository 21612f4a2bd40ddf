"""Shared arithmetic of the readers of a scan-cache refresh
(`layers/refresh_*.py`): the parts of the `scan_prep` row a refreshing
statement has, and a counter's delta a refresh. A program without those
rows and counters reads None everywhere here."""

from __future__ import annotations

from .layerlib import counter_delta
from .spanlib import span_ms

REFRESH_COUNTERS = ("greptime_scan_cache_incremental_total",
                    "greptime_scan_cache_miss_total")


def refresh_ms(rec: dict, *parts):
    """The named parts a refreshing statement has, summed; None for a
    statement without `scan_prep.delta` (it did not refresh)."""
    if span_ms(rec, "scan_prep.delta") is None:
        return None
    return sum(span_ms(rec, p) or 0.0 for p in parts)


def per_refresh(run: dict, counter: str):
    """The window's delta of `counter` over its refreshes (what
    `layers/cache_refreshes.py` counts); None without statements, without
    the counter, and in a window without a refresh."""
    counters = run.get("counters")
    if "statements" not in run or not counters \
            or counter not in counters["after"]:
        return None
    refreshes = sum(counter_delta(run, c) for c in REFRESH_COUNTERS)
    return counter_delta(run, counter) / refreshes if refreshes else None
