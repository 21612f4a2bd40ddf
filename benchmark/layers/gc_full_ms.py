"""Layer: process start, compile cache. The interpreter's full
(generation-2) collections inside the window, in ms: the delta of
`greptime_gc_full_collection_seconds_sum`
(`common/telemetry.py:install_gc_timer`). Every thread of the server
stands still for one, so a statement and the acknowledgements around it
are late by it together. 0 in a window without one; None for a program
without the timer and outside a window. /metrics."""

from benchlib.layerlib import counter_delta

NAME = "greptime_gc_full_collection_seconds_sum"


def read(run):
    counters = run.get("counters")
    if not counters or NAME not in counters["after"]:
        return None
    return counter_delta(run, NAME) * 1e3
