"""Layer: protocol servers. How late the HTTP server's event loop runs a
call it was asked to make at a given time: the mean of
`greptime_event_loop_lag_seconds` over the window (a tick every 100 ms,
`servers/http.py:HttpServer._run`), in ms. What a request waits before
the middleware sees it, and what a long step on the loop's thread costs
every other connection. None for a program without the series.
/metrics."""

from benchlib.layerlib import counter_delta

SERIES = "greptime_event_loop_lag_seconds_{}"


def read(run):
    counters = run.get("counters")
    if not counters or SERIES.format("sum") not in counters["after"]:
        return None
    ticks = counter_delta(run, SERIES.format("count"))
    if not ticks:
        return None
    return counter_delta(run, SERIES.format("sum")) / ticks * 1e3
