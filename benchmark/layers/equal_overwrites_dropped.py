"""Layer: prune / decode / merge. Rows that a refresh of the scan cache
found resident with the values they carry (a retried body) and dropped
before any merge or upload, inside the window: the delta of
`greptime_scan_cache_overwrites_total{kind="equal"}`
(`query/tpu_exec.py:_settle`). A count; None in a window without
statements and for a program without the counter. /metrics."""

from benchlib.layerlib import counter_delta

COUNTER = 'greptime_scan_cache_overwrites_total{kind="equal"}'


def read(run):
    counters = run.get("counters")
    if "statements" not in run or not counters \
            or COUNTER not in counters["after"]:
        return None
    return counter_delta(run, COUNTER)
