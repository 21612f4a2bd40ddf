"""Layer: prune / decode / merge. Rows that a refresh of the scan cache
placed at or before their series' last resident row inside the window
(late rows: a relay's queue drained behind the live ticks) without a
merge of the table: the delta of `greptime_scan_cache_late_rows_total`
(`query/tpu_exec.py:_settle`). A count; None in a window without
statements and for a program without the counter. /metrics."""

from benchlib.layerlib import counter_delta

COUNTER = "greptime_scan_cache_late_rows_total"


def read(run):
    counters = run.get("counters")
    if "statements" not in run or not counters \
            or COUNTER not in counters["after"]:
        return None
    return counter_delta(run, COUNTER)
