"""Layer: prune / decode / merge. Merges of the scan cache inside the
window: the delta of `greptime_scan_cache_merges_total` (a tail past its
capacity, a tombstone, an overwrite or a late row, or a caller that wants
one sorted scan: every column of the base copied once, its mirrors
uploaded and its programs compiled again for the new length). A count; 0
for a program that counts refreshed rows and merged nothing; None in a
window without statements and for a program without the counters.
/metrics."""

from benchlib.layerlib import counter_delta


def read(run):
    counters = run.get("counters")
    if "statements" not in run or not counters or \
            "greptime_scan_cache_delta_rows_total" not in counters["after"]:
        return None
    return counter_delta(run, "greptime_scan_cache_merges_total")
