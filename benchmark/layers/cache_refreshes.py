"""Layer: prune / decode / merge. Statements of the window that found the
region's scan cache behind the region and rebuilt it: the
`scan_cache_incremental` (the delta spliced into the cached arrays) and
`scan_cache_miss` (a full rebuild) counters of /metrics, after minus
before. A count; a window without writes reads 0."""

from benchlib.layerlib import counter_delta


def read(run):
    if "statements" not in run or not run.get("counters"):
        return None
    return sum(counter_delta(run, f"greptime_scan_cache_{how}_total")
               for how in ("incremental", "miss"))
