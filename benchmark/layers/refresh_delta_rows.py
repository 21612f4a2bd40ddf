"""Layer: prune / decode / merge. Rows a refresh of the scan cache applied,
a refresh: the window's delta of `greptime_scan_cache_delta_rows_total`
(the rows beyond the cache's watermark that `_ScanCache._incremental`
collected) over `cache_refreshes`. None in a window without a refresh and
for a program without the counter. /metrics."""

from benchlib.refreshlib import per_refresh


def read(run):
    return per_refresh(run, "greptime_scan_cache_delta_rows_total")
