"""Layer: prune / decode / merge. Bytes of scan-cache mirrors sent to the
device, a refresh: the window's delta of
`greptime_scan_cache_upload_bytes_total` (every mirror of a cached scan
that `MergedScan._put` uploads: in a window under writes the tail's, at
its capacity, in the refresh or at a statement's first use; the base's
only after a merge or a rebuild) over `cache_refreshes`. Beside
`refresh_delta_rows` it says what a written row costs on the wire to the
device. None in a window without a refresh and for a program without the
counter. /metrics."""

from benchlib.refreshlib import per_refresh


def read(run):
    return per_refresh(run, "greptime_scan_cache_upload_bytes_total")
