"""Layer: prune / decode / merge. The host's part of a scan-cache refresh
inside a statement that found rows written since the last one: the parts
`scan_prep.delta` (the rows beyond the cache's watermark collected from
the memtables and new SSTs, sorted) and `scan_prep.apply` (merged into
the tail, or tail and delta into a new base) of the `scan_prep` row
(`query/tpu_exec.py:_ScanCache._incremental`). Mean over families of
family means over the statements that refreshed; a statement that found
the cache current has no such row and does not count. None where no
statement of the window refreshed, and for a program without the rows.
EXPLAIN ANALYZE."""

from benchlib.layerlib import mean_of_family_means
from benchlib.refreshlib import refresh_ms


def read(run):
    return mean_of_family_means(
        run, lambda r: refresh_ms(r, "scan_prep.delta", "scan_prep.apply"))
