"""Layer: write path. A coalesced follower parked on its leader's shared
insert: the `ingest_coalesce_wait` timer (`servers/coalesce.py:_follow`),
ms per acknowledged batch (leaders wait 0; no follower at all reads 0).
/metrics."""

from benchlib.spanlib import timer_ms_per_batch


def read(run):
    return timer_ms_per_batch(run, "ingest_coalesce_wait",
                              since_row_insert_timers=True)
