"""Layer: write path. The wait for the WAL's (shared) fsync, inside
`region_write`: the `wal_fsync` timer (`storage/native_wal.py:
_wait_ticket`, `storage/wal.py`), ms per acknowledged batch. Reads 0
where the deployment acknowledges without waiting for an fsync (the
timer is never observed). /metrics."""

from benchlib.spanlib import timer_ms_per_batch


def read(run):
    return timer_ms_per_batch(run, "wal_fsync",
                              since_row_insert_timers=True)
