"""Layer: write path. The waits for the WAL's (shared) fsync of a
remote-write block's seven tables, inside `prom_write_insert` and inside
`region_write`: the `wal_fsync` timer (`storage/native_wal.py:
_wait_ticket`), ms per acknowledged block: what the durability the
configuration states costs an acknowledgement. `wal_fsync_ms` reads the
same timer per line-protocol request and finds none here. /metrics."""

from benchlib.writelib import timer_ms_per_block


def read(run):
    return timer_ms_per_block(run, "wal_fsync")
