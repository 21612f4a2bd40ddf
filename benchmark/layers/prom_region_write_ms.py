"""Layer: write path. The region writes of a remote-write block's seven
tables (memtable insert, WAL append and the fsync waited for), inside
`prom_write_insert`: the `region_write` timer (`storage/region.py:
write`), ms per acknowledged block. `region_write_ms` reads the same
timer per line-protocol request and finds none here. /metrics."""

from benchlib.writelib import timer_ms_per_block


def read(run):
    return timer_ms_per_block(run, "region_write")
