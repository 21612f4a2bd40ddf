"""Layer: write path. A remote-write block's inserts: the
`prom_write_insert` timer around the seven `COALESCER.ingest` calls of
`servers/http.py:handle_prom_write` (a table each: the coalescer's
window, the region write, the WAL append and the fsync waited for, one
after the other), ms per acknowledged block. /metrics."""

from benchlib.writelib import timer_ms_per_block


def read(run):
    return timer_ms_per_block(run, "prom_write_insert")
