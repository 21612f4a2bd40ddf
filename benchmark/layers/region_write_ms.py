"""Layer: write path. `Region.write` as its caller waits for it (WAL
append, memtable insert, the wait for the shared fsync): the
`region_write` timer (`storage/region.py:write`). Only leaders write, for
their whole cohort; spread over all acknowledged batches so that it adds
to the other parts. /metrics."""

from benchlib.spanlib import timer_ms_per_batch


def read(run):
    return timer_ms_per_batch(run, "region_write")
