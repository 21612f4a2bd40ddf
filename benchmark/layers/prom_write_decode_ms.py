"""Layer: write path. A remote-write body to columns: the
`prom_write_decode` timer around `prom_mod.write_request_to_inserts`
(`servers/http.py:handle_prom_write`: snappy, the protobuf walked a field
at a time in Python, the per-table column lists; the wait for the
admission gate's parse turn lies outside it, in `ingest_parse_wait`), ms
per acknowledged block. /metrics."""

from benchlib.writelib import timer_ms_per_block


def read(run):
    return timer_ms_per_block(run, "prom_write_decode")
