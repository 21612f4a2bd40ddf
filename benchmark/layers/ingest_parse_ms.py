"""Layer: write path. Line protocol to columns: the `ingest_parse` timer
around `influx_mod.body_to_inserts` (`servers/http.py:
handle_influx_write`), ms per acknowledged batch. /metrics."""

from benchlib.spanlib import timer_ms_per_batch


def read(run):
    return timer_ms_per_batch(run, "ingest_parse")
