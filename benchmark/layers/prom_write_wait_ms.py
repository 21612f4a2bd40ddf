"""Layer: write path. A remote-write body's wait for its turn at the
decoder: the `ingest_parse_wait` timer
(`common/admission.py:AdmissionGate.parse_turn`, taken by
`servers/http.py:handle_prom_write` as the line-protocol handler takes
it: bodies are decoded one at a time, the wait lies outside
`prom_write_decode` and inside `prom_write_server_ms`), ms per
acknowledged block. None for a program whose remote-write handler takes
no turn and never observed the timer. /metrics."""

from benchlib.writelib import timer_ms_per_block


def read(run):
    return timer_ms_per_block(run, "ingest_parse_wait")
