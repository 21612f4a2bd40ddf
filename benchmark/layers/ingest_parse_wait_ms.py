"""Layer: write path. An admitted body's wait for its turn at the
line-protocol parser: the `ingest_parse_wait` timer
(`common/admission.py:AdmissionGate.parse_turn`; bodies are parsed one at
a time, the wait lies outside `ingest_parse`), ms per acknowledged batch.
None for a program without the timer (bodies parsed side by side: the
wait was inside `ingest_parse`). /metrics."""

from benchlib.spanlib import timer_ms_per_batch


def read(run):
    return timer_ms_per_batch(run, "ingest_parse_wait")
