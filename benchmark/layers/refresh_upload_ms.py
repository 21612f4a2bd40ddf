"""Layer: prune / decode / merge. What a scan-cache refresh sends to the
device inside the statement: the part `scan_prep.upload` of the
`scan_prep` row (the tail's pad mask and the mirrors the statements
before the write had in use, at the tail's capacity;
`query/tpu_exec.py:_ScanCache._incremental`). Mean over families of
family means over the statements that have the row; None where none has
(no refresh in the window, only merges, or a program without the row).
EXPLAIN ANALYZE."""

from benchlib.spanlib import mean_span_ms


def read(run):
    return mean_span_ms(run, "scan_prep.upload")
