"""Layer: write path. What the line-protocol parser's thread stood
off a processor for: the `ingest_parse` timer's seconds less the thread's
CPU seconds inside it (`greptime_ingest_parse_cpu_seconds_total`,
`common/telemetry.py:timer`), ms per acknowledged batch as
`ingest_parse_ms` beside it. The wait for the interpreter lock, a lock of
the program's, the scheduler, the disk. None for a program without the
counter. /metrics."""

from benchlib.layerlib import counter_delta
from benchlib.spanlib import WRITE_ROUTE, timer_ms_per_batch

CPU = "greptime_ingest_parse_cpu_seconds_total"


def read(run):
    wall_ms = timer_ms_per_batch(run, "ingest_parse")
    if wall_ms is None or CPU not in run["counters"]["after"]:
        return None
    batches = counter_delta(
        run, "greptime_http_request_seconds_count" + WRITE_ROUTE)
    return wall_ms - counter_delta(run, CPU) / batches * 1e3
