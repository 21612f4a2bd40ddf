"""Layer: write path. The `queue` phase of `/v1/influxdb/write`: a line-
protocol write's hand-off from the event loop to an executor thread,
from the handler's submit to the first line of its work on the thread
(`servers/http.py:RequestPhases`), inside `ingest_server_ms`:
`greptime_http_phase_seconds{route, phase}`, ms per acknowledged batch
as the timers beside it. None for a program without the series.
/metrics."""

from benchlib.spanlib import timer_ms_per_batch

LABELS = '{phase="queue",route="/v1/influxdb/write"}'


def read(run):
    return timer_ms_per_batch(run, "http_phase", LABELS)
