"""Layer: write path. The `resume` phase of `/v1/influxdb/write`: a line-
protocol write's hand-off back, from the last line of its work on the
executor thread to the handler's next line on the event loop
(`servers/http.py:RequestPhases`), inside `ingest_server_ms`:
`greptime_http_phase_seconds{route, phase}`, ms per acknowledged batch
as the timers beside it. None for a program without the series.
/metrics."""

from benchlib.spanlib import timer_ms_per_batch

LABELS = '{phase="resume",route="/v1/influxdb/write"}'


def read(run):
    return timer_ms_per_batch(run, "http_phase", LABELS)
