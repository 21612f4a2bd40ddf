"""Layer: write path. The `read` phase of `/v1/influxdb/write`: the event
loop's part of a line-protocol write before the body leaves it (auth,
the body read and decoded), from the middleware to the handler's submit
to the executor (`servers/http.py:RequestPhases`), inside
`ingest_server_ms`: `greptime_http_phase_seconds{route, phase}`, ms per
acknowledged batch as the timers beside it. None for a program without
the series. /metrics."""

from benchlib.spanlib import timer_ms_per_batch

LABELS = '{phase="read",route="/v1/influxdb/write"}'


def read(run):
    return timer_ms_per_batch(run, "http_phase", LABELS)
