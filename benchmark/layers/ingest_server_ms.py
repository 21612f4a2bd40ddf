"""Layer: write path. Server time of a line-protocol write request, from
the handler's entry to its response: the program's own
`greptime_http_request_seconds{route="/v1/influxdb/write"}`
(`servers/http.py:_observed`), ms per acknowledged batch. /metrics."""

from benchlib.spanlib import WRITE_ROUTE, timer_ms_per_batch


def read(run):
    return timer_ms_per_batch(run, "http_request", WRITE_ROUTE)
