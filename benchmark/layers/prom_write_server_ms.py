"""Layer: write path. Server time of a remote-write request, from the
handler's entry to its response: the program's own
`greptime_http_request_seconds{route="/v1/prometheus/write"}`
(`servers/http.py:_observed`), ms per acknowledged block. /metrics."""

from benchlib.writelib import PROM_WRITE_ROUTE, timer_ms_per_block


def read(run):
    return timer_ms_per_block(run, "http_request", PROM_WRITE_ROUTE)
