"""Layer: protocol servers. A `/v1/sql` response's way out: from the HTTP
server's middleware handing it to aiohttp (`prepare`, `write_eof`) until
it is drained into the socket, the `write` phase of
`greptime_http_phase_seconds` (`servers/http.py:_error_middleware`), ms a
request over the window's requests. It cannot be a stage row of the
response it writes. None for a program without the series and where the
window sent nothing over HTTP. /metrics."""

from benchlib.layerlib import counter_delta

SERIES = 'greptime_http_phase_seconds_{}{{phase="write",route="/v1/sql"}}'


def read(run):
    counters = run.get("counters")
    if not counters or SERIES.format("sum") not in counters["after"]:
        return None
    requests = counter_delta(run, SERIES.format("count"))
    if not requests:
        return None
    return counter_delta(run, SERIES.format("sum")) / requests * 1e3
