"""Layer: protocol servers. The `request.queue` stage row of a statement
sent over HTTP: the hand-off from the event loop to an executor thread,
from the handler's submit to the first line of the submitted function on
its thread (`servers/http.py:RequestPhases`, `HttpServer._offload`).
Mean over families of family means; a family sent over MySQL has no such
row (its statement runs on the connection's own thread) and is left out
of the mean. None for a program without the row. EXPLAIN ANALYZE."""

from benchlib.spanlib import mean_span_ms


def read(run):
    return mean_span_ms(run, "request.queue")
