"""Layer: protocol servers. The `request.read` stage row of a statement
sent over HTTP: the event loop's part before the statement leaves it,
from the HTTP server's middleware to the handler's submit to the
executor (auth, the `sql` parameter, the form's decode)
(`servers/http.py:RequestPhases`, `HttpServer._offload`). Mean over
families of family means; a family sent over MySQL has no such row (its
statement runs on the connection's own thread) and is left out of the
mean. None for a program without the row. EXPLAIN ANALYZE."""

from benchlib.spanlib import mean_span_ms


def read(run):
    return mean_span_ms(run, "request.read")
