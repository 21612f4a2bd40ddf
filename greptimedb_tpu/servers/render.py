"""Rendering a statement's result into a protocol's bytes, under a span.

The HTTP, MySQL and Postgres writers encode every result through
`render()`: a `telemetry.span("render", protocol=, rows=, bytes=)` that
hangs off the statement's `execute_stmt` span (so OTLP and the trace
store show it in the statement's trace, after the engine's spans), the
histogram `greptime_render_seconds{protocol}` on /metrics, and the span's
annotation on the profiler's host timeline. The encoders make a result's
bytes a column at a time (servers/columnar.py); the span's `path`, the
stage row's and `greptime_render_rows_total{protocol, path}` say which of
its routes the rows took: `compiled`, `columnar`, or the per-cell code.

`EXPLAIN ANALYZE` answers with stage rows, not with the statement's
result, so its writer would skip the cost a client of the plain statement
pays. The engine therefore hands the analysed statement's own `Output`
along (`Output.analyzed`); it is encoded here with the same encoder, the
bytes are discarded, and what that took is appended to the stage rows as
`render` — after `total`, like PostgreSQL's EXPLAIN (ANALYZE, SERIALIZE).
Plain statements never encode twice. Over HTTP the row before it is
`request.resume`: the statement's way back from its thread to the event
loop, which the HTTP server timed (servers/http.py:RequestPhases).
"""

from __future__ import annotations

import time
from typing import Callable, List, Optional, Tuple, TypeVar

from ..common import process_list
from ..common.exec_stats import StageStat, Timed
from ..common.telemetry import (continue_trace, increment_counter,
                                observe_latency, span)
from ..datatypes.record_batch import RecordBatch
from ..query.output import Output
from .columnar import RouteRows, route_of

T = TypeVar("T")

#: encode(outputs, discard) -> (what the writer wants back, bytes made,
#: the rows each route of servers/columnar.py rendered); with `discard`
#: the bytes go nowhere (no socket, no sequence numbers)
Encoder = Callable[[List[Output], bool], Tuple[T, int, RouteRows]]


def render(protocol: str, outputs: List[Output], encode: Encoder,
           resumed: Optional[Timed] = None) -> T:
    """Encode `outputs` (one response) for the wire and return what
    `encode` returns for them. `resumed`: what the server timed between
    the statement's end on its thread and this call."""
    for out in outputs:
        if out.analyzed is not None:
            analyzed, out.analyzed = out.analyzed, None
            analyzed.trace = out.trace
            _, sp, cpu_s = _encode(protocol, [analyzed], encode, True)
            rows = [] if resumed is None else [StageStat(
                resumed.name, elapsed_s=resumed.elapsed_s,
                cpu_s=resumed.cpu_s, t0_ns=resumed.t0_ns)]
            _append_stage_rows(out, rows + [StageStat(
                "render", rows=analyzed.num_rows, cpu_s=cpu_s,
                elapsed_s=sp["elapsed_ms"] / 1e3, t0_ns=sp["start_unix_ns"],
                detail={"protocol": protocol,
                        "bytes": sp["attrs"]["bytes"],
                        "path": sp["attrs"]["path"]})])
    value, _, _ = _encode(protocol, outputs, encode, False)
    return value


def _encode(protocol: str, outputs: List[Output], encode: Encoder,
            discard: bool):
    """-> (what `encode` returns, the `render` span, the CPU seconds
    of this thread inside it: read for a discarded encoding, which
    becomes a stage row, and None otherwise)."""
    rows = sum(o.num_rows for o in outputs if o.is_batches)
    with continue_trace(outputs[-1].trace if outputs else None), \
            span("render", protocol=protocol, rows=rows) as sp, \
            process_list.REGISTRY.rendering():
        cpu0 = time.thread_time_ns() if discard else 0
        value, sp["attrs"]["bytes"], routes = encode(outputs, discard)
        cpu_s = (time.thread_time_ns() - cpu0) / 1e9 if discard else None
        sp["attrs"]["path"] = route_of(routes)
    observe_latency("render", sp["elapsed_ms"] / 1e3, protocol=protocol)
    for path, n in routes.items():
        increment_counter("render_rows", n, protocol=protocol, path=path)
    return value, sp, cpu_s


def _append_stage_rows(out: Output, stats: List[StageStat]) -> None:
    """More rows under an EXPLAIN ANALYZE stage table."""
    batch = out.batches[0]
    cols = batch.to_pydict()
    for st in stats:
        for name, v in (("stage", st.stage), ("rows", st.rows),
                        ("files", st.files),
                        ("elapsed_ms", st.elapsed_s * 1e3),
                        ("detail", st.detail_str())):
            cols[name].append(v)
    out.batches = [RecordBatch.from_pydict(batch.schema, cols)]
