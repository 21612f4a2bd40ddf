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
Plain statements never encode twice.
"""

from __future__ import annotations

from typing import Callable, List, Tuple, TypeVar

from ..common import process_list
from ..common.exec_stats import StageStat
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


def render(protocol: str, outputs: List[Output], encode: Encoder) -> T:
    """Encode `outputs` (one response) for the wire and return what
    `encode` returns for them."""
    for out in outputs:
        if out.analyzed is not None:
            analyzed, out.analyzed = out.analyzed, None
            analyzed.trace = out.trace
            _, sp = _encode(protocol, [analyzed], encode, True)
            _append_stage_row(out, StageStat(
                "render", rows=analyzed.num_rows,
                elapsed_s=sp["elapsed_ms"] / 1e3, t0_ns=sp["start_unix_ns"],
                detail={"protocol": protocol,
                        "bytes": sp["attrs"]["bytes"],
                        "path": sp["attrs"]["path"]}))
    value, _ = _encode(protocol, outputs, encode, False)
    return value


def _encode(protocol: str, outputs: List[Output], encode: Encoder,
            discard: bool):
    rows = sum(o.num_rows for o in outputs if o.is_batches)
    with continue_trace(outputs[-1].trace if outputs else None), \
            span("render", protocol=protocol, rows=rows) as sp, \
            process_list.REGISTRY.rendering():
        value, sp["attrs"]["bytes"], routes = encode(outputs, discard)
        sp["attrs"]["path"] = route_of(routes)
    observe_latency("render", sp["elapsed_ms"] / 1e3, protocol=protocol)
    for path, n in routes.items():
        increment_counter("render_rows", n, protocol=protocol, path=path)
    return value, sp


def _append_stage_row(out: Output, st: StageStat) -> None:
    """One more row under an EXPLAIN ANALYZE stage table."""
    batch = out.batches[0]
    cols = batch.to_pydict()
    for name, v in (("stage", st.stage), ("rows", st.rows),
                    ("files", st.files),
                    ("elapsed_ms", st.elapsed_s * 1e3),
                    ("detail", st.detail_str())):
        cols[name].append(v)
    out.batches = [RecordBatch.from_pydict(batch.schema, cols)]
