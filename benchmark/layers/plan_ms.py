"""Layer: parse / plan / dispatch. The `plan` stage row: analysis, table
resolution, literal coercion, the rollup check, the aggregate plan and
the dispatch decision (`query/engine.py:_execute_query_inner`,
`query/tpu_exec.py:try_execute`, `:region_moment_frames`).
EXPLAIN ANALYZE."""

from benchlib.spanlib import mean_span_ms


def read(run):
    return mean_span_ms(run, "plan")
