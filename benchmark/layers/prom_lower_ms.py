"""Layer: promql lowering. The `lower` stage row of a lowered TQL
statement: `promql/lowering.py:eval_lowered` after the aggregate plan has
answered, from the finalized moment frame (a row a (series, window)) back
to the inner instant vector that the outer aggregate reads; all on the
host. EXPLAIN ANALYZE; None for a program whose lowered statements carry
no such row."""

from benchlib.spanlib import mean_span_ms


def read(run):
    return mean_span_ms(run, "lower")
