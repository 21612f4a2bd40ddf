"""Layer: promql select / matrix. The `select.tail` row of a row-path
TQL statement: the part of `select` spent on the rows written since the
scan cache's base was built (`promql/lowering.py:_matrix_from_runs`: the
tail's bisection and the matrix cells taken from it; `_rows_kept` on the
general path), summed over the statement's selectors. The statements
that met a tail only; EXPLAIN ANALYZE; None for a program without the row
(the parent merges the tail into a new base inside `select.scan`)."""

from benchlib.spanlib import mean_span_ms


def read(run):
    return mean_span_ms(run, "select.tail")
