"""Layer: parse / plan / dispatch. The `parse` stage row: the statement
text to an AST, timed in `frontend/instance.py:do_query` before (and
outside) `total`. EXPLAIN ANALYZE."""

from benchlib.spanlib import mean_span_ms


def read(run):
    return mean_span_ms(run, "parse")
