"""Layer: promql select / matrix. The `select` stage row of a TQL
statement: `promql/lowering.py:select_series`, from the region's rows as
host arrays to the [series, samples] matrix (its parts `select.scan`,
`.filter`, `.labels`, `.matrix`), summed over the statement's selectors.
EXPLAIN ANALYZE; None for a program whose TQL statements carry no such
row."""

from benchlib.spanlib import mean_span_ms


def read(run):
    return mean_span_ms(run, "select")
