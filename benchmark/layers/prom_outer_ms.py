"""Layer: promql outer. The `outer` stage row: what a TQL statement's
evaluation spends outside `select` and `window`, summed: label grouping,
vector matching, binary operators, `topk`, and the result shaped into
record batches (`promql/engine.py`, all on the host). EXPLAIN ANALYZE."""

from benchlib.spanlib import mean_span_ms


def read(run):
    return mean_span_ms(run, "outer")
