"""Layer: promql select / matrix. What the [series, samples] matrix costs
before a kernel can run: `select.matrix` (concatenate, sort,
`ops/window.py:SeriesMatrix.build`) plus `window.upload` (timestamps
rebased to int32, values to float32 offsets from each series' first
sample in float64, `device_put`): built on the host and uploaded for
every statement. EXPLAIN ANALYZE."""

from benchlib.spanlib import mean_span_ms


def read(run):
    return mean_span_ms(run, "select.matrix", "window.upload")
