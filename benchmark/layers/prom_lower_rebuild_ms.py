"""Layer: promql lowering. The `lower.rebuild` part of the `lower` row:
the frame's group columns factorised to series codes (a label rendered
once a series) and the [series, steps] values and `ok` arrays filled,
with the extrapolation of `rate` where the function is a counter's.
EXPLAIN ANALYZE; None for a program without the row."""

from benchlib.spanlib import mean_span_ms


def read(run):
    return mean_span_ms(run, "lower.rebuild")
