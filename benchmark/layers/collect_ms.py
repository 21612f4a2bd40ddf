"""Layer: copy, sweep, mask (host side of reduce). `reduce.collect`: the
fetched runs folded into a moment frame
(`query/tpu_exec.py:_collect_moment_frame`). EXPLAIN ANALYZE."""

from benchlib.spanlib import mean_span_ms


def read(run):
    return mean_span_ms(run, "reduce.collect")
