"""Layer: copy, sweep, mask (host side of reduce). The `reduce.seam` row
of a lowered statement whose plan holds a window's growth: the tail's
derived mirror of per-sample differences made across the seam with its
base (`query/tpu_exec.py:_make_seams`, `MergedScan.device_run_diffs`,
`_seam`: one pair a series, float64 on the host) and uploaded. Made at
the launch that reads it, once a tail: the statements that made one only.
EXPLAIN ANALYZE; None for a program without the row (the parent merges
the tail for such a plan)."""

from benchlib.spanlib import mean_span_ms


def read(run):
    return mean_span_ms(run, "reduce.seam")
