"""Layer: copy, sweep, mask (host side of reduce). `reduce.fetch`: the
host blocked in `jax.device_get` until the device is done, then the copy
back (`query/tpu_exec.py:_moment_frame_for_scan`). EXPLAIN ANALYZE."""

from benchlib.spanlib import mean_span_ms


def read(run):
    return mean_span_ms(run, "reduce.fetch")
