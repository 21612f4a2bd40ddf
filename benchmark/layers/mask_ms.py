"""Layer: copy, sweep, mask (host side of reduce). The host's work before
the launch: `reduce.runs` (run-id sweep) + `reduce.mask` (predicates to a
row mask) + `reduce.upload` (every device_put), timed in
`query/tpu_exec.py:_launch_scan_kernel`. EXPLAIN ANALYZE."""

from benchlib.spanlib import mean_span_ms


def read(run):
    return mean_span_ms(run, "reduce.runs", "reduce.mask", "reduce.upload")
