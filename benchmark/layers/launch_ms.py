"""Layer: copy, sweep, mask (host side of reduce). `reduce.launch`: the
jitted scan program called (`query/tpu_exec.py:_launch_scan_kernel`):
microseconds of dispatch where the program is compiled, the whole compile
where the table's length is new to it. EXPLAIN ANALYZE."""

from benchlib.spanlib import mean_span_ms


def read(run):
    return mean_span_ms(run, "reduce.launch")
