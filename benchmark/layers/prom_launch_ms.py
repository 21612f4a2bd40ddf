"""Layer: window kernels. `window.launch`: the jitted programs of
`ops/window.py` called (bounds, stacked gathers, the function's epilogue):
microseconds of dispatch each where they are compiled, a whole compile
where a matrix shape is new. EXPLAIN ANALYZE."""

from benchlib.spanlib import mean_span_ms


def read(run):
    return mean_span_ms(run, "window.launch")
