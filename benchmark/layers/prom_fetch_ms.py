"""Layer: window kernels. `window.fetch`: the host blocked in
`jax.device_get` until the device is done, the copy back of the [series,
steps] result, and that result made float64 (rounded to 6 digits, the
series' base added back). EXPLAIN ANALYZE."""

from benchlib.spanlib import mean_span_ms


def read(run):
    return mean_span_ms(run, "window.fetch")
