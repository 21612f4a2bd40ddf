"""Layer: window kernels. Share of the HBM roofline: the bytes the window
kernels must read at least once (every cell of every [series, samples]
matrix the window's statements built, 4 B of int32 timestamp and 4 B of
float32 value: `floor_bytes`) over the device time inside those
statements, against the device's peak bytes/s (benchlib/peaks.json). The
kernels read a matrix several times over (bounds, prefix sums, one gather
a channel) and rate reads two value arrays, so the count is a true floor
and the share cannot pass 100%. Program counter
(`greptime_promql_matrix_cells_total`) and device trace."""

from benchlib.layerlib import (counter_delta, device_ms,
                               window_statements)
from benchlib.peaks import peak_of

CELLS = "greptime_promql_matrix_cells_total"


def floor_bytes(cells: float) -> float:
    """One int32 timestamp and one float32 value a cell, read once."""
    return cells * (4 + 4)


def read(run):
    trace = run.get("trace")
    counters = run.get("counters")
    if trace is None or not trace.planes or not counters \
            or CELLS not in counters["after"]:
        return None
    busy_ms = sum(device_ms(run, rec) or 0.0
                  for rec in window_statements(run))
    if not busy_ms:
        return None
    bandwidth = peak_of(run["device"]["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * floor_bytes(counter_delta(run, CELLS)) \
        / (busy_ms / 1e3) / bandwidth
