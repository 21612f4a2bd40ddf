"""Layer: scan kernels. Share of the HBM roofline of the scan kernels under
lowered PromQL: the bytes the window's resident launches must read at
least once (`floor_bytes`: every row a launch read, counted by the program
a launch, full or narrowed, as `greptime_scan_device_rows_total`, times
4 B of int32 timestamp and 4 B of one f32 value column) over the device
time inside the window's statements, against the device's peak bytes/s
(benchlib/peaks.json). Every launch reads the timestamps and at least one
value column (a counter family reads two, the field and its derived
mirror, and the kernels go over a column several times), so the count is
a true floor and the share cannot pass 100%. On a configuration of
several tables `run["rows_loaded"]` is all of them, which is why this
cell does not report `scan_kernels_roofline`. Program counter and device
trace; None for a program without the counter."""

from benchlib.layerlib import (counter_delta, device_ms,
                               window_statements)
from benchlib.peaks import peak_of

ROWS = "greptime_scan_device_rows_total"


def floor_bytes(rows: float, columns: int = 1) -> float:
    """One int32 timestamp and `columns` f32 value columns a row, read
    once."""
    return rows * 4 * (1 + columns)


def read(run):
    trace = run.get("trace")
    counters = run.get("counters")
    if trace is None or not trace.planes or not counters \
            or ROWS not in counters["after"]:
        return None
    busy_ms = sum(device_ms(run, rec) or 0.0
                  for rec in window_statements(run))
    if not busy_ms:
        return None
    bandwidth = peak_of(run["device"]["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * floor_bytes(counter_delta(run, ROWS)) \
        / (busy_ms / 1e3) / bandwidth
