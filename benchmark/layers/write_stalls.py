"""Layer: write path. Writers stalled on a flush backlog during the window:
the `region_write_stalls` counter of /metrics, after minus before."""

from benchlib.layerlib import counter_delta


def read(run):
    if "batches" not in run:
        return None
    return counter_delta(run, "greptime_region_write_stalls_total")
