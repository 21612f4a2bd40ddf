"""Layer: write path. Memtable flush files written during the window: the
`flush_files` counter of /metrics, after minus before."""

from benchlib.layerlib import counter_delta


def read(run):
    if "batches" not in run:
        return None
    return counter_delta(run, "greptime_flush_files_total")
