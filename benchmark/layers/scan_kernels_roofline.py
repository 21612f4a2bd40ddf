"""Layer: scan kernels. Share of the HBM roofline: the bytes a full scan
must read (per row 4 B of timestamp or group id and 4 B per field read, the
f32 / int32 device mirrors) over the statement's device time, against the
device's peak bytes/s (benchlib/peaks.json). Only families that read every
row count (double-groupby-*, lastpoint), so the byte count is a true floor
and the share cannot pass 100%. Device trace."""

from benchlib.layerlib import device_ms, mean_of_family_means
from benchlib.peaks import peak_of
from benchlib.spec import load_family


def read(run):
    trace = run.get("trace")
    if trace is None or not trace.planes or "rows_loaded" not in run:
        return None
    bandwidth = peak_of(run["device"]["device_kind"])["hbm_bytes_per_s"]
    fields = {}
    for name in run["mix"].get("families", ()):
        fam = load_family(name)
        if getattr(fam, "full_scan_fields", None):
            fields[name] = fam.full_scan_fields
    if not fields:
        return None

    def value(rec):
        dev = device_ms(run, rec)
        if not dev:
            return None
        floor_bytes = run["rows_loaded"] * 4 * (1 + fields[rec["family"]])
        return 100.0 * floor_bytes / (dev / 1e3) / bandwidth
    return mean_of_family_means(run, value, families=fields)
