"""Layer: copy, sweep, mask (the host side of `reduce`). The `reduce` stage
row minus the device time inside that statement: mask building, uploads,
launch and fetch. EXPLAIN ANALYZE and the device trace."""

from benchlib.layerlib import device_ms, mean_of_family_means, stage_ms


def read(run):
    def value(rec):
        dev = device_ms(run, rec)
        return None if dev is None else stage_ms(rec, "reduce") - dev
    return mean_of_family_means(run, value)
