"""Layer: scan kernels. Device time of a statement that lies outside its
`reduce` stage row: the statement's busy time (as `kernel_ms` takes it)
minus the busy time inside `[reduce.t0_ns, + elapsed]`. Device trace and
EXPLAIN ANALYZE's wall-clock starts."""

from benchlib.layerlib import device_ms, mean_of_family_means
from benchlib.spanlib import interval_ns


def read(run):
    def value(rec):
        whole, inside = device_ms(run, rec), interval_ns(rec, "reduce")
        if whole is None or inside is None:
            return None
        lo = max(inside[0], rec["t_send_ns"])
        hi = min(inside[1], rec["t_done_ns"])
        return whole - run["trace"].busy_ns_between(lo, max(lo, hi)) / 1e6
    return mean_of_family_means(run, value)
