"""Layer: parse / plan / dispatch. The server's `total` stage row minus every
timed stage under it (the `plan` and `dispatch` rows carry no time of their
own today): parsing, planning, the dispatch decision. EXPLAIN ANALYZE."""

from benchlib.layerlib import mean_of_family_means, stage_ms

TIMED = ("prune", "decode", "scan_prep", "scan", "filter", "aggregate",
         "reduce", "finalize", "project")


def read(run):
    return mean_of_family_means(
        run, lambda r: stage_ms(r, "total") - stage_ms(r, *TIMED))
