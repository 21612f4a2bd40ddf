"""Layer: fold / finalize / wire. The `finalize` stage row. EXPLAIN ANALYZE."""

from benchlib.layerlib import mean_of_family_means, stage_ms


def read(run):
    return mean_of_family_means(run, lambda r: stage_ms(r, "finalize"))
