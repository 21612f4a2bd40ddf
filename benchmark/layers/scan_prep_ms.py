"""Layer: prune / decode / merge. The `prune`, `decode` and `scan_prep` stage
rows of the window's statements (a warm scan cache: near zero).
EXPLAIN ANALYZE."""

from benchlib.layerlib import mean_of_family_means, stage_ms


def read(run):
    return mean_of_family_means(
        run, lambda r: stage_ms(r, "prune", "decode", "scan_prep"))
