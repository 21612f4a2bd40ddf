"""Layer: protocol servers. Client wall time minus the server's `total` stage
row, per statement of the traced window. Host clock (client) and
EXPLAIN ANALYZE (server)."""

from benchlib.layerlib import mean_of_family_means, stage_ms


def read(run):
    return mean_of_family_means(
        run, lambda r: r["client_ms"] - stage_ms(r, "total"))
