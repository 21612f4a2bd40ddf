"""Layer: protocol servers. The wire: client wall time minus the server's
`total` stage row and minus the `render` row that follows it (its own
metric, `render_ms`), per statement of the traced window. What is left is
the request's way in, the event loop and the response's way out. Host
clock (client) and EXPLAIN ANALYZE (server)."""

from benchlib.layerlib import mean_of_family_means, stage_ms


def read(run):
    return mean_of_family_means(
        run, lambda r: r["client_ms"] - stage_ms(r, "total", "render"))
