"""Layer: protocol servers. The `render` stage row: the analysed
statement's own result encoded by its protocol's writer into a discarded
buffer (`servers/render.py:render`), after `total`. EXPLAIN ANALYZE."""

from benchlib.spanlib import mean_span_ms


def read(run):
    return mean_span_ms(run, "render")
