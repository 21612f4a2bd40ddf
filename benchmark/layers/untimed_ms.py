"""Layer: parse / plan / dispatch. What no span covers: the `total`
stage row minus every timed row directly under it (`plan` included).
EXPLAIN ANALYZE; None for a program whose rows carry no `t0_ns`."""

from benchlib.layerlib import mean_of_family_means, stage_ms
from benchlib.spanlib import top_level_spans


def read(run):
    def value(rec):
        spans = top_level_spans(rec)
        if not spans:
            return None
        return stage_ms(rec, "total") - stage_ms(rec, *spans)
    return mean_of_family_means(run, value)
