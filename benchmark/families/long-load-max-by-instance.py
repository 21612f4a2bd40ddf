"""Saturation at "Last 24 hours": max by (instance) (max_over_time(node_load1[1m])).

Every one of the table's 1,000 live series (1,110 over the span), a
gauge, `max` on the device a (series, window) and `max` again by
instance on the host (one series an instance: the outer aggregate only
drops the name); a 101,000-row answer, the widest of the mix.

Tolerance, relative: a load of 0 to 16 as f32 is good to 6e-8, and `max`
picks one of the mirror's values, so that is the whole error; a bf16
mirror is off by 2e-3 to 4e-3 of a value. (A load of exactly 0, the
walk's lower clamp, is 0 in every precision.)
"""

from benchlib.promlong import LoadMaxByInstance

FAMILY = LoadMaxByInstance("long-load-max-by-instance",
                           dict(rtol=1e-6, atol=0.0))
