"""Saturation at "Last 24 hours" while the agent writes: max by (instance) (max_over_time(node_load1[1m])).

`long-load-max-by-instance` (every series of the table, a gauge, `max` on
the device a (series, window) and again by instance on the host, a
101,000-row answer) over the 100 min before the acknowledged frontier,
any second of the minute: base and tail are reduced apart and their
maxima fold a run, as they do for SQL (the window that holds the end of
the load is a run of both).

Tolerance and its reason are the parent's: relative, a load of 0 to 16
as f32 is good to 6e-8 and `max` picks one of the mirror's values; a bf16
mirror is off by 2e-3 to 4e-3 of a value.
"""

from benchlib.promlive import LoadMaxByInstanceLive

FAMILY = LoadMaxByInstanceLive("long-load-max-by-instance-live",
                               dict(rtol=1e-6, atol=0.0))
