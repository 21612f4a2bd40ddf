"""Memory the fleet has left at "Last 24 hours": sum(node_memory_MemAvailable_bytes).

`agg(selector)`: the lowering asks for `last` and its time a (series,
one-minute bucket) over the panel's range and the 5 m lookback before
it, and carries a target's newest sample forward under the lookback
(a replaced target is seen for 5 m more). 1,000 live series.

Tolerance, relative: each value of 4e8 to 1.3e11 B as f32 is good to
6e-8 and the sum of 1,000 to less; bf16 mirrors leave 1e-5 and more in
the sum.
"""

from benchlib.promlong import MemAvailableFleet

FAMILY = MemAvailableFleet("long-mem-available-fleet",
                           dict(rtol=1e-6, atol=0.0))
