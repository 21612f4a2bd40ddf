"""HostOutOfMemory's gauges as the used share while the agent writes: 1 - node_memory_MemAvailable_bytes / node_memory_MemTotal_bytes.

`prom-mem-used-ratio` (two tables, instant selectors with the 5 m
lookback, one-to-one matching on (instance, job)) over the 15 min before
the acknowledged frontier: two selectors, two scan-cache entries, two
tails; a target that joined at the first live scrape is in both tails and
in neither base.

Tolerance and its reason are the parent's: absolute on a share in [0.05,
0.95]; both gauges come back as f32 offsets from a float64 base, so the
quotient is within 1e-7; bf16 mirrors of 1e10 B are off by 4e-3 of it.
"""

from benchlib.promfam import ROW_PATH_ON_TPU
from benchlib.promlive import MemUsedRatioLive

FAMILY = MemUsedRatioLive("prom-mem-used-ratio-live",
                          dict(rtol=0.0, atol=1e-5),
                          dispatch=ROW_PATH_ON_TPU)
