"""HostOutOfMemory's gauges as the used share: 1 - node_memory_MemAvailable_bytes / node_memory_MemTotal_bytes.

Two tables, instant selectors with the 5 m lookback (a replaced target is
seen for 5 m more), one-to-one vector matching on (instance, job), a
scalar on the left.

Tolerance, absolute on a share in [0.05, 0.95]: both gauges come back
from the device as f32 offsets from a float64 base, so the quotient is
within 1e-7; bf16 mirrors of 1e10 B are off by 4e-3 of it.
"""

from benchlib.promfam import ROW_PATH_ON_TPU, MemUsedRatio

FAMILY = MemUsedRatio("prom-mem-used-ratio", dict(rtol=0.0, atol=1e-5),
                      dispatch=ROW_PATH_ON_TPU)
