"""One node's CPU panel while the agent writes: sum by (mode) (rate(node_cpu_seconds_total{instance="<drawn>"}[5m])).

`prom-cpu-by-mode-1` (64 series of 64,000 by an equality matcher: the
per-statement overhead of the row path) over the 15 min before the
acknowledged frontier: 64 runs of the base and 64 of the tail. The target
is drawn from those scraped through the loaded span and the live rounds
(another matrix width is a program the window would compile, as the
parent says).

Tolerance and its reason are the parent's: a sum of 8 rates, each rounded
to 6 digits by the program (within 5e-6); a counter of 2.6e6 s cast to
f32 as it is was off by 1e-4 and more of a mode's rate, bf16 mirrors by
far more.
"""

from benchlib.promfam import ROW_PATH_ON_TPU
from benchlib.promlive import CpuByModeOneLive

FAMILY = CpuByModeOneLive("prom-cpu-by-mode-1-live",
                          dict(rtol=2e-5, atol=0.0),
                          dispatch=ROW_PATH_ON_TPU)
