"""One node's CPU panel: sum by (mode) (rate(node_cpu_seconds_total{instance="<drawn>"}[5m])).

64 series of 64,000 (`matcher_sids` resolves the equality matcher to the
series' runs in the scan cache): the per-statement overhead of the row
path. The instance is drawn from the targets scraped through the whole
span (980 of 1,020, the rebooted ones among them): a target that ends or
begins inside it gives a narrower matrix, a shape of its own that the
window would compile (read as 440-770 ms in a family of 80; tier-1 tests
hold the answers for such targets).

Tolerance as `prom-cpu-by-mode-all`, and here the rounding to 6 digits
shows: a sum of 8 rates, each within 5e-6 (2.8e-6 read). A counter of
2.6e6 s cast to f32 as it is was off by 1e-4 and more of a mode's rate
(read on the parent), bf16 mirrors by far more.
"""

from benchlib.promfam import ROW_PATH_ON_TPU, CpuByMode

FAMILY = CpuByMode("prom-cpu-by-mode-1", dict(rtol=2e-5, atol=0.0), one=True,
                   dispatch=ROW_PATH_ON_TPU)
