"""Node Exporter Full's CPU panel over the fleet: sum by (mode) (rate(node_cpu_seconds_total[5m])).

The whole table through `select_series` and the prefix-sum kernel:
64,000 series x 128 samples in, 8 x 61 points out.

Tolerance, relative: each of 8,000 rates of a sum is f32 arithmetic on
rebased values, then rounded to 6 digits by the program (`_from_device_f32`:
up to 5e-6 of a value that starts with a 1, and it averages out over a
sum: 1.2e-7 read at full size); bf16 mirrors of the counters are off by
whole seconds a sample.
"""

from benchlib.promfam import ROW_PATH_ON_TPU, CpuByMode

FAMILY = CpuByMode("prom-cpu-by-mode-all", dict(rtol=2e-5, atol=0.0),
                   dispatch=ROW_PATH_ON_TPU)
