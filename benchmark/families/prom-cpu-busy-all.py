"""HostHighCpuLoad's expression over the fleet: 100 - (avg by (instance) (rate(node_cpu_seconds_total{mode="idle"}[5m])) * 100).

8,000 of 64,000 series selected by a label, a sliding `rate` (5 m at a
15 s step does not tumble, so nothing lowers), 1,000 x 61 points out.

Tolerance, absolute in percent points because a busy share near 0 has no
relative error worth the name: an idle rate is near 0.5 s/s, f32 on values
rebased per series keeps 6 digits of it (the program rounds a device
result to 6), times 100 is 5e-5; bf16 mirrors of counters of 1e3 to
2.6e6 s are off by whole seconds a sample, percent points by the hundred.
"""

from benchlib.promfam import ROW_PATH_ON_TPU, CpuBusy

FAMILY = CpuBusy("prom-cpu-busy-all", dict(rtol=0.0, atol=1e-3),
                 dispatch=ROW_PATH_ON_TPU)
