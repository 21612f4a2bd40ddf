"""HostHighCpuLoad's expression while the agent writes: 100 - (avg by (instance) (rate(node_cpu_seconds_total{mode="idle"}[5m])) * 100).

`prom-cpu-busy-all` (8,000 of 64,000 series by a label, a sliding `rate`,
the row path) over the 15 min before the acknowledged frontier at the
statement's send: the selection is cut from the scan cache's base and
from the rows remote write has brought since (`promql/lowering.py:
_matrix_from_runs`, `select.tail`), the churned targets' series from the
latter alone.

Tolerance and its reason are the parent's: absolute in percent points,
an idle rate near 0.5 s/s in f32 on values rebased per series keeps 6
digits (the program rounds a device result to 6), times 100 is 5e-5;
bf16 mirrors of counters of 1e3 to 2.6e6 s are off by percent points by
the hundred.
"""

from benchlib.promfam import ROW_PATH_ON_TPU
from benchlib.promlive import CpuBusyLive

FAMILY = CpuBusyLive("prom-cpu-busy-all-live", dict(rtol=0.0, atol=1e-3),
                     dispatch=ROW_PATH_ON_TPU)
