"""One node's CPU panel at "Last 24 hours": sum by (mode) (rate(node_cpu_seconds_total{instance="<drawn>"}[1m])).

64 series of 64,000 by a point predicate: `scan_read_path` chooses
`narrow`, the launch reads the 64 ranges' 38,784 rows out of the
resident mirrors (PR 31), 6,464 groups back. The instance is drawn from
the targets scraped through the whole span (the rebooted ones among
them). Lowers as a counter.

Tolerance, relative: a mode's rate is the sum of 8 CPUs' rates, each
good to f32's rounding of a scrape's growth and of the sum of six of
them (about 2e-7); steal or irq rates of 1e-3 s/s carry the same relative
error. bf16 mirrors and plain f32 mirrors of counters of 2.6e6 s miss it
by orders of magnitude.
"""

from benchlib.promlong import CpuByModeOne

FAMILY = CpuByModeOne("long-cpu-by-mode-1", dict(rtol=5e-6, atol=0.0))
