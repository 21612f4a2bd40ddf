"""USE Method / Cluster, CPU utilisation at "Last 24 hours": 1 - avg(rate(node_cpu_seconds_total{mode="idle"}[1m])).

8,000 of 64,000 series chosen by a label, all 46.08M rows of the table
scanned on the device (8,000 ranges pad to more than an eighth of the
table, so `scan_read_path` says `full`), 808,000 (series, window) groups
back, one line of 101 points out. Lowers as a counter: the window's
growth is sum(d) - first(d) over the derived mirror of per-sample
differences (`MergedScan.device_run_diffs`).

Tolerance, absolute because a busy share near 0 has no relative error
worth the name: an idle rate is near 0.5 s/s; a scrape's growth of about
5 s in f32 is good to 2.4e-7 s, a window's rate to about 1e-8, and the
mean of 8,000 of them lies closer still (TOLERANCE_READINGS below). The
reference over float32 of the raw counters (1e3 to 2.6e6 s, half a
quarter second a sample) is off by 1e-5 and more of the fleet's mean, so
a program that reduced plain f32 mirrors is not `correct` here; bf16
mirrors are off by whole seconds a sample.
"""

from benchlib.promlong import CpuUtilFleet

FAMILY = CpuUtilFleet("long-cpu-util-fleet", dict(rtol=0.0, atol=1e-6))
