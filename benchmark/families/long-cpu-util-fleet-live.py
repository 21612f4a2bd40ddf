"""USE Method / Cluster, CPU utilisation at "Last 24 hours" while the agent writes: 1 - avg(rate(node_cpu_seconds_total{mode="idle"}[1m])).

`long-cpu-util-fleet` (8,000 of 64,000 series by a label, all 46.08M rows
of the table scanned on the device, lowered as a counter: a window's
growth is sum(d) - first(d) over the derived mirror of per-sample
differences) over the 100 min before the acknowledged frontier, any
second of the minute: the one-minute grid has another phase at every
statement (the base's runs are laid out from the selection and their ids
made on the device, `tpu_exec._selection_layout`), and the window that
holds the end of the load holds, for five series in six, a series' last
loaded sample and its first written one. The full launch over the base,
a second one over the tail, and that window across them: the tail's
mirror takes a series' first difference from the base's last sample of
that series, in float64 on the host (`MergedScan.device_run_diffs`,
`reduce.seam`), and the two growths and that difference add
(`_fold_runs`). A program that leaves the difference out is off by 0.08
s/s a series in that window (`loops/remote-write.py`: `seam-left-out`).

Tolerance and its reason are the parent's: absolute, an idle rate near
0.5 s/s; a scrape's growth of about 5 s in f32 is good to 2.4e-7 s, a
window's rate to about 1e-8, the mean of 8,000 closer still. The f32
`first` / `last` of two partials across the seam (counters of 1e3 to
2.6e6 s, a quarter second a sample) would be off by 1e-5 and more of the
fleet's mean: a program that folded those is not `correct` here; bf16
mirrors are off by whole seconds a sample.
"""

from benchlib.promlive import CpuUtilFleetLive

FAMILY = CpuUtilFleetLive("long-cpu-util-fleet-live",
                          dict(rtol=0.0, atol=1e-6))
