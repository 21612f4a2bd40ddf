"""USE Method / Cluster, network at "Last 24 hours": sum(rate(node_network_receive_bytes_total{device!="lo"}[1m])).

1,000 of 2,000 series by a negative matcher (no point predicate: the
full launch over the table's 1.44M rows), counters up to 2.6e14 B: the
precision case. A plain f32 mirror of such a counter steps by 1.7e7 to
3.4e7 B where a window grows by 6e9, so the reference over float32 of
the raw counters is off by 1e-3 of the fleet's rate and a program that
reduced plain f32 mirrors is not `correct`; the derived mirror of
per-sample differences holds a scrape's 1e9 B to 6e-8 of itself.

Tolerance, relative: f32's rounding of a scrape's growth and of the sum
of six (about 3e-7 of a series' rate), less in the sum of 1,000; bf16
mirrors of the counters are off by whole windows' growth.
"""

from benchlib.promlong import NetReceiveFleet

FAMILY = NetReceiveFleet("long-net-rx-fleet", dict(rtol=2e-6, atol=0.0))
