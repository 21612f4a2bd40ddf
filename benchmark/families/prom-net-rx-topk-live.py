"""The five busiest receivers while the agent writes: topk(5, sum by (instance) (rate(node_network_receive_bytes_total{device!="lo"}[5m]))).

`prom-net-rx-topk` (a negative matcher, byte counters of up to 2.6e14,
`topk` per step, which never lowers; compared by rank on the value and
the chosen target's number) over the 15 min before the acknowledged
frontier: the counters' newest samples come from the tail.

Tolerance and its reason are the parent's: a rate is rounded to 6 digits
by the program (up to 5e-6); the eight largest rates lie 10% apart and a
window's rate wanders by 1%, so the five chosen do not hang on those
digits; a byte counter of 1e12 cast to f32 as it is would be off by 13% of
a rate of 1 KB/s.
"""

from benchlib.promfam import ROW_PATH_ON_TPU
from benchlib.promlive import NetReceiveTopkLive

FAMILY = NetReceiveTopkLive("prom-net-rx-topk-live",
                            dict(rtol=2e-5, atol=0.0),
                            dispatch=ROW_PATH_ON_TPU)
