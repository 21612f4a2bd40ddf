"""The five busiest receivers: topk(5, sum by (instance) (rate(node_network_receive_bytes_total{device!="lo"}[5m]))).

A negative matcher (1,000 of 2,000 series), byte counters of up to 2.6e14,
and `topk` per step, which never lowers. The comparison is by rank within a
step, on the value and on the number of the chosen target: another
instance chosen at a step is a wrong answer.

Tolerance, relative: a rate is rounded to 6 digits by the program (up to
5e-6; 4.2e-6 read); the generator sets the eight largest rates 10% apart,
a window's rate wanders by 1%, so the five chosen do not hang on those
digits. A byte counter of 1e12 cast to f32 as
it is (ulp 65,536 B) would be off by 13% of a rate of 1 KB/s.
"""

from benchlib.promfam import ROW_PATH_ON_TPU, NetReceiveTopk

FAMILY = NetReceiveTopk("prom-net-rx-topk", dict(rtol=2e-5, atol=0.0),
                        dispatch=ROW_PATH_ON_TPU)
