"""The fullest filesystem of a kind at "Last 24 hours": min by (mountpoint) (min_over_time(node_filesystem_avail_bytes{fstype!="tmpfs"}[1m])).

3,000 of 4,000 series by a negative matcher, a gauge, `min` on the
device a (series, window) and `min` by mountpoint on the host: three
lines of 101 points.

Tolerance, relative: `min` picks one f32 value of 3.6e9 to 1.8e12 B,
good to 6e-8; a bf16 mirror is off by 2e-3 to 4e-3.
"""

from benchlib.promlong import FsAvailMin

FAMILY = FsAvailMin("long-fs-avail-min", dict(rtol=1e-6, atol=0.0))
