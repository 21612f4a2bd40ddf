"""HostDiskWillFillIn24Hours' function: predict_linear(node_filesystem_avail_bytes{fstype!="tmpfs"}[10m], 3600).

The gather path of `ops/window.py` (least squares over each window's 60
samples), 3,000 series, 3,000 x 61 points out. The rule reads [1h] and
predicts 24 h ahead; the window is cut to the loaded 30 min (`reduced`).

Tolerance, absolute in bytes because a prediction may pass through 0: the
device fits f32 offsets from a float64 base (offsets of up to 3e8 B, so
the fit at +1 h is good to a few hundred bytes, and the program rounds it
to 6 digits: 1e3 B); bf16 mirrors of 1e10 to 2e12 B are off by 1e8 B.
"""

from benchlib.promfam import ROW_PATH_ON_TPU, FsPredict

FAMILY = FsPredict("prom-fs-predict", dict(rtol=0.0, atol=1e5),
                   dispatch=ROW_PATH_ON_TPU)
