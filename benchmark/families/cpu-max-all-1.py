"""TSBS cpu-only `cpu-max-all-1`: max of all 10 metrics, 1 host, per hour over 8 h."""

from benchlib.tsbs import CpuMaxAll

FAMILY = CpuMaxAll("cpu-max-all-1", 1, "mysql")
