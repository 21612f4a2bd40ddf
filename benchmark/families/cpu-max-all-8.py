"""TSBS cpu-only `cpu-max-all-8`: max of all 10 metrics, 8 hosts, per hour over 8 h."""

from benchlib.tsbs import CpuMaxAll

FAMILY = CpuMaxAll("cpu-max-all-8", 8, "http")
