"""TSBS cpu-only `double-groupby-1`: avg of 1 metric by hostname and hour over the 12 h window, all hosts."""

from benchlib.tsbs import DoubleGroupby

FAMILY = DoubleGroupby("double-groupby-1", 1, "http")
