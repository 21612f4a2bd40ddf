"""TSBS cpu-only `double-groupby-all`: avg of all 10 metrics by hostname and hour over the 12 h window, all hosts."""

from benchlib.tsbs import DoubleGroupby

FAMILY = DoubleGroupby("double-groupby-all", 10, "http")
