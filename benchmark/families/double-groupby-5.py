"""TSBS cpu-only `double-groupby-5`: avg of 5 metrics by hostname and hour over the 12 h window, all hosts."""

from benchlib.tsbs import DoubleGroupby

FAMILY = DoubleGroupby("double-groupby-5", 5, "http")
