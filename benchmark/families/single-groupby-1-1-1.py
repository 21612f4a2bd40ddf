"""TSBS cpu-only `single-groupby-1-1-1`: max of 1 metric, 1 host, per minute over 1 h."""

from benchlib.tsbs import SingleGroupby

FAMILY = SingleGroupby("single-groupby-1-1-1", 1, 1, 1, "http")
