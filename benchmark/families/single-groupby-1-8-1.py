"""TSBS cpu-only `single-groupby-1-8-1`: max of 1 metric, 8 hosts, per minute over 1 h."""

from benchlib.tsbs import SingleGroupby

FAMILY = SingleGroupby("single-groupby-1-8-1", 1, 8, 1, "http")
