"""TSBS cpu-only `single-groupby-5-8-1`: max of 5 metrics, 8 hosts, per minute over 1 h."""

from benchlib.tsbs import SingleGroupby

FAMILY = SingleGroupby("single-groupby-5-8-1", 5, 8, 1, "mysql")
