"""TSBS cpu-only `single-groupby-5-1-1`: max of 5 metrics, 1 host, per minute over 1 h."""

from benchlib.tsbs import SingleGroupby

FAMILY = SingleGroupby("single-groupby-5-1-1", 5, 1, 1, "mysql")
