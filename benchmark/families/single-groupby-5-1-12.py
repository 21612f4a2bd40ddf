"""TSBS cpu-only `single-groupby-5-1-12`: max of 5 metrics, 1 host, per minute over 12 h."""

from benchlib.tsbs import SingleGroupby

FAMILY = SingleGroupby("single-groupby-5-1-12", 5, 1, 12, "http")
