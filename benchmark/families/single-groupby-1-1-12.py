"""TSBS cpu-only `single-groupby-1-1-12`: max of 1 metric, 1 host, per minute over 12 h."""

from benchlib.tsbs import SingleGroupby

FAMILY = SingleGroupby("single-groupby-1-1-12", 1, 1, 12, "mysql")
