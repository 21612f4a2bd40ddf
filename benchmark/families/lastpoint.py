"""TSBS cpu-only `lastpoint`: the last usage_user of every host, as last() GROUP BY hostname."""

from benchlib.tsbs import LastPoint

FAMILY = LastPoint("lastpoint", "mysql")
