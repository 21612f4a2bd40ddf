"""Layer: process start, compile cache. The wall time of the cell's warm-up
statements in set-up: what a cold compile cache costs shows here. Host clock."""


def read(run):
    return run.get("warm_s")
