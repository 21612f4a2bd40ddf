"""Layer: write path. The Flight bulk load every set-up does: rows
acknowledged per second, generator and Arrow encoding included. Host clock."""


def read(run):
    if not run.get("load_s"):
        return None
    return run["rows_loaded"] / run["load_s"]
