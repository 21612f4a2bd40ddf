"""Layer: prune / decode / merge. The `prune`, `decode` and `scan_prep` stage
rows of the first statement of set-up, which builds the scan cache.
EXPLAIN ANALYZE."""


def read(run):
    first = next((w for w in run.get("warm", ()) if w["stages"]), None)
    if first is None:
        return None
    return sum(first["stages"].get(s, {}).get("elapsed_ms", 0.0)
               for s in ("prune", "decode", "scan_prep")) / 1e3
