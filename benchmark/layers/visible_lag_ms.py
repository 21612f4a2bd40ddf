"""Layer: prune / decode / merge. Staleness of a `live` statement under
ingest: its send time minus the send time of the oldest row among the
newest rows it shows, one per host (`loops/mixed.py` puts it on the
statement's record: from the answer in a plain window, from the
`scan_prep` row count in a traced one). Mean over the window's
statements. Host clock."""

import statistics


def read(run):
    lags = [r["visible_lag_ms"] for r in run.get("statements", ())
            if r.get("in_window") and r.get("ok")
            and r.get("visible_lag_ms") is not None]
    return statistics.fmean(lags) if lags else None
