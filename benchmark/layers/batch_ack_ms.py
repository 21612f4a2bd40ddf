"""Layer: write path. Median send-to-acknowledgement time of the batches
acknowledged inside the window. Host clock."""

import statistics


def read(run):
    acks = [b["ack_ms"] for b in run.get("batches", ())
            if b.get("in_window") and b.get("ok")]
    return statistics.median(acks) if acks else None
