"""The table of published peaks, keyed by `device_kind` as JAX reports it.
A device that is not in the table is an error, not a default."""

from __future__ import annotations

import json
import os


def peak_of(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       "in benchmark/benchlib/peaks.json")
    return table[device_kind]
