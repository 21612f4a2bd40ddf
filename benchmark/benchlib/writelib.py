"""What the remote-write cell's pieces share. The readers
(`layers/prom_write_*.py`): a timer of the program over the window, per
acknowledged block; a program without the timer (the parent: its
`/v1/prometheus/write` handler is not timed) and a window without such
requests read None, and the metric is left out of the line. The generator
and the loop (`generators/node-exporter-live.py`, `loops/remote-write.py`):
the two protobuf pieces a prompb message is written with."""

from __future__ import annotations

from .layerlib import counter_delta

PROM_WRITE_ROUTE = '{route="/v1/prometheus/write"}'


def blocks_in_window(run: dict):
    """Remote-write requests the server answered inside the window."""
    if "batches" not in run or not run.get("counters"):
        return None
    return counter_delta(
        run, "greptime_http_request_seconds_count" + PROM_WRITE_ROUTE) \
        or None


def timer_ms_per_block(run: dict, timer: str, labels: str = ""):
    """The window's delta of `greptime_<timer>_seconds_sum` over its
    remote-write requests, in ms: every such reader divides by the same
    count, so they add up."""
    blocks = blocks_in_window(run)
    name = f"greptime_{timer}_seconds_sum{labels}"
    if not blocks or name not in run["counters"]["after"]:
        return None
    return counter_delta(run, name) / blocks * 1e3


def varint(n: int) -> bytes:
    """A protobuf varint of a non-negative int."""
    out = bytearray()
    while n > 0x7F:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def field(number: int, payload: bytes) -> bytes:
    """A length-delimited protobuf field."""
    return varint(number << 3 | 2) + varint(len(payload)) + payload
