"""Reading the program's own spans: the EXPLAIN ANALYZE stage rows that
carry a wall-clock start (`t0_ns=` at the end of a row's detail) and the
program's timers on /metrics. Shared by the readers under
`benchmark/layers/` that were added with those spans; a program without
them (no `t0_ns`, no such timer) reads as None everywhere here, and the
metric is then left out of the line."""

from __future__ import annotations

import re

from .layerlib import counter_delta, mean_of_family_means

_T0 = re.compile(r"t0_ns=(\d+)\s*$")

#: rows that lie outside `total`: before it and after it
OUTSIDE_TOTAL = ("parse", "render")

WRITE_ROUTE = '{route="/v1/influxdb/write"}'


def t0_ns(rec: dict, stage: str):
    """Wall-clock start of a statement's stage row, or None when the row
    is absent or not a span."""
    found = _T0.search(rec["stages"].get(stage, {}).get("detail") or "")
    return int(found.group(1)) if found else None


def span_ms(rec: dict, *stages):
    """Summed time of the named rows, None unless each is a span."""
    if any(t0_ns(rec, s) is None for s in stages):
        return None
    return sum(rec["stages"][s]["elapsed_ms"] for s in stages)


def interval_ns(rec: dict, stage: str):
    """[start, end) of a stage row on the wall clock, or None."""
    start = t0_ns(rec, stage)
    if start is None:
        return None
    return start, start + int(rec["stages"][stage]["elapsed_ms"] * 1e6)


def top_level_spans(rec: dict) -> list:
    """The timed rows directly under `total`: spans that are no
    `<parent>.<part>`, no datanode's indented row, and not outside."""
    return [s for s in rec["stages"]
            if "." not in s and s == s.strip() and s != "total"
            and s not in OUTSIDE_TOTAL and t0_ns(rec, s) is not None]


def mean_span_ms(run: dict, *stages):
    """Mean over families of family means of span_ms, like the
    neighbours in layerlib."""
    return mean_of_family_means(run, lambda r: span_ms(r, *stages))


def timer_ms_per_batch(run: dict, timer: str, labels: str = "",
                       since_row_insert_timers: bool = False):
    """The window's delta of `greptime_<timer>_seconds_sum` over the
    window's delta of acknowledged line-protocol requests, in ms: every
    such reader divides by the same count, so they add up. None outside
    a write window and when the program has no such timer. A timer
    appears on /metrics with its first observation: with
    `since_row_insert_timers`, a program that has the row-insert timers
    (`ingest_parse`) and never observed this one reads 0, not None."""
    if "batches" not in run or not run.get("counters"):
        return None
    after = run["counters"]["after"]
    name = f"greptime_{timer}_seconds_sum{labels}"
    if name not in after:
        if since_row_insert_timers and \
                "greptime_ingest_parse_seconds_sum" in after:
            return 0.0
        return None
    batches = counter_delta(
        run, "greptime_http_request_seconds_count" + WRITE_ROUTE)
    if not batches:
        return None
    return counter_delta(run, name) / batches * 1e3
