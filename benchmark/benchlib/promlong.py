"""The long-range PromQL families of the node_exporter fleet as builders,
and what `promref.py` lacks for them: a file under `benchmark/families/`
named `long-*` is one call of one of these.

A long-range family is a panel of a fleet overview opened at "Last 24
hours": Grafana sets `$__interval` to the panel's step (1 m there), so
`rate(x[$__interval])` or `max_over_time(x[$__interval])` has a range
equal to its step and every window tumbles. Such a statement is
`agg(selector)` or `agg(fn(selector[R]))` with `R == step`, the two shapes
`promql/lowering.py` lowers onto the plan IR: the scan kernels SQL uses
reduce it, one group a (series, window), and its executed dispatch is
`device-resident (scan cache)` with no `host-partial` suffix, counters
included. A family's draw varies the end only (and, for the one-target
family, the target), so every statement of a family has the same shape.

This module imports `promref.py` (`matches`, `extrapolated_rate`,
`instant`, `points`) and `promfam.py` (`PromFamily`: the draw, the `TQL
EVAL` text, the step grid, the parse), and nothing of the program. What
is new here is written from Prometheus's documentation in float64 numpy:
`over_time` (`max_over_time` / `min_over_time` over the samples of
`(t - range, t]`) and `aggregate` (`sum` / `avg` / `min` / `max` `by` any
labels, none included).
"""

from __future__ import annotations

import numpy as np

from . import promref as ref
from .promfam import (CPU, FS_AVAIL, MEM_AVAILABLE, NET, PromFamily,
                      _columns)

LOAD1 = "node_load1"

#: what a lowered statement's EXPLAIN ANALYZE shows; the CPU backend
#: prints the same for the same path, so the debug size swaps nothing
RESIDENT = "device-resident (scan cache)"


def over_time(op: str, samples, keep, steps, range_ms: int):
    """`max_over_time` / `min_over_time`: per kept series and step the
    largest / smallest sample of (t - range, t]; one sample is enough.
    -> (values [S', T'], ok)."""
    pick = {"max": np.fmax.reduce, "min": np.fmin.reduce}[op]
    v = samples.values[keep]
    first, last = samples.first[keep], samples.last[keep]
    out = np.full((len(v), len(steps)), np.nan)
    for j, t in enumerate(steps):
        a = int(np.searchsorted(samples.times, t - range_ms, side="right"))
        b = int(np.searchsorted(samples.times, t, side="right"))
        if b <= a:
            continue
        k = np.arange(a, b)[None, :]
        exists = (k >= first[:, None]) & (k < last[:, None])
        out[:, j] = pick(np.where(exists, v[:, a:b], np.nan), axis=1)
    return out, ~np.isnan(out)


def aggregate(op: str, values, ok, by: list):
    """`sum`, `avg`, `min` or `max` `by` the given label columns ([S]
    each; none: one group without labels) -> (label columns of the
    groups, values [G, T'], ok). The metric name is dropped."""
    if by:
        joined = np.array(["\x00".join(map(str, row)) for row in zip(*by)])
        _, firsts, group = np.unique(joined, return_index=True,
                                     return_inverse=True)
    else:
        firsts = np.zeros(1, dtype=np.int64)
        group = np.zeros(len(values), dtype=np.int64)
    groups, steps = len(firsts), values.shape[1]
    count = np.zeros((groups, steps))
    np.add.at(count, group, ok)
    present = count > 0
    if op in ("sum", "avg"):
        out = np.zeros((groups, steps))
        np.add.at(out, group, np.where(ok, values, 0.0))
        if op == "avg":
            out = out / np.maximum(count, 1)
    elif op in ("min", "max"):
        fill = np.inf if op == "min" else -np.inf
        out = np.full((groups, steps), fill)
        (np.minimum if op == "min" else np.maximum).at(
            out, group, np.where(ok, values, fill))
    else:
        raise ValueError(f"no reference for aggregate {op}")
    return ([col[firsts] for col in by], np.where(present, out, np.nan),
            present)


class LongFamily(PromFamily):
    """`[$__interval]` at a one-minute step: the range is the step."""
    range_ms = 60_000

    def __init__(self, name: str, tolerance: dict):
        super().__init__(name, tolerance, dispatch=RESIDENT)


class CpuUtilFleet(LongFamily):
    """USE Method / Cluster, CPU utilisation: the busy share of the whole
    fleet from the idle counters of its CPUs. 8,000 of 64,000 series."""

    def query(self, p, ds):
        return f'1 - avg(rate({CPU}{{mode="idle"}}[1m]))'

    def reference(self, p, ds):
        s, steps = ds.samples(CPU), self.steps(p, ds)
        keep = ref.matches(s, [("mode", "=", "idle")])
        rate, ok = ref.extrapolated_rate(s, keep, steps, self.range_ms)
        _, avg, present = aggregate("avg", rate, ok, [])
        return ref.points([], steps, 1.0 - avg, present)


class CpuByModeOne(LongFamily):
    """One node's CPU panel over the long range: seconds a second by
    mode, for a target scraped through the whole span (a target that ends
    or begins inside it selects fewer rows, a narrowed launch of another
    length bucket; tier-1 tests hold the answers for such targets)."""

    def draw(self, rng, ds):
        p = super().draw(rng, ds)
        whole = np.nonzero((ds.first == 0) & (ds.last == ds.ticks))[0]
        p["instance"] = ds.instances[int(whole[rng.integers(0, len(whole))])]
        return p

    def query(self, p, ds):
        return (f'sum by (mode) (rate({CPU}{{instance="{p["instance"]}"}}'
                '[1m]))')

    def reference(self, p, ds):
        s, steps = ds.samples(CPU), self.steps(p, ds)
        keep = ref.matches(s, [("instance", "=", p["instance"])])
        rate, ok = ref.extrapolated_rate(s, keep, steps, self.range_ms)
        by, total, present = aggregate(
            "sum", rate, ok, _columns(s, keep, ["mode"]))
        return ref.points(by, steps, total, present)


class NetReceiveFleet(LongFamily):
    """USE Method / Cluster, network: bytes a second received by the
    fleet, loopback left out. Counters up to 2.6e14: the precision case."""

    def query(self, p, ds):
        return f'sum(rate({NET}{{device!="lo"}}[1m]))'

    def reference(self, p, ds):
        s, steps = ds.samples(NET), self.steps(p, ds)
        keep = ref.matches(s, [("device", "!=", "lo")])
        rate, ok = ref.extrapolated_rate(s, keep, steps, self.range_ms)
        _, total, present = aggregate("sum", rate, ok, [])
        return ref.points([], steps, total, present)


class LoadMaxByInstance(LongFamily):
    """Saturation: every target's highest load1 a minute. A gauge, `max`
    twice, a 101,000-row answer."""

    def query(self, p, ds):
        return f"max by (instance) (max_over_time({LOAD1}[1m]))"

    def reference(self, p, ds):
        s, steps = ds.samples(LOAD1), self.steps(p, ds)
        keep = np.ones(len(s.first), dtype=bool)
        top, ok = over_time("max", s, keep, steps, self.range_ms)
        by, out, present = aggregate(
            "max", top, ok, _columns(s, keep, ["instance"]))
        return ref.points(by, steps, out, present)


class MemAvailableFleet(LongFamily):
    """Memory the fleet has left: `agg(selector)`, the newest sample of
    every target in the 5 m lookback, carried forward from its bucket."""

    def query(self, p, ds):
        return f"sum({MEM_AVAILABLE})"

    def reference(self, p, ds):
        s, steps = ds.samples(MEM_AVAILABLE), self.steps(p, ds)
        keep = np.ones(len(s.first), dtype=bool)
        v, ok = ref.instant(s, keep, steps,
                            ds.config["query"]["lookback_s"] * 1000)
        _, total, present = aggregate("sum", v, ok, [])
        return ref.points([], steps, total, present)


class FsAvailMin(LongFamily):
    """The fullest filesystem of a kind across the fleet: the least bytes
    left by mountpoint, tmpfs left out. A gauge, `min` twice, a negative
    matcher."""

    def query(self, p, ds):
        return (f'min by (mountpoint) (min_over_time({FS_AVAIL}'
                '{fstype!="tmpfs"}[1m]))')

    def reference(self, p, ds):
        s, steps = ds.samples(FS_AVAIL), self.steps(p, ds)
        keep = ref.matches(s, [("fstype", "!=", "tmpfs")])
        low, ok = over_time("min", s, keep, steps, self.range_ms)
        by, out, present = aggregate(
            "min", low, ok, _columns(s, keep, ["mountpoint"]))
        return ref.points(by, steps, out, present)
