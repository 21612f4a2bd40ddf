"""The PromQL families of the fleet that is written while it is read
(`generators/node-exporter-live.py`): the parents' expressions,
tolerances and dispatch (`promfam.py`, `promlong.py`), evaluated where a
target is scraped at an offset of its own inside the interval, and over a
range that ends where the loop says: the acknowledged frontier at the
statement's send (`loops/remote-write.py`).

`promref.py` evaluates series that share one scrape grid. Here series s's
sample k lies at `times[k] + offset[s]`, and every function of
`promref.py` / `promlong.py` that cuts a window out of the grid is
invariant under a shift of the samples and the steps together (it reads
differences of times only): the value of series s at step t is the shared
grid's at step `t - offset[s]`. `shifted` does that through the parents'
own float64 code, a group of series with one offset at a time. Which
samples a window holds depends on the offset's whole second alone (steps
are whole seconds, offsets never are), so the functions that read nothing
else of the times (`instant`, `over_time`) take ten groups
(`by="second"`); `rate`, whose extrapolation reads the distances to the
window's edges, would take one a target, a thousand calls a statement,
and is written out here for all series at once (`extrapolated_rate`,
`promref.py`'s arithmetic line for line; `benchmark/test_remote_write.py`
holds the two equal).
"""

from __future__ import annotations

import numpy as np

from . import promfam, promlong
from . import promref as ref


def _second_above(offset: np.ndarray) -> np.ndarray:
    """An offset's next whole second, in ms: a grid sample at
    `times[k] + offset` lies at or before a whole-second step exactly
    where `times[k] + this` does."""
    return -(-offset // 1000) * 1000


def shifted(fn, samples, keep, steps, *args, by: str = "offset", **kw):
    """`fn(samples, keep, steps, ...)` -> (values [S', T'], ok) of
    `promref.py` (`extrapolated_rate`, `instant`) or `promlong.py`
    (`over_time`, through `lambda`), with every kept series read at its
    own offset (`by="second"`: at its offset's next whole second, for a
    function that reads which samples a window holds and no distance)."""
    kept = np.flatnonzero(keep)
    values = np.full((len(kept), len(steps)), np.nan)
    ok = np.zeros(values.shape, dtype=bool)
    offsets = samples.offset[kept]
    if by == "second":
        offsets = _second_above(offsets)
    for off in np.unique(offsets):
        rows = np.flatnonzero(offsets == off)
        one = np.zeros(len(keep), dtype=bool)
        one[kept[rows]] = True
        values[rows], ok[rows] = fn(samples, one, steps - off, *args, **kw)
    return values, ok


def extrapolated_rate(samples, keep, steps, range_ms: int,
                      without_seam_at: int = None):
    """`promref.extrapolated_rate` (a counter's rate a second) for series
    that each hold the grid at an offset: the same windows `(t - range,
    t]`, resets and extrapolation, every time taken where the sample
    lies. -> (values [S', T'], ok). `without_seam_at`: a tick; a window
    that holds a series' sample of that tick and of the one before it
    loses the growth between the two (the seam control)."""
    v = samples.values[keep]
    n_ticks = v.shape[1]
    first, last = samples.first[keep], samples.last[keep]
    offset = samples.offset[keep]
    # which ticks a window holds: one search a whole second of offset
    above = _second_above(offset)
    seconds, group = np.unique(above, return_inverse=True)
    lo = np.stack([np.searchsorted(samples.times, steps - c - range_ms,
                                   side="right") for c in seconds])[group]
    hi = np.stack([np.searchsorted(samples.times, steps - c, side="right")
                   for c in seconds])[group]
    lo = np.maximum(lo, first[:, None])
    hi = np.maximum(np.minimum(hi, last[:, None]), lo)
    n = hi - lo
    ok = n >= 2
    i0 = np.clip(lo, 0, n_ticks - 1)
    i1 = np.clip(hi - 1, 0, n_ticks - 1)
    first_v = np.take_along_axis(v, i0, axis=1)
    last_v = np.take_along_axis(v, i1, axis=1)
    # the value before each reset, summed over the resets up to a tick
    drop = np.where(v[:, 1:] < v[:, :-1], v[:, :-1], 0.0)
    k = np.arange(1, n_ticks)[None, :]
    exists = (k > first[:, None]) & (k < last[:, None])
    resets = np.concatenate(
        [np.zeros((len(v), 1)), np.cumsum(np.where(exists, drop, 0.0),
                                          axis=1)], axis=1)
    result = last_v - first_v + np.take_along_axis(resets, i1, axis=1) \
        - np.take_along_axis(resets, i0, axis=1)
    if without_seam_at is not None:
        k = without_seam_at
        across = np.where(v[:, k] < v[:, k - 1], v[:, k],
                          v[:, k] - v[:, k - 1])
        result = result - np.where((lo < k) & (hi > k), across[:, None], 0.0)
    t_first = (samples.times[i0] + offset[:, None]).astype(np.float64)
    t_last = (samples.times[i1] + offset[:, None]).astype(np.float64)
    ends = steps[None, :].astype(np.float64)
    with np.errstate(all="ignore"):
        to_start = (t_first - (ends - range_ms)) / 1e3
        to_end = (ends - t_last) / 1e3
        sampled = (t_last - t_first) / 1e3
        interval = sampled / np.maximum(n - 1, 1)
        threshold = interval * 1.1
        to_start = np.where(to_start >= threshold, interval / 2, to_start)
        to_zero = np.where((result > 0) & (first_v >= 0),
                           sampled * (first_v / result), np.inf)
        to_start = np.minimum(to_start, to_zero)
        to_end = np.where(to_end >= threshold, interval / 2, to_end)
        factor = (sampled + to_start + to_end) / sampled
        out = result * (factor / (range_ms / 1e3))
    return np.where(ok, out, np.nan), ok


class Live:
    """What a live family adds to its parent: its range ends where the
    loop says (`end_s` in the statement's parameters, seconds from the
    data's start; a draw of its own ends inside the loaded history, for
    the warm statements), its panel's span and step are those of
    `panel` in the configuration's `panels` (one fleet serves the
    dashboard's 15 min at 15 s and the overview's 100 min at 1 m), and
    its reference reads every series at its offset. `end_grid_s`: the
    grid the warm statements' ends are drawn on, the panel's step unless
    given."""
    live_end = True
    panel = "dashboard"
    end_grid_s = None

    def _panel(self, ds) -> dict:
        return ds.config["panels"][self.panel]

    def draw(self, rng, ds) -> dict:
        """`PromFamily.draw` over this panel's span: an end on the
        grid, from `end_from_s` to the end of the load."""
        self._debug = ds.debug
        q = self._panel(ds)
        grid = self.end_grid_s or q["step_s"]
        first = max(q["end_from_s"], q["span_s"] + self.range_ms // 1000)
        steps = (ds.ticks * ds.tick_ms // 1000 - first) // grid
        return {"end_s": int(first + grid * rng.integers(0, steps + 1))}

    def sql(self, p: dict, ds) -> str:
        q = self._panel(ds)
        end = ds.t0_ms // 1000 + p["end_s"]
        return (f"TQL EVAL ({end - q['span_s']}, {end}, '{q['step_s']}s') "
                + self.query(p, ds))

    def steps(self, p: dict, ds) -> np.ndarray:
        q = self._panel(ds)
        end = ds.t0_ms + p["end_s"] * 1000
        return np.arange(end - q["span_s"] * 1000, end + 1,
                         q["step_s"] * 1000, dtype=np.int64)

    def frontier(self, end_s: int, ds) -> dict:
        """The parameters of a statement whose range ends at `end_s`;
        the rest (one node's target) stays as drawn."""
        return {"end_s": int(end_s)}

    def reference(self, p, ds):
        """The family's float64 answer over the generator's samples (no
        step reaches past the statement's end, so none written after it
        is read); with `without_newest_block` over the table as it was
        one block earlier (`loops/remote-write.py`'s stale control); with
        `without_seam`, where the family reads a window's growth from
        two scans, what a program answers that adds the two and leaves
        out the growth between the last loaded sample and the first
        written one (the seam control)."""
        if p.get("without_newest_block"):
            ds = ds.without_block(
                ds.newest_block_at(int(self.steps(p, ds)[-1])))
        return self._reference(p, ds)


def _whole_targets(ds) -> np.ndarray:
    """Targets scraped through the loaded span and the live rounds."""
    return np.nonzero((ds.first == 0) & (ds.last >= ds.total_ticks))[0]


class CpuBusyLive(Live, promfam.CpuBusy):
    def _reference(self, p, ds):
        s, steps = ds.samples(promfam.CPU), self.steps(p, ds)
        keep = ref.matches(s, [("mode", "=", "idle")])
        rate, ok = extrapolated_rate(s, keep, steps, self.range_ms)
        by, avg, present = ref.aggregate(
            "avg", rate, ok, promfam._columns(s, keep, ["instance"]))
        return ref.points(by, steps, 100.0 - avg * 100.0, present)


class CpuByModeOneLive(Live, promfam.CpuByMode):
    def __init__(self, name, tolerance, **kw):
        super().__init__(name, tolerance, one=True, **kw)

    def draw(self, rng, ds):
        p = Live.draw(self, rng, ds)
        whole = _whole_targets(ds)
        p["instance"] = ds.instances[int(whole[rng.integers(0, len(whole))])]
        return p

    def _reference(self, p, ds):
        s, steps = ds.samples(promfam.CPU), self.steps(p, ds)
        keep = ref.matches(s, [("instance", "=", p["instance"])])
        rate, ok = extrapolated_rate(s, keep, steps, self.range_ms)
        by, total, present = ref.aggregate(
            "sum", rate, ok, promfam._columns(s, keep, ["mode"]))
        return ref.points(by, steps, total, present)


class MemUsedRatioLive(Live, promfam.MemUsedRatio):
    def _reference(self, p, ds):
        steps = self.steps(p, ds)
        lookback = ds.config["query"]["lookback_s"] * 1000
        a = ds.samples(promfam.MEM_AVAILABLE)
        b = ds.samples(promfam.MEM_TOTAL)
        every_a = np.ones(len(a.first), dtype=bool)
        every_b = np.ones(len(b.first), dtype=bool)
        av, aok = shifted(ref.instant, a, every_a, steps, lookback,
                          by="second")
        bv, bok = shifted(ref.instant, b, every_b, steps, lookback,
                          by="second")
        names = sorted(a.labels)
        li, ri = ref.one_to_one(promfam._columns(a, every_a, names),
                                promfam._columns(b, every_b, names))
        with np.errstate(all="ignore"):
            used = 1.0 - av[li] / bv[ri]
        return ref.points([a.labels[n][li] for n in names], steps, used,
                          aok[li] & bok[ri])


class NetReceiveTopkLive(Live, promfam.NetReceiveTopk):
    def _reference(self, p, ds):
        s, steps = ds.samples(promfam.NET), self.steps(p, ds)
        keep = ref.matches(s, [("device", "!=", "lo")])
        rate, ok = extrapolated_rate(s, keep, steps, self.range_ms)
        by, total, present = ref.aggregate(
            "sum", rate, ok, promfam._columns(s, keep, ["instance"]))
        return self._ranked(ref.points(by, steps, total,
                                       ref.topk(5, total, present)))


class LongLive(Live):
    """A "Last 24 hours" panel ends at the frontier like the others, any
    second of its one-minute step: its windows are cut off the grid of
    every statement before it, and the newest of them lies across the
    seam between the load and what was written. The warm statements'
    ends are drawn so too."""
    panel = "overview"
    end_grid_s = 1


class CpuUtilFleetLive(LongLive, promlong.CpuUtilFleet):
    def _reference(self, p, ds):
        s, steps = ds.samples(promfam.CPU), self.steps(p, ds)
        keep = ref.matches(s, [("mode", "=", "idle")])
        rate, ok = extrapolated_rate(
            s, keep, steps, self.range_ms,
            without_seam_at=ds.ticks if p.get("without_seam") else None)
        _, avg, present = promlong.aggregate("avg", rate, ok, [])
        return ref.points([], steps, 1.0 - avg, present)


class LoadMaxByInstanceLive(LongLive, promlong.LoadMaxByInstance):
    def _reference(self, p, ds):
        s, steps = ds.samples(promlong.LOAD1), self.steps(p, ds)
        keep = np.ones(len(s.first), dtype=bool)
        top, ok = shifted(
            lambda *a: promlong.over_time("max", *a), s, keep, steps,
            self.range_ms, by="second")
        by, out, present = promlong.aggregate(
            "max", top, ok, promfam._columns(s, keep, ["instance"]))
        return ref.points(by, steps, out, present)
