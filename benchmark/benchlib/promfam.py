"""The PromQL statement families of the node_exporter deployment as
builders: a file under `benchmark/families/` is one call of one of these.
Every statement is a range query over the last `span_s` before an end
drawn from the seed and aligned to the step, sent as `TQL EVAL (start,
end, '<step>s') <query>` over HTTP `/v1/sql` (what `/v1/promql` runs).
Each family renders its query, evaluates the same query in float64 with
`benchlib/promref.py` over the generator's arrays, and parses an answer
(label columns in sorted order, then `ts`, then `value`) into the same
{(label values..., step ms): [value]} form.

The executed dispatch names the platform the window kernel ran on. A
family file states it for the chip; at the configuration's debug size
(`selftest.py`, `--debug-platform cpu`) the platform's name is swapped.
"""

from __future__ import annotations

import numpy as np

from . import promref as ref

ROW_PATH_ON_TPU = "promql-row-path (window kernel on tpu)"

CPU = "node_cpu_seconds_total"
NET = "node_network_receive_bytes_total"
FS_AVAIL = "node_filesystem_avail_bytes"
MEM_AVAILABLE = "node_memory_MemAvailable_bytes"
MEM_TOTAL = "node_memory_MemTotal_bytes"


class PromFamily:
    via = "http"
    range_ms = 300_000

    def __init__(self, name: str, tolerance: dict,
                 dispatch: str = ROW_PATH_ON_TPU):
        self.name, self.tolerance = name, tolerance
        self._dispatch, self._debug = dispatch, False

    @property
    def dispatch(self) -> str:
        if self._debug:
            return self._dispatch.replace("on tpu)", "on cpu)")
        return self._dispatch

    def draw(self, rng, ds) -> dict:
        """An end aligned to the step, from `end_from_s` (or from where
        the first window's range begins with the loaded data, if that is
        later) to the end of the load."""
        self._debug = ds.debug
        q = ds.config["query"]
        first = max(q["end_from_s"], q["span_s"] + self.range_ms // 1000)
        steps = (ds.ticks * ds.tick_ms // 1000 - first) // q["step_s"]
        return {"end_s": int(first + q["step_s"]
                             * rng.integers(0, steps + 1))}

    def query(self, p: dict, ds) -> str:
        raise NotImplementedError

    def sql(self, p: dict, ds) -> str:
        q = ds.config["query"]
        end = ds.t0_ms // 1000 + p["end_s"]
        return (f"TQL EVAL ({end - q['span_s']}, {end}, '{q['step_s']}s') "
                + self.query(p, ds))

    def steps(self, p: dict, ds) -> np.ndarray:
        q = ds.config["query"]
        end = ds.t0_ms + p["end_s"] * 1000
        return np.arange(end - q["span_s"] * 1000, end + 1,
                         q["step_s"] * 1000, dtype=np.int64)

    def parse(self, rows, ds) -> dict:
        return {tuple(r[:-2]) + (int(r[-2]),): [float(r[-1])]
                for r in rows}


def _columns(samples, keep, names) -> list:
    return [samples.labels[n][keep] for n in sorted(names)]


class CpuBusy(PromFamily):
    """HostHighCpuLoad's expression: the busy share of every target in
    percent, from the idle counters of its CPUs."""

    def query(self, p, ds):
        return (f'100 - (avg by (instance) (rate({CPU}{{mode="idle"}}'
                '[5m])) * 100)')

    def reference(self, p, ds):
        s, steps = ds.samples(CPU), self.steps(p, ds)
        keep = ref.matches(s, [("mode", "=", "idle")])
        rate, ok = ref.extrapolated_rate(s, keep, steps, self.range_ms)
        by, avg, present = ref.aggregate(
            "avg", rate, ok, _columns(s, keep, ["instance"]))
        return ref.points(by, steps, 100.0 - avg * 100.0, present)


class CpuByMode(PromFamily):
    """Node Exporter Full's CPU panel: seconds a second by mode, over the
    fleet or (`one=True`) over one target drawn from the seed."""

    def __init__(self, name, tolerance, one: bool = False, **kw):
        super().__init__(name, tolerance, **kw)
        self.one = one

    def draw(self, rng, ds):
        p = super().draw(rng, ds)
        if self.one:
            # a target that is scraped through the whole span (980 of
            # 1,020; the rebooted ones among them): one that ends or
            # begins inside it has fewer samples in the window, the
            # matrix another width, and that is a program the window
            # would compile (0.4-0.7 s in a family of 80 ms)
            whole = np.nonzero((ds.first == 0) & (ds.last == ds.ticks))[0]
            p["instance"] = ds.instances[int(whole[rng.integers(
                0, len(whole))])]
        return p

    def query(self, p, ds):
        sel = f'{{instance="{p["instance"]}"}}' if self.one else ""
        return f"sum by (mode) (rate({CPU}{sel}[5m]))"

    def reference(self, p, ds):
        s, steps = ds.samples(CPU), self.steps(p, ds)
        keep = ref.matches(
            s, [("instance", "=", p["instance"])] if self.one else [])
        rate, ok = ref.extrapolated_rate(s, keep, steps, self.range_ms)
        by, total, present = ref.aggregate(
            "sum", rate, ok, _columns(s, keep, ["mode"]))
        return ref.points(by, steps, total, present)


class MemUsedRatio(PromFamily):
    """HostOutOfMemory's two gauges as the used share: two tables, instant
    selectors with lookback, one-to-one matching on (instance, job)."""

    def query(self, p, ds):
        return f"1 - {MEM_AVAILABLE} / {MEM_TOTAL}"

    def reference(self, p, ds):
        steps = self.steps(p, ds)
        lookback = ds.config["query"]["lookback_s"] * 1000
        a, b = ds.samples(MEM_AVAILABLE), ds.samples(MEM_TOTAL)
        every_a = np.ones(len(a.first), dtype=bool)
        every_b = np.ones(len(b.first), dtype=bool)
        av, aok = ref.instant(a, every_a, steps, lookback)
        bv, bok = ref.instant(b, every_b, steps, lookback)
        names = sorted(a.labels)
        li, ri = ref.one_to_one(_columns(a, every_a, names),
                                _columns(b, every_b, names))
        with np.errstate(all="ignore"):
            used = 1.0 - av[li] / bv[ri]
        return ref.points([a.labels[n][li] for n in names], steps, used,
                          aok[li] & bok[ri])


class NetReceiveTopk(PromFamily):
    """The five targets that receive most, per step: a negative matcher,
    and `topk`, which keeps the chosen series' own labels. Compared by
    rank: {(rank, step ms): [value, the chosen target's number]}, so that
    another target chosen is a number off (a wrong answer), not a key
    that differs, which would leave the check nothing to print."""

    def query(self, p, ds):
        return (f'topk(5, sum by (instance) (rate({NET}{{device!="lo"}}'
                '[5m])))')

    @staticmethod
    def _ranked(chosen: dict) -> dict:
        """{(instance, step): [value]} -> by rank within a step."""
        by_step = {}
        for (instance, t), (v,) in chosen.items():
            number = float(instance.split("_")[1].split(":")[0])
            by_step.setdefault(t, []).append((v, number))
        return {(rank, t): list(pair) for t, pairs in by_step.items()
                for rank, pair in enumerate(sorted(pairs, reverse=True))}

    def reference(self, p, ds):
        s, steps = ds.samples(NET), self.steps(p, ds)
        keep = ref.matches(s, [("device", "!=", "lo")])
        rate, ok = ref.extrapolated_rate(s, keep, steps, self.range_ms)
        by, total, present = ref.aggregate(
            "sum", rate, ok, _columns(s, keep, ["instance"]))
        return self._ranked(ref.points(by, steps, total,
                                       ref.topk(5, total, present)))

    def parse(self, rows, ds):
        return self._ranked(super().parse(rows, ds))


class FsPredict(PromFamily):
    """HostDiskWillFillIn24Hours' function, its window cut from 1 h to
    10 m and its horizon from 24 h to 1 h (`reduced`): the bytes every
    filesystem but tmpfs will have left, by least squares."""
    range_ms = 600_000
    ahead_s = 3600

    def query(self, p, ds):
        return (f'predict_linear({FS_AVAIL}{{fstype!="tmpfs"}}[10m], '
                f'{self.ahead_s})')

    def reference(self, p, ds):
        s, steps = ds.samples(FS_AVAIL), self.steps(p, ds)
        keep = ref.matches(s, [("fstype", "!=", "tmpfs")])
        pred, ok = ref.predict_linear(s, keep, steps, self.range_ms,
                                      float(self.ahead_s))
        return ref.points(_columns(s, keep, list(s.labels)), steps, pred, ok)
