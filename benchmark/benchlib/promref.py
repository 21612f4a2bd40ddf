"""The plain reference for the PromQL families: PromQL's semantics in
float64 numpy, written from Prometheus's documentation (querying basics,
functions, operators; `extrapolatedRate` and `linearRegression` as
promql/functions.go of Prometheus 3 describes them) and not from the
program's `ops/window.py`. It imports nothing of the program.

A metric is a `Samples`-shaped object (generators/node-exporter.py): the
scrape times `times[T]` (ms, shared by every series, as one scrape
interval makes them), `values[S, T]` float64, per series the ticks that
exist `first[s] <= k < last[s]`, and `labels[name][S]`.

Semantics fixed here: a range selector at step `t` holds the samples in
`(t - range, t]`; an instant selector takes the newest sample in
`(t - lookback, t]`; `rate` / `increase` need two samples, add the value
before every counter reset and extrapolate to the window's edges as
Prometheus does (to an edge that is nearer than 1.1 average sample
intervals, else by half an interval, and never below a counter's zero);
aggregation groups by the `by` labels and drops the metric name; binary
operators between vectors match one-to-one on the whole label set; `topk`
picks per step; `predict_linear` is the least-squares line through the
window's samples, evaluated `ahead_s` after the step.
"""

from __future__ import annotations

import numpy as np


def matches(samples, matchers) -> np.ndarray:
    """[(label, "=" | "!=", value)] -> bool[S]."""
    keep = np.ones(len(samples.first), dtype=bool)
    for label, op, value in matchers:
        hit = samples.labels[label] == value
        keep &= hit if op == "=" else ~hit
    return keep


def _window(samples, keep, steps, range_ms: int):
    """Per kept series and step the existing samples of (t - range, t]
    as ticks [lo, hi): the shared scrape grid cut by what exists."""
    lo = np.searchsorted(samples.times, steps - range_ms, side="right")
    hi = np.searchsorted(samples.times, steps, side="right")
    lo = np.maximum(lo[None, :], samples.first[keep][:, None])
    hi = np.minimum(hi[None, :], samples.last[keep][:, None])
    return lo, np.maximum(hi, lo)


def extrapolated_rate(samples, keep, steps, range_ms: int, *,
                      counter: bool = True, per_second: bool = True):
    """rate (default), increase (per_second=False) or delta (counter=False,
    per_second=False) of the kept series -> (values [S', T'], ok)."""
    v = samples.values[keep]
    n_ticks = v.shape[1]
    lo, hi = _window(samples, keep, steps, range_ms)
    n = hi - lo
    ok = n >= 2
    i0 = np.clip(lo, 0, n_ticks - 1)
    i1 = np.clip(hi - 1, 0, n_ticks - 1)
    first_v = np.take_along_axis(v, i0, axis=1)
    last_v = np.take_along_axis(v, i1, axis=1)
    result = last_v - first_v
    if counter:
        # the value before each reset, summed over the resets up to a tick
        drop = np.where(v[:, 1:] < v[:, :-1], v[:, :-1], 0.0)
        k = np.arange(1, n_ticks)[None, :]
        exists = (k > samples.first[keep][:, None]) & \
            (k < samples.last[keep][:, None])
        resets = np.concatenate(
            [np.zeros((len(v), 1)), np.cumsum(np.where(exists, drop, 0.0),
                                              axis=1)], axis=1)
        result = result + np.take_along_axis(resets, i1, axis=1) \
            - np.take_along_axis(resets, i0, axis=1)
    t_first = samples.times[i0].astype(np.float64)
    t_last = samples.times[i1].astype(np.float64)
    ends = steps[None, :].astype(np.float64)
    with np.errstate(all="ignore"):
        to_start = (t_first - (ends - range_ms)) / 1e3
        to_end = (ends - t_last) / 1e3
        sampled = (t_last - t_first) / 1e3
        interval = sampled / np.maximum(n - 1, 1)
        threshold = interval * 1.1
        to_start = np.where(to_start >= threshold, interval / 2, to_start)
        if counter:
            to_zero = np.where((result > 0) & (first_v >= 0),
                               sampled * (first_v / result), np.inf)
            to_start = np.minimum(to_start, to_zero)
        to_end = np.where(to_end >= threshold, interval / 2, to_end)
        factor = (sampled + to_start + to_end) / sampled
        if per_second:
            factor = factor / (range_ms / 1e3)
        out = result * factor
    return np.where(ok, out, np.nan), ok


def instant(samples, keep, steps, lookback_ms: int):
    """The newest sample of every kept series in (t - lookback, t]."""
    idx = np.searchsorted(samples.times, steps, side="right") - 1
    i = np.minimum(idx[None, :], samples.last[keep][:, None] - 1)
    ok = (i >= samples.first[keep][:, None]) & (i >= 0)
    i = np.clip(i, 0, len(samples.times) - 1)
    ok &= samples.times[i] > steps[None, :] - lookback_ms
    v = np.take_along_axis(samples.values[keep], i, axis=1)
    return np.where(ok, v, np.nan), ok


def predict_linear(samples, keep, steps, range_ms: int, ahead_s: float):
    """Least squares through the window's samples with x the seconds from
    the step; the line's value `ahead_s` later. Two samples or more."""
    v = samples.values[keep]
    first, last = samples.first[keep], samples.last[keep]
    out = np.full((len(v), len(steps)), np.nan)
    ok = np.zeros(out.shape, dtype=bool)
    for j, t in enumerate(steps):
        a = int(np.searchsorted(samples.times, t - range_ms, side="right"))
        b = int(np.searchsorted(samples.times, t, side="right"))
        if b - a < 2:
            continue
        k = np.arange(a, b)[None, :]
        m = ((k >= first[:, None]) & (k < last[:, None])).astype(np.float64)
        n = m.sum(axis=1)
        x = ((samples.times[a:b] - t) / 1e3)[None, :]
        # around the window's own mean: sums of 1e12-sized values would
        # spend float64's digits on the level, not on the slope
        block = v[:, a:b]
        level = (block * m).sum(axis=1) / np.maximum(n, 1)
        y = (block - level[:, None]) * m
        with np.errstate(all="ignore"):
            sx, sy = (x * m).sum(axis=1), y.sum(axis=1)
            sxx, sxy = (x * x * m).sum(axis=1), (x * y).sum(axis=1)
            slope = (sxy - sx * sy / n) / (sxx - sx * sx / n)
            intercept = sy / n - slope * sx / n
            out[:, j] = level + intercept + slope * ahead_s
        ok[:, j] = n >= 2
    return np.where(ok, out, np.nan), ok


def aggregate(op: str, values, ok, by: list):
    """`sum` or `avg` `by` the given label columns ([S] each) ->
    (label columns of the groups, values [G, T'], ok)."""
    joined = by[0].astype(str) if len(by) == 1 else np.array(
        ["\x00".join(map(str, row)) for row in zip(*by)])
    _, firsts, group = np.unique(joined, return_index=True,
                                 return_inverse=True)
    groups = len(firsts)
    total = np.zeros((groups, values.shape[1]))
    count = np.zeros((groups, values.shape[1]))
    for j in range(values.shape[1]):
        total[:, j] = np.bincount(group, np.where(ok[:, j], values[:, j],
                                                  0.0), groups)
        count[:, j] = np.bincount(group, ok[:, j], groups)
    if op == "avg":
        total = total / np.maximum(count, 1)
    elif op != "sum":
        raise ValueError(f"no reference for aggregate {op}")
    present = count > 0
    return ([col[firsts] for col in by], np.where(present, total, np.nan),
            present)


def topk(k: int, values, ok):
    """Per step the k largest present values -> the kept points' mask."""
    ranked = np.where(ok, values, -np.inf)
    order = np.argsort(-ranked, axis=0, kind="stable")
    keep = np.zeros(ok.shape, dtype=bool)
    np.put_along_axis(keep, order[:k], True, axis=0)
    return keep & ok


def one_to_one(left_labels: list, right_labels: list):
    """Vector matching on the whole label set (the metric name is not a
    label here): -> (index into the left side, index into the right side)
    of the pairs. A label set twice on one side is an error, as it is in
    Prometheus."""
    right = {}
    for j, key in enumerate(zip(*right_labels)):
        if key in right:
            raise ValueError(f"many-to-many matching: {key} twice")
        right[key] = j
    li, ri = [], []
    for i, key in enumerate(zip(*left_labels)):
        if key in right:
            li.append(i)
            ri.append(right[key])
    return np.array(li, dtype=np.int64), np.array(ri, dtype=np.int64)


def points(label_columns: list, steps, values, ok) -> dict:
    """A range query's answer as the families compare it:
    {(label values..., step ms): [value]} for every present point."""
    rows, cols = np.nonzero(ok)
    labels = list(zip(*[col[rows] for col in label_columns])) \
        if label_columns else [()] * len(rows)
    stamps = steps[cols].tolist()
    vals = values[rows, cols].tolist()
    return {lab + (t,): [v] for lab, t, v in zip(labels, stamps, vals)}
