"""PromQL range-vector evaluation as vmapped window reductions.

Reference behavior: src/promql — `RangeManipulate` materializes per-step
window views (`RangeArray`, a DictionaryArray trick) and evaluates range
functions row-by-row per series (aggr_over_time.rs, extrapolate_rate.rs).

TPU design: series are laid out as a dense padded matrix [S, L] sorted by
time within each row. For an aligned step grid t_j = start + j*step, the
window (t_j - range, t_j] of every series is located with a vmapped
`searchsorted`, and:

- sum/count/avg/stddev/rate/increase/delta/changes/resets/last/first/idelta
  evaluate O(1) per window from per-series prefix sums (cumsum path):
  a few values of a channel read at a window's bounds;
- min/max/deriv/predict_linear reduce the window's samples;
- quantile/mad/holt_winters need the window's samples side by side:
  they gather bounded windows (MAXW static) and sort or scan them.

How a sample is READ has two forms, chosen from the shapes alone
(`window_read_path`), because a gather is what is slow on a TPU (10 to
14 ns a value fetched on a v5e) and a compare is not (0.001 to 0.007 ns a
cell; the measurements stand beside `_DENSE_WINDOW_MAX_RATIO` and
`_DENSE_POINT_MAX_LEN`):

- *dense*: a row is sorted and a window is the index range [lo, hi), so a
  window's reduction is `reduce_l(where(lo <= l < hi, row[l], identity))`
  and a point read is `sum_l(where(l == e, bits(row[l]), 0))`: compare,
  select and reduce fused over the row axis, O(S*T*L), no [.., W] array
  of fetched samples. What a dashboard's rows take (L of 128 to a few
  thousand): predict_linear over [3072, 256] x 64 steps 1,277 -> 2 ms,
  rate's five channel reads over [65536, 129] x 84 292 -> 4 ms.
- *gather*: `take_along_axis`, O(S*T*W) or O(S*T): long rows, and the
  ops that need the samples side by side.

Counter resets are handled with a per-series cumulative correction array so
`increase` is a pure difference of adjusted prefix values — no per-window
scan. Extrapolation follows Prometheus `extrapolatedRate` semantics
(reference: src/promql/src/functions/extrapolate_rate.rs:53-200).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

TS_PAD = np.iinfo(np.int64).max

CUMSUM_OPS = {
    "sum_over_time", "count_over_time", "avg_over_time", "stddev_over_time",
    "stdvar_over_time", "last_over_time", "first_over_time", "present_over_time",
    "rate", "increase", "delta", "idelta", "irate_num", "changes", "resets",
}
GATHER_OPS = {"min_over_time", "max_over_time", "quantile_over_time",
              "deriv", "predict_linear", "mad_over_time", "holt_winters"}
RANGE_OPS = CUMSUM_OPS | GATHER_OPS
#: gather-family ops that only reduce a window (sum, mean, min, max)
REDUCE_OPS = frozenset({"min_over_time", "max_over_time", "deriv",
                        "predict_linear"})

#: A window reduction (REDUCE_OPS) goes dense while the row is at most
#: this many times the window a gather would fetch: dense costs a*S*T*L,
#: the gather b*S*T*maxw. Measured on a v5e (PR 29, [3072, L] x 64 steps,
#: bounds given): dense predict_linear 1.97 ms at L = 256, 2.95 at 1024,
#: 5.86 at 4096, 21.4 at 16384 (a = 0.0066 ns a cell; 1.5 ms of it is the
#: launch), max_over_time [65536, 128] x 64 in 4.4 ms (0.0082); the gather
#: 1,277 ms at maxw = L = 256, and at maxw = 64 322 ms at L = 256 or 1024
#: and 545 at L = 4096 (b = 25.5 ns a fetched sample for the least
#: squares' two arrays and rising with the row; 12.8 for min / max's one,
#: 161 ms). b / a is 1,560 to 3,860: the constant sits under both. The
#: PromQL engine hands maxw = the row, so there it never crosses.
_DENSE_WINDOW_MAX_RATIO = 1024

#: A point read (one value of a channel at a window's bound: every
#: CUMSUM_OPS function but the counts) goes dense up to this row length:
#: dense costs O(S*T*L) a channel, the gather O(S*T). Measured on a v5e
#: (PR 29, `_stack_rate`'s five channels read at 84 positions a series;
#: ms gathered / dense): [65536, 129] 292.0 / 3.95, [8192, 129] 36.1 /
#: 1.12, [16384, 1025] 95.2 / 9.88, and at [4096, L + 1] L = 2048 24.4 /
#: 3.56, 4096 25.3 / 9.50, 8192 34.9 / 14.3, 16384 40.2 / 35.9. A gathered
#: value costs 10.6 to 23 ns (more in longer rows), a compared cell
#: 0.0010 to 0.0013 ns: they meet past L = 16384, and the constant is the
#: longest row at which dense was still more than twice as fast.
_DENSE_POINT_MAX_LEN = 8192

#: cells of one dense block: were the compare-select-reduce ever left
#: unfused, this bounds what it would write (2**26 f32 = 256 MB)
_DENSE_BLOCK_CELLS = 1 << 26


def window_read_path(op: str, row_len: int, maxw: int = 0) -> Optional[str]:
    """"dense" or "gather": the form that reads `op`'s samples from rows
    of `row_len` (`maxw`: the samples a gathered window would hold); None
    for an op that reads no sample (a count is a difference of bounds).
    The kernels below choose by this function and by nothing else, so a
    caller that asks it knows what ran."""
    if op in ("count_over_time", "present_over_time"):
        return None
    if op in CUMSUM_OPS:
        return "dense" if _point_reads_dense(row_len) else "gather"
    if op in REDUCE_OPS and row_len <= _DENSE_WINDOW_MAX_RATIO * max(maxw, 1):
        return "dense"
    return "gather"


def _point_reads_dense(row_len: int) -> bool:
    return row_len <= _DENSE_POINT_MAX_LEN


class SeriesMatrix:
    """Dense padded [num_series, max_len] layout of a set of time series."""

    __slots__ = ("ts", "values", "lengths", "num_series", "max_len",
                 "_base")

    def __init__(self, ts: np.ndarray, values: np.ndarray, lengths: np.ndarray):
        self.ts = ts
        self.values = values
        self.lengths = lengths
        self.num_series, self.max_len = ts.shape
        self._base = None

    @property
    def value_base(self) -> np.ndarray:
        """[S] float64: every series' first sample (0 for an empty row).
        The device computes in float32, and a counter that has run for a
        month (2.6e6 CPU seconds, 1e12 bytes) has no digits left for the
        growth inside a window once it is cast as it is; its offset from
        this base has them all."""
        if self._base is None:
            first = np.asarray(self.values[:, 0], dtype=np.float64)
            self._base = np.where(self.lengths > 0, first, 0.0)
        return self._base

    def rebased_values(self) -> np.ndarray:
        """values - value_base in float64 (padding holds nothing a kernel
        reads): what every shift-invariant range function (delta, stddev,
        deriv, changes...) computes on as it is, and the others (last,
        avg, min, max, quantiles, predict_linear, instant selection) add
        the base back to in float64 on the host."""
        return np.asarray(self.values, dtype=np.float64) \
            - self.value_base[:, None]

    def counter_adjusted(self) -> np.ndarray:
        """The reset-corrected counter (value + every value seen before a
        reset so far) as its offset from its own first sample, in
        float64: monotone from 0, so `rate` / `increase` are differences
        of numbers no larger than the growth over the matrix. The value
        before a reset is as large as the counter was: added on the
        device in float32 it would cost the digits again."""
        v = np.asarray(self.values, dtype=np.float64)
        out = self.rebased_values()
        rows, cols = np.nonzero(v[:, 1:] < v[:, :-1])
        real = cols + 1 < self.lengths[rows]     # not the step into padding
        rows, cols = rows[real], cols[real]
        if len(rows) <= self.num_series:
            # resets are rare (a reboot): each lifts the rest of its row
            for r, c in zip(rows.tolist(), cols.tolist()):
                out[r, c + 1:] += v[r, c]
            return out
        lift = np.zeros_like(v)
        lift[rows, cols + 1] = v[rows, cols]
        return out + np.cumsum(lift, axis=1)

    @staticmethod
    def build(series_ids: np.ndarray, ts: np.ndarray, values: np.ndarray,
              num_series: int, max_len: Optional[int] = None) -> "SeriesMatrix":
        """Build from flat arrays sorted by (series_id, ts). Rows whose
        series_id is outside [0, num_series) are dropped."""
        if len(series_ids) and (series_ids.min() < 0
                                or series_ids.max() >= num_series):
            sel = (series_ids >= 0) & (series_ids < num_series)
            series_ids, ts, values = series_ids[sel], ts[sel], values[sel]
        counts = np.bincount(series_ids, minlength=num_series)
        longest = int(counts.max(initial=0))
        if max_len is not None and max_len < longest:
            raise ValueError(
                f"max_len={max_len} smaller than longest series ({longest} rows)")
        L = int(max_len if max_len is not None else max(longest, 1))
        # bucket L to powers of two to bound compile cache misses
        L = 1 << (L - 1).bit_length() if L > 1 else 1
        ts2d = np.full((num_series, L), TS_PAD, dtype=np.int64)
        val2d = np.zeros((num_series, L), dtype=values.dtype)
        offsets = np.zeros(num_series + 1, dtype=np.int64)
        np.cumsum(counts, out=offsets[1:])
        col = np.arange(len(series_ids)) - offsets[series_ids]
        ts2d[series_ids, col] = ts
        val2d[series_ids, col] = values
        return SeriesMatrix(ts2d, val2d, counts.astype(np.int32))

    def device_arrays(self, base: Optional[int] = None):
        """Return (ts, values, lengths, base) ready for device transfer.

        On TPU x64 is typically disabled, so int64 epoch timestamps would
        silently truncate. When the time span fits, timestamps are rebased to
        int32 offsets from `base` (padding becomes int32 max, preserving the
        sentinel ordering); callers must rebase query times by the same base.
        """
        # rows lie sorted by time with their padding last, so the span
        # is in each row's first and last sample: no pass over the cells
        some = np.nonzero(self.lengths > 0)[0]
        first = last = 0
        if len(some):
            first = int(self.ts[some, 0].min())
            last = int(self.ts[some, self.lengths[some] - 1].max())
        if base is None:
            base = first
        if not len(some) or (last - base < 2**31 - 1 and base <= first):
            rel = np.where(self.ts != TS_PAD, self.ts - base,
                           np.iinfo(np.int32).max)
            return rel.astype(np.int32), self.values, self.lengths, base
        return self.ts, self.values, self.lengths, 0


def _counts_leq_grid(ts2d: jax.Array, t0, step, nsteps: int) -> jax.Array:
    """#samples per row with ts <= t0 + k*step, for k in [0, nsteps) —
    i.e. side='right' searchsorted against a REGULAR query grid, computed
    without gathers: bucketize every sample (elementwise), then a fused
    [S-chunk, L, T] compare-reduce. Measured 6.6x faster than vmapped
    searchsorted at the 10k-series × 8192-pt × 1440-step PromQL shape on
    v5e (890ms vs 5.9s per bounds array) — binary search is random-gather
    bound on TPU; this is pure VPU compare-adds."""
    S, L = ts2d.shape
    # smallest k with t0 + k*step >= ts  (pad sentinel maps to nsteps,
    # excluded from every window; pre-window samples map to 0).
    # The dtype-max pad sentinel would overflow t0 - ts for negative t0,
    # so pads are routed through t0 and forced to nsteps afterwards.
    sentinel = jnp.iinfo(ts2d.dtype).max
    is_pad = ts2d == sentinel
    safe_ts = jnp.where(is_pad, t0, ts2d)
    b = jnp.clip(-jnp.floor_divide(t0 - safe_ts, step), 0, nsteps) \
        .astype(jnp.int32)
    b = jnp.where(is_pad, nsteps, b)
    cmp_dtype = jnp.int16 if nsteps + 1 < 2**15 else jnp.int32
    b = b.astype(cmp_dtype)   # halve compare width: 2x VPU lanes
    ks = jnp.arange(nsteps, dtype=cmp_dtype)
    chunk = max(1, min(S, 512))
    pad = (-S) % chunk
    if pad:
        # padded rows are garbage and sliced off; padding avoids the
        # dynamic_slice start clamp silently duplicating rows
        b = jnp.concatenate(
            [b, jnp.full((pad, L), nsteps, b.dtype)], axis=0)
    outs = []
    for i in range(0, S + pad, chunk):
        part = jax.lax.dynamic_slice_in_dim(b, i, chunk, 0)
        outs.append((part[:, :, None] <= ks[None, None, :])
                    .sum(axis=1, dtype=jnp.int32))
    out = jnp.concatenate(outs, axis=0)
    return out[:S] if pad else out


#: above this row length the O(S*L*T) compare-reduce loses to the
#: O(S*T*log L) gather-bound binary search (crossover ~55k at measured
#: v5e gather/VPU rates)
_BUCKETIZE_MAX_LEN = 32768


@functools.partial(jax.jit, static_argnames=("step", "range_ms", "nsteps"))
def compute_window_bounds(ts2d, t0, *, step: int, range_ms: int,
                          nsteps: int) -> Tuple[jax.Array, jax.Array]:
    """Standalone window-bounds kernel for callers that reuse bounds across
    range functions (rate + avg_over_time over one selector share them —
    the bounds pass dominates PromQL evaluation at 10k-series scale).

    When the window is step-aligned (range % step == 0, the common PromQL
    shape) and the extension is not wider than the grid itself, lo is a
    shifted hi: ONE extended compare-reduce over T + range/step steps
    replaces the two separate passes. Wide-range instant queries
    (shift >> nsteps, e.g. rate(x[1d]) at one step) keep the two-pass
    form, which is O(nsteps)."""
    T = int(nsteps)
    L = ts2d.shape[1]
    if (L <= _BUCKETIZE_MAX_LEN and T > 1 and step > 0
            and range_ms % step == 0 and range_ms >= 0
            and range_ms // step <= T):
        shift = range_ms // step
        ext = _ext_counts(ts2d, t0, step=step, range_ms=range_ms, nsteps=T)
        return ext[:, :T], ext[:, shift:]
    step_ends = t0 + jnp.arange(T, dtype=ts2d.dtype) * step
    return window_bounds(ts2d, step_ends, range_ms)


def window_bounds(ts2d: jax.Array, step_ends: jax.Array, range_ms: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """lo/hi [S, T]: window (end - range, end] as index ranges [lo, hi)."""
    T = int(step_ends.shape[0])
    if ts2d.shape[1] <= _BUCKETIZE_MAX_LEN and T > 1:
        # step_ends is a regular grid by construction (t0 + k*step)
        t0 = step_ends[0]
        step = step_ends[1] - step_ends[0]
        hi = _counts_leq_grid(ts2d, t0, step, T)
        lo = _counts_leq_grid(ts2d, t0 - range_ms, step, T)
        return lo, hi
    ss = jax.vmap(lambda row, v: jnp.searchsorted(row, v, side="right"),
                  in_axes=(0, None))
    lo = ss(ts2d, step_ends - range_ms)
    hi = ss(ts2d, step_ends)
    return lo.astype(jnp.int32), hi.astype(jnp.int32)


def _gather(row2d: jax.Array, idx: jax.Array) -> jax.Array:
    """Gather row2d[s, idx[s, t]] → [S, T] (idx clipped by caller)."""
    return jnp.take_along_axis(row2d, idx, axis=1)


def _read_at_dense(channels, idx: jax.Array):
    """channels[k][s, idx[s, t]] for K channels [S, N] -> K arrays [S, T]
    with no gather: `sum_n where(n == idx[s, t], bits(channel[s, n]), 0)`,
    one fused compare-select-reduce over the row axis a channel. The
    select (not a product with a one-hot) and the integer sum of the
    value's bits make the result the gather's bit for bit: an `inf` or a
    `NaN` elsewhere in the row adds nothing, -0.0 stays -0.0. `idx` lies
    in [0, N), as a gather's caller clips it."""
    N = channels[0].shape[1]
    hit = jnp.arange(N, dtype=jnp.int32)[None, :, None] == idx[:, None, :]
    outs = []
    for c in channels:
        bits = c if jnp.issubdtype(c.dtype, jnp.integer) else \
            jax.lax.bitcast_convert_type(
                c, jnp.int32 if c.dtype.itemsize == 4 else jnp.int64)
        r = jnp.sum(jnp.where(hit, bits[:, :, None], 0), axis=1,
                    dtype=bits.dtype)                       # over [S, N, T]
        outs.append(jax.lax.bitcast_convert_type(r, c.dtype))
    return outs


def _read_at(channels, idx: jax.Array, row_len: int):
    """channels[k][s, idx[s, t]] -> K arrays [S, T] in the form rows of
    `row_len` take (a channel may be a prefix array, one longer than the
    row): the one place a point read chooses."""
    if _point_reads_dense(row_len):
        return _read_at_dense(channels, idx)
    return [_gather(c, idx) for c in channels]


def _rebase_i64_host(ts2d, t0, step=0, nsteps=1, range_ms=0):
    """Host-validating guard against silent int64→int32 narrowing.

    With jax_enable_x64 off (the norm on TPU), `jnp.asarray` narrows int64
    host arrays to int32: epoch-ms timestamps wrap negative and the TS_PAD
    sentinel becomes -1, breaking the sorted-order precondition every range
    kernel relies on. When handed a host int64 ts matrix in that regime,
    rebase it to int32 offsets from its minimum (remapping TS_PAD to int32
    max so padding still sorts last) and shift t0 by the same base. Device
    arrays and non-int64 inputs pass through untouched.

    The whole quantity range the kernel computes with must fit int32:
    the data span, t0, the last step end t0 + (nsteps-1)*step, and the
    earliest window start t0 - range_ms are all validated (strictly below
    int32 max: a sample rebasing exactly to int32 max would alias the pad
    sentinel and be silently dropped).

    Returns (ts2d, t0) safe to hand to jit.
    """
    if jax.config.jax_enable_x64:
        return ts2d, t0
    if not (isinstance(ts2d, np.ndarray) and ts2d.dtype == np.int64):
        return ts2d, t0
    valid = ts2d != TS_PAD
    if valid.any():
        base, hi = int(ts2d[valid].min()), int(ts2d[valid].max())
    else:
        # no samples: rebase the query grid onto itself so evaluation
        # proceeds and every step reports ok=False (not a crash)
        base = hi = int(t0)
    i32 = np.iinfo(np.int32)
    last_end = int(t0) + (int(nsteps) - 1) * int(step)
    bounds = [hi - base, int(t0) - base, last_end - base,
              int(t0) - int(range_ms) - base]
    if any(b >= i32.max or b < i32.min for b in bounds):
        raise ValueError(
            f"timestamp/query span after rebase exceeds int32 "
            f"({min(bounds)}..{max(bounds)}) and x64 is disabled: rebase to "
            f"region-relative offsets first (see SeriesMatrix.device_arrays)")
    rel = np.where(valid, ts2d - base, i32.max).astype(np.int32)
    return rel, np.int32(int(t0) - base)


def range_aggregate_cumsum(
    ts2d, val2d, lengths, t0, step, range_ms, *, op: str, nsteps: int,
    param: float = 0.0, bounds: Optional[Tuple[jax.Array, jax.Array]] = None,
    counter: Optional[Tuple[jax.Array, jax.Array]] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Evaluate a cumsum-path range function on the aligned step grid.

    `counter` = (adj2d, abs2d) lets a caller whose `val2d` is rebased
    (SeriesMatrix.rebased_values) keep counter semantics exact: `adj2d`
    is SeriesMatrix.counter_adjusted (rate / increase difference it
    instead of correcting resets here, either may be None), `abs2d` the
    values as they are (where a counter's own level counts: the
    extrapolation's zero point, irate's value after a reset).

    Returns (result [S, T], ok [S, T]) — ok False means "no point for this
    series at this step" (NaN / absent in PromQL terms).

    Host int64 timestamps are auto-rebased when x64 is off (step/range are
    deltas and stay as passed; t0 shifts with the base). `bounds` lets
    callers reuse one `compute_window_bounds` result across several range
    functions over the same selector — the bounds pass dominates PromQL
    evaluation at 10k-series scale.
    """
    ts2d, t0 = _rebase_i64_host(ts2d, t0, step, nsteps, range_ms)
    adj2d, abs2d = counter if counter is not None else (None, None)
    if bounds is not None:
        return _range_aggregate_cumsum_pre(
            ts2d, val2d, lengths, t0, step, range_ms, bounds[0], bounds[1],
            adj2d, abs2d, op=op, nsteps=nsteps, param=param)
    return _range_aggregate_cumsum(ts2d, val2d, lengths, t0, step, range_ms,
                                   adj2d, abs2d, op=op, nsteps=nsteps,
                                   param=param)


@functools.partial(jax.jit, static_argnames=("op", "nsteps"))
def _range_aggregate_cumsum(
    ts2d: jax.Array, val2d: jax.Array, lengths: jax.Array,
    t0, step, range_ms, adj2d=None, abs2d=None, *, op: str, nsteps: int,
    param: float = 0.0,
) -> Tuple[jax.Array, jax.Array]:
    step_ends = t0 + jnp.arange(nsteps, dtype=ts2d.dtype) * step
    lo, hi = window_bounds(ts2d, step_ends, range_ms)
    return _rac_body(ts2d, val2d, lengths, lo, hi, step_ends, range_ms,
                     op=op, nsteps=nsteps, adj2d=adj2d, abs2d=abs2d)


@functools.partial(jax.jit, static_argnames=("op", "nsteps"))
def _range_aggregate_cumsum_pre(
    ts2d: jax.Array, val2d: jax.Array, lengths: jax.Array,
    t0, step, range_ms, lo, hi, adj2d=None, abs2d=None, *, op: str,
    nsteps: int, param: float = 0.0,
) -> Tuple[jax.Array, jax.Array]:
    step_ends = t0 + jnp.arange(nsteps, dtype=ts2d.dtype) * step
    return _rac_body(ts2d, val2d, lengths, lo, hi, step_ends, range_ms,
                     op=op, nsteps=nsteps, adj2d=adj2d, abs2d=abs2d)


def _rac_body(ts2d, val2d, lengths, lo, hi, step_ends, range_ms, *,
              op: str, nsteps: int, adj2d=None, abs2d=None
              ) -> Tuple[jax.Array, jax.Array]:
    S, L = ts2d.shape
    idx = jnp.arange(L, dtype=jnp.int32)
    valid = idx[None, :] < lengths[:, None]
    fv = val2d.dtype
    count = (hi - lo).astype(jnp.int32)
    ok1 = count >= 1
    hi1 = jnp.maximum(hi - 1, 0)

    def take(row2d, at):        # dense or gather, by the row's length
        return _read_at([row2d], at, L)[0]

    def pick_first():
        return take(val2d, jnp.minimum(lo, L - 1))

    def pick_last():
        return take(val2d, hi1)

    if op in ("count_over_time", "present_over_time"):
        if op == "present_over_time":
            return jnp.ones_like(count, dtype=fv), ok1
        return count.astype(fv), ok1

    if op in ("sum_over_time", "avg_over_time", "stddev_over_time",
              "stdvar_over_time"):
        vz = jnp.where(valid, val2d, 0).astype(fv)
        cs = jnp.cumsum(vz, axis=1)
        csp = jnp.concatenate([jnp.zeros((S, 1), fv), cs], axis=1)
        wsum = take(csp, hi) - take(csp, lo)
        if op == "sum_over_time":
            return wsum, ok1
        cnt = jnp.maximum(count, 1).astype(fv)
        mean = wsum / cnt
        if op == "avg_over_time":
            return mean, ok1
        cs2 = jnp.cumsum(vz * vz, axis=1)
        cs2p = jnp.concatenate([jnp.zeros((S, 1), fv), cs2], axis=1)
        wsq = take(cs2p, hi) - take(cs2p, lo)
        var = jnp.maximum(wsq / cnt - mean * mean, 0.0)
        if op == "stdvar_over_time":
            return var, ok1
        return jnp.sqrt(var), ok1

    if op == "first_over_time":
        return pick_first(), ok1
    if op == "last_over_time":
        return pick_last(), ok1

    if op in ("idelta", "irate_num"):
        ok2 = count >= 2
        last = pick_last()
        prev = take(val2d, jnp.maximum(hi - 2, 0))
        if op == "irate_num":
            # prometheus instantValue counter-reset rule: on reset
            # (last < prev) the delta is the last sample alone, at the
            # counter's own level
            alone = last if abs2d is None else take(abs2d, hi1)
            return jnp.where(last < prev, alone, last - prev), ok2
        return last - prev, ok2

    if op in ("changes", "resets"):
        prev = jnp.concatenate([val2d[:, :1], val2d[:, :-1]], axis=1)
        pair_ok = valid & (idx[None, :] >= 1)
        if op == "changes":
            ind = pair_ok & (val2d != prev)
        else:
            ind = pair_ok & (val2d < prev)
        ci = jnp.cumsum(ind.astype(jnp.int32), axis=1)
        cip = jnp.concatenate([jnp.zeros((S, 1), jnp.int32), ci], axis=1)
        # pairs (i-1, i) with both endpoints inside [lo, hi)
        cnt = take(cip, hi) - take(cip, jnp.minimum(lo + 1, L))
        cnt = jnp.where(count >= 1, cnt, 0)
        return cnt.astype(fv), ok1

    if op in ("rate", "increase", "delta"):
        ok2 = count >= 2
        first_t = take(ts2d, jnp.minimum(lo, L - 1)).astype(fv)
        last_t = take(ts2d, hi1).astype(fv)
        first_v = pick_first()
        last_v = pick_last()
        if op == "delta":
            raw = last_v - first_v
            is_counter = False
        else:
            adj = adj2d
            if adj is None:
                # counter-reset correction: adjusted[i] = v[i] + sum of
                # resets <= i
                prev = jnp.concatenate([val2d[:, :1], val2d[:, :-1]], axis=1)
                pair_ok = valid & (idx[None, :] >= 1)
                contrib = jnp.where(pair_ok & (val2d < prev), prev,
                                    0).astype(fv)
                adj = val2d + jnp.cumsum(contrib, axis=1)
            raw = take(adj, hi1) - take(adj, jnp.minimum(lo, L - 1))
            if abs2d is not None:   # the zero point is the counter's own
                first_v = take(abs2d, jnp.minimum(lo, L - 1))
            is_counter = True
        return _extrapolate(raw, first_t, last_t, first_v, count, step_ends,
                            range_ms, op=op, is_counter=is_counter)

    raise ValueError(f"not a cumsum-path op: {op}")


def _extrapolate(raw, first_t, last_t, first_v, count, step_ends, range_ms,
                 *, op: str, is_counter: bool):
    """Prometheus extrapolation epilogue (extrapolate_rate.rs:100-200),
    shared by the per-op kernel and the stacked-gather fast path."""
    fv = raw.dtype
    ok2 = count >= 2
    ms = jnp.asarray(range_ms, fv)
    range_start = step_ends[None, :].astype(fv) - ms
    range_end = step_ends[None, :].astype(fv)
    dur_to_start = first_t - range_start
    dur_to_end = range_end - last_t
    sampled = last_t - first_t
    avg_dur = sampled / jnp.maximum(count - 1, 1).astype(fv)
    threshold = avg_dur * 1.1
    if is_counter:
        # cap extrapolation below zero for counters (only meaningful when
        # the first sample is non-negative, per extrapolate_rate.rs)
        dur_to_zero = jnp.where((raw > 0) & (first_v >= 0),
                                sampled * (first_v / jnp.where(raw == 0, 1, raw)),
                                jnp.inf)
        dur_to_start = jnp.minimum(dur_to_start, dur_to_zero)
    ext_start = jnp.where(dur_to_start < threshold, dur_to_start, avg_dur / 2)
    ext_end = jnp.where(dur_to_end < threshold, dur_to_end, avg_dur / 2)
    factor = (sampled + ext_start + ext_end) / jnp.where(sampled == 0, 1, sampled)
    out = raw * factor
    if op == "rate":
        out = out / (ms / 1000.0)
    return out, ok2 & (sampled > 0)


def range_aggregate_gather(
    ts2d, val2d, t0, step, range_ms, *, op: str, nsteps: int, maxw: int,
    param: float = 0.0, param2: float = 0.0, series_block: int = 128,
    bounds: Optional[Tuple[jax.Array, jax.Array]] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Gather-path range functions (host int64 ts auto-rebased, see
    `range_aggregate_cumsum`; `bounds` reuses a `compute_window_bounds`
    result)."""
    ts2d, t0 = _rebase_i64_host(ts2d, t0, step, nsteps, range_ms)
    if bounds is not None:
        return _range_aggregate_gather_pre(
            ts2d, val2d, t0, step, range_ms, bounds[0], bounds[1], op=op,
            nsteps=nsteps, maxw=maxw, param=param, param2=param2,
            series_block=series_block)
    return _range_aggregate_gather(ts2d, val2d, t0, step, range_ms, op=op,
                                   nsteps=nsteps, maxw=maxw, param=param,
                                   param2=param2, series_block=series_block)


@functools.partial(jax.jit, static_argnames=("op", "nsteps", "maxw", "series_block"))
def _range_aggregate_gather(
    ts2d: jax.Array, val2d: jax.Array,
    t0, step, range_ms, *, op: str, nsteps: int, maxw: int,
    param: float = 0.0, param2: float = 0.0, series_block: int = 128,
) -> Tuple[jax.Array, jax.Array]:
    return _rag_body(ts2d, val2d, t0, step, range_ms, None, None, op=op,
                     nsteps=nsteps, maxw=maxw, param=param, param2=param2,
                     series_block=series_block)


@functools.partial(jax.jit, static_argnames=("op", "nsteps", "maxw", "series_block"))
def _range_aggregate_gather_pre(
    ts2d: jax.Array, val2d: jax.Array,
    t0, step, range_ms, lo, hi, *, op: str, nsteps: int, maxw: int,
    param: float = 0.0, param2: float = 0.0, series_block: int = 128,
) -> Tuple[jax.Array, jax.Array]:
    return _rag_body(ts2d, val2d, t0, step, range_ms, lo, hi, op=op,
                     nsteps=nsteps, maxw=maxw, param=param, param2=param2,
                     series_block=series_block)


def _rag_body(
    ts2d: jax.Array, val2d: jax.Array,
    t0, step, range_ms, pre_lo, pre_hi, *, op: str, nsteps: int, maxw: int,
    param: float = 0.0, param2: float = 0.0, series_block: int = 128,
) -> Tuple[jax.Array, jax.Array]:
    """The gather family of range functions, in the form the shapes ask
    for (`window_read_path`).

    Dense (min / max / deriv / predict_linear on rows of up to
    `_DENSE_WINDOW_MAX_RATIO` x maxw samples): a window's samples are
    those whose index lies within its bounds, so every sum, mean, min and
    max is a masked reduction over the row axis of the row broadcast along
    the steps. XLA fuses compare, select and reduce: nothing of [B, T, L]
    is written, no [B, T, W] array exists and `maxw` plays no part.
    predict_linear at [3072, 256] x 64 is 1.97 ms on a v5e where the two
    gathers of [128, 64, 256] a block took 1,277 (PR 29).

    Gather (the rest): each window materializes ≤ maxw samples; windows
    longer than maxw are truncated to their most recent maxw samples
    (callers size maxw from data density), processed in series blocks via
    lax.map to bound the footprint.

    Row validity comes from the TS_PAD sentinel (padded slots sort last and
    fall outside every window), so no lengths array is needed."""
    S, L = ts2d.shape
    step_ends = t0 + jnp.arange(nsteps, dtype=ts2d.dtype) * step
    dense = window_read_path(op, L, maxw) == "dense"
    if dense:
        # one block where the whole selection is within the budget (no
        # `while` on the device), else the fewest blocks of a multiple
        # of 8 series
        per = max(8, _DENSE_BLOCK_CELLS // (nsteps * L) // 8 * 8)
        series_block = max(1, min(S, per))
    pad_s = (-S) % series_block
    pad_sentinel = jnp.iinfo(ts2d.dtype).max
    ts2d = jnp.pad(ts2d, ((0, pad_s), (0, 0)), constant_values=pad_sentinel)
    val2d = jnp.pad(val2d, ((0, pad_s), (0, 0)))
    SB = (S + pad_s) // series_block
    have_bounds = pre_lo is not None
    if have_bounds:
        # padded series get empty windows (lo == hi == 0)
        pre_lo = jnp.pad(pre_lo, ((0, pad_s), (0, 0)))
        pre_hi = jnp.pad(pre_hi, ((0, pad_s), (0, 0)))

    def block(args):
        if have_bounds:
            tsb, valb, lo, hi = args  # [B, L] / [B, T]
        else:
            tsb, valb = args          # [B, L]
            lo, hi = window_bounds(tsb, step_ends, range_ms)
        if dense:
            idx = jnp.arange(L, dtype=jnp.int32)[None, None, :]
            inwin = (idx >= lo[:, :, None]) & (idx < hi[:, :, None])
            vals, tvals = valb[:, None, :], tsb[:, None, :]     # [B, 1, L]
        else:
            lo = jnp.maximum(lo, hi - maxw)
            w = jnp.arange(maxw, dtype=jnp.int32)
            widx = lo[:, :, None] + w[None, None, :]            # [B, T, W]
            inwin = widx < hi[:, :, None]
            widx_c = jnp.minimum(widx, L - 1)
            vals = jnp.take_along_axis(
                jnp.broadcast_to(valb[:, None, :],
                                 (valb.shape[0], nsteps, L)), widx_c, axis=2)
            tvals = jnp.take_along_axis(
                jnp.broadcast_to(tsb[:, None, :],
                                 (tsb.shape[0], nsteps, L)), widx_c, axis=2)
        count = (hi - lo).astype(jnp.int32)
        ok1 = count >= 1
        fv = valb.dtype
        if op == "min_over_time":
            r = jnp.min(jnp.where(inwin, vals, jnp.inf), axis=2)
            return r, ok1
        if op == "max_over_time":
            r = jnp.max(jnp.where(inwin, vals, -jnp.inf), axis=2)
            return r, ok1
        if op == "mad_over_time":
            med = _masked_quantile(vals, inwin, 0.5)
            dev = jnp.abs(vals - med[:, :, None])
            r = _masked_quantile(dev, inwin, 0.5)
            return r, ok1
        if op == "quantile_over_time":
            return _masked_quantile(vals, inwin, param), ok1
        if op in ("deriv", "predict_linear"):
            ok2 = count >= 2
            # least squares around the window's own means: the textbook
            # n*sxy - sx*sy subtracts two float32 products that agree in
            # their leading digits (values of 1e8 B, slope off by 1e-3).
            # Samples outside the window are selected away, not multiplied
            # by 0: an `inf` beside a window is none of its business.
            t_sec = (tvals.astype(fv) - step_ends[None, :, None].astype(fv)) / 1000.0
            n = jnp.maximum(count, 1).astype(fv)
            xm = jnp.sum(jnp.where(inwin, t_sec, 0), axis=2) / n
            ym = jnp.sum(jnp.where(inwin, vals, 0), axis=2) / n
            dx = jnp.where(inwin, t_sec - xm[:, :, None], 0)
            dy = jnp.where(inwin, vals - ym[:, :, None], 0)
            sxx = jnp.sum(dx * dx, axis=2)
            sxy = jnp.sum(dx * dy, axis=2)
            slope = jnp.where(sxx != 0, sxy / jnp.where(sxx == 0, 1, sxx),
                              jnp.nan)
            if op == "deriv":
                return slope, ok2
            # the line at the step (x = 0), then `param` seconds on
            return ym + slope * (param - xm), ok2
        if op == "holt_winters":
            return _holt_winters(vals, inwin, param, param2), count >= 2
        raise ValueError(f"not a gather-path op: {op}")

    operands = (ts2d.reshape(SB, series_block, L),
                val2d.reshape(SB, series_block, L))
    if have_bounds:
        operands += (pre_lo.reshape(SB, series_block, nsteps),
                     pre_hi.reshape(SB, series_block, nsteps))
    if SB == 1:
        outs, oks = block(tuple(a[0] for a in operands))
    else:
        outs, oks = jax.lax.map(block, operands)
    out = outs.reshape(-1, nsteps)[:S]
    ok = oks.reshape(-1, nsteps)[:S]
    return out, ok


# ---------------------------------------------------------------------------
# Aligned-window shared evaluation (the PromQL dashboard fast path)
# ---------------------------------------------------------------------------
# When the window is a multiple of the step (rate(x[5m]) at 1m step — the
# common dashboard shape), every per-(series, step) quantity the cumsum-op
# family needs is a value at either index lo[k] or hi[k]-1, and lo is a
# shifted view of hi over an EXTENDED grid. So ONE set of channels gathered
# at the extended grid serves every op — rate + avg_over_time + ... over
# the same selector share the bounds pass, the cumsums, and the gathers,
# leaving only tiny [S, T] vector epilogues per op.

# tier-A channels (prefix/instant values)
_CH_CSP, _CH_TS_PREV, _CH_TS_AT, _CH_VAL_PREV, _CH_VAL_AT, _CH_VAL_PREV2 = \
    range(6)


def _gather_channels(channels, e):
    """K channels [S, L+1] at positions e [S, T_ext] -> [S, T_ext, K].

    Rows of up to `_DENSE_POINT_MAX_LEN` samples are read without a
    gather (`_read_at_dense`, bit for bit the same): on a v5e the five
    channels of `_stack_rate` at [65536, 129] x 84 take 3.95 ms so and
    292.0 ms gathered (PR 29; the dashboard's whole-table `rate`).

    Longer rows: one 2-D gather per channel. A single gather over the
    stacked [S, L+1, K] operand never returns on jax 0.9.0 / libtpu
    0.0.34 for some shapes (on a v5e: [4000, 513, 6] hangs, [4000, 397,
    6] and [4000, 1025, 6] take 3-6 ms), with or without an optimization
    barrier and with the channel axis in either place."""
    return jnp.stack(_read_at(channels, e, channels[0].shape[1] - 1),
                     axis=-1)


@jax.jit
def _stack_prefix(ts2d, val2d, lengths, ext):
    """Tier A: gather [csp, ts_prev, ts_at, val_prev, val_at, val_prev2]
    at the extended-grid positions; X_at[e] = X[min(e, L-1)],
    X_prev[e] = X[max(e-1, 0)], X_prev2[e] = X[max(e-2, 0)]."""
    S, L = ts2d.shape
    fv = val2d.dtype
    idx = jnp.arange(L, dtype=jnp.int32)
    valid = idx[None, :] < lengths[:, None]
    vz = jnp.where(valid, val2d, 0).astype(fv)
    csp = jnp.concatenate([jnp.zeros((S, 1), fv), jnp.cumsum(vz, axis=1)],
                          axis=1)
    tsf = ts2d.astype(fv)
    return _gather_channels([
        csp,
        jnp.concatenate([tsf[:, :1], tsf], axis=1),
        jnp.concatenate([tsf, tsf[:, -1:]], axis=1),
        jnp.concatenate([val2d[:, :1], val2d], axis=1).astype(fv),
        jnp.concatenate([val2d, val2d[:, -1:]], axis=1).astype(fv),
        jnp.concatenate([val2d[:, :1], val2d[:, :1], val2d[:, :-1]],
                        axis=1).astype(fv),
    ], jnp.minimum(ext, L))


@jax.jit
def _stack_counter(ts2d, val2d, lengths, ext):
    """Tier B: counter-reset-adjusted values [adj_prev, adj_at]."""
    S, L = ts2d.shape
    fv = val2d.dtype
    idx = jnp.arange(L, dtype=jnp.int32)
    valid = idx[None, :] < lengths[:, None]
    prev = jnp.concatenate([val2d[:, :1], val2d[:, :-1]], axis=1)
    pair_ok = valid & (idx[None, :] >= 1)
    contrib = jnp.where(pair_ok & (val2d < prev), prev, 0).astype(fv)
    adj = val2d + jnp.cumsum(contrib, axis=1)
    return _gather_channels([
        jnp.concatenate([adj[:, :1], adj], axis=1),
        jnp.concatenate([adj, adj[:, -1:]], axis=1),
    ], jnp.minimum(ext, L))


@jax.jit
def _stack_rate(ts2d, adj2d, abs2d, ext):
    """rate / increase over host-prepared counter arrays
    (SeriesMatrix.counter_adjusted, and the values as they are for the
    zero point): [ts_prev, ts_at, adj_prev, adj_at, abs_at]."""
    L = ts2d.shape[1]
    fv = adj2d.dtype
    tsf = ts2d.astype(fv)
    return _gather_channels([
        jnp.concatenate([tsf[:, :1], tsf], axis=1),
        jnp.concatenate([tsf, tsf[:, -1:]], axis=1),
        jnp.concatenate([adj2d[:, :1], adj2d], axis=1),
        jnp.concatenate([adj2d, adj2d[:, -1:]], axis=1),
        jnp.concatenate([abs2d, abs2d[:, -1:]], axis=1).astype(fv),
    ], jnp.minimum(ext, L))


@functools.partial(jax.jit, static_argnames=("op", "nsteps", "shift"))
def _rate_from_stack(gr, lo, hi, t0, step, range_ms, *, op: str,
                     nsteps: int, shift: int):
    T = nsteps
    count = (hi - lo).astype(jnp.int32)
    step_ends = t0 + jnp.arange(T, dtype=jnp.int32) * step
    first_t = gr[:, :T, 1]
    last_t = gr[:, shift:, 0]
    raw = gr[:, shift:, 2] - gr[:, :T, 3]
    return _extrapolate(raw, first_t, last_t, gr[:, :T, 4], count,
                        step_ends, range_ms, op=op, is_counter=True)


@jax.jit
def _stack_sq(ts2d, val2d, lengths, ext):
    """Tier C: squared-value prefix (stddev/stdvar only)."""
    S, L = ts2d.shape
    fv = val2d.dtype
    idx = jnp.arange(L, dtype=jnp.int32)
    valid = idx[None, :] < lengths[:, None]
    vz = jnp.where(valid, val2d, 0).astype(fv)
    csp2 = jnp.concatenate(
        [jnp.zeros((S, 1), fv), jnp.cumsum(vz * vz, axis=1)], axis=1)
    return _gather_channels([csp2], jnp.minimum(ext, L))


@functools.partial(jax.jit, static_argnames=("step", "range_ms", "nsteps"))
def _ext_counts(ts2d, t0, *, step: int, range_ms: int, nsteps: int):
    """Counts at the extended grid [t0 - range, ..., t0 + (nsteps-1)*step]:
    lo = ext[:, :nsteps], hi = ext[:, shift:] for shift = range // step."""
    shift = range_ms // step
    T_ext = nsteps + shift
    if ts2d.shape[1] <= _BUCKETIZE_MAX_LEN and T_ext > 1:
        return _counts_leq_grid(ts2d, t0 - range_ms, step, T_ext)
    ends = (t0 - range_ms) + jnp.arange(T_ext, dtype=ts2d.dtype) * step
    ss = jax.vmap(lambda row, v: jnp.searchsorted(row, v, side="right"),
                  in_axes=(0, None))
    return ss(ts2d, ends).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("op", "nsteps", "shift"))
def _op_from_stack(ga, gb, gc, lo, hi, t0, step, range_ms, *,
                   op: str, nsteps: int, shift: int):
    T = nsteps
    fv = ga.dtype
    count = (hi - lo).astype(jnp.int32)
    ok1 = count >= 1

    def lo_of(x):
        return x[:, :T]

    def hi_of(x):
        return x[:, shift:]

    def A(c):
        return ga[..., c]

    if op == "sum_over_time":
        return hi_of(A(_CH_CSP)) - lo_of(A(_CH_CSP)), ok1
    if op in ("avg_over_time", "stddev_over_time", "stdvar_over_time"):
        wsum = hi_of(A(_CH_CSP)) - lo_of(A(_CH_CSP))
        cnt = jnp.maximum(count, 1).astype(fv)
        mean = wsum / cnt
        if op == "avg_over_time":
            return mean, ok1
        csp2 = gc[..., 0]
        wsq = hi_of(csp2) - lo_of(csp2)
        var = jnp.maximum(wsq / cnt - mean * mean, 0.0)
        return (var if op == "stdvar_over_time" else jnp.sqrt(var)), ok1
    if op == "first_over_time":
        return lo_of(A(_CH_VAL_AT)), ok1
    if op == "last_over_time":
        return hi_of(A(_CH_VAL_PREV)), ok1
    if op in ("idelta", "irate_num"):
        ok2 = count >= 2
        last = hi_of(A(_CH_VAL_PREV))
        prev = hi_of(A(_CH_VAL_PREV2))
        if op == "irate_num":
            return jnp.where(last < prev, last, last - prev), ok2
        return last - prev, ok2
    if op in ("rate", "increase", "delta"):
        step_ends = t0 + jnp.arange(T, dtype=jnp.int32) * step
        first_t = lo_of(A(_CH_TS_AT))
        last_t = hi_of(A(_CH_TS_PREV))
        first_v = lo_of(A(_CH_VAL_AT))
        last_v = hi_of(A(_CH_VAL_PREV))
        if op == "delta":
            raw = last_v - first_v
            is_counter = False
        else:
            raw = hi_of(gb[..., 0]) - lo_of(gb[..., 1])
            is_counter = True
        return _extrapolate(raw, first_t, last_t, first_v, count, step_ends,
                            range_ms, op=op, is_counter=is_counter)
    raise ValueError(f"not a stack-path op: {op}")


@functools.partial(jax.jit, static_argnames=("op", "fv"))
def _count_from_bounds(lo, hi, *, op: str, fv):
    # fv = value dtype, so results match the non-aligned kernel's dtype
    # (float64 under x64) regardless of which path a query takes
    count = (hi - lo).astype(jnp.int32)
    ok1 = count >= 1
    if op == "present_over_time":
        return jnp.ones_like(count, dtype=fv), ok1
    return count.astype(fv), ok1


class AlignedWindowEval:
    """Shared-state evaluator for cumsum-path range functions over one
    series matrix and one step-aligned grid (range % step == 0).

    Bounds, cumsums, and the stacked gather are computed once and cached;
    each op adds only a [S, T] vector epilogue. The PromQL engine caches
    one of these per (selector, window) within an evaluation."""

    def __init__(self, ts2d, val2d, lengths, t0, step, range_ms, nsteps,
                 counter=None):
        """`val2d`: the values, or a callable -> them, asked when a
        function first reads values. `counter`: a callable -> (adj2d,
        abs2d) as `range_aggregate_cumsum` takes them, asked only when
        a counter function is evaluated; None computes them from
        `val2d` on the device, as a caller with plain values wants."""
        step, range_ms, nsteps = int(step), int(range_ms), int(nsteps)
        if step <= 0 or range_ms < 0 or range_ms % step:
            raise ValueError("AlignedWindowEval needs range % step == 0")
        ts2d, t0 = _rebase_i64_host(ts2d, t0, step, nsteps, range_ms)
        self.ts2d, self._val2d, self.lengths = ts2d, val2d, lengths
        self.t0, self.step, self.range_ms = t0, step, range_ms
        self.nsteps = nsteps
        self.shift = range_ms // step
        self._ext = None
        self._ga = self._gb = self._gc = self._gr = None
        self._counter = counter

    @property
    def val2d(self):
        if callable(self._val2d):
            self._val2d = self._val2d()
        return self._val2d

    def ext(self):
        if self._ext is None:
            self._ext = _ext_counts(self.ts2d, self.t0, step=self.step,
                                    range_ms=self.range_ms,
                                    nsteps=self.nsteps)
        return self._ext

    def bounds(self) -> Tuple[jax.Array, jax.Array]:
        ext = self.ext()
        return ext[:, :self.nsteps], ext[:, self.shift:]

    def eval(self, op: str) -> Tuple[jax.Array, jax.Array]:
        if op not in CUMSUM_OPS:
            raise ValueError(f"not a cumsum-path op: {op}")
        lo, hi = self.bounds()
        if op in ("count_over_time", "present_over_time"):
            return _count_from_bounds(lo, hi, op=op,
                                      fv=self.val2d.dtype)
        if op in ("changes", "resets") or (
                op == "irate_num" and self._counter is not None):
            # outside the stack family; still shares the bounds pass
            return range_aggregate_cumsum(
                self.ts2d, self.val2d, self.lengths, self.t0, self.step,
                self.range_ms, op=op, nsteps=self.nsteps, bounds=(lo, hi),
                counter=self._counter() if op == "irate_num" else None)
        if op in ("rate", "increase") and self._counter is not None:
            if self._gr is None:
                adj2d, abs2d = self._counter()
                self._gr = _stack_rate(self.ts2d, adj2d, abs2d, self.ext())
            return _rate_from_stack(
                self._gr, lo, hi, self.t0, self.step, self.range_ms, op=op,
                nsteps=self.nsteps, shift=self.shift)
        if self._ga is None:
            self._ga = _stack_prefix(self.ts2d, self.val2d, self.lengths,
                                     self.ext())
        gb = gc = None
        if op in ("rate", "increase"):
            if self._gb is None:
                self._gb = _stack_counter(self.ts2d, self.val2d,
                                          self.lengths, self.ext())
            gb = self._gb
        if op in ("stddev_over_time", "stdvar_over_time"):
            if self._gc is None:
                self._gc = _stack_sq(self.ts2d, self.val2d, self.lengths,
                                     self.ext())
            gc = self._gc
        return _op_from_stack(ga=self._ga, gb=gb, gc=gc, lo=lo, hi=hi,
                              t0=self.t0, step=self.step,
                              range_ms=self.range_ms, op=op,
                              nsteps=self.nsteps, shift=self.shift)


def _masked_quantile(vals: jax.Array, mask: jax.Array, q) -> jax.Array:
    """Quantile along the last axis ignoring masked entries (sort-based,
    linear interpolation, matching Prometheus quantile semantics)."""
    big = jnp.where(mask, vals, jnp.inf)
    svals = jnp.sort(big, axis=-1)
    n = jnp.sum(mask, axis=-1)
    fv = vals.dtype
    pos = q * (n.astype(fv) - 1)
    lo_i = jnp.clip(jnp.floor(pos).astype(jnp.int32), 0, vals.shape[-1] - 1)
    hi_i = jnp.clip(lo_i + 1, 0, vals.shape[-1] - 1)
    frac = pos - lo_i.astype(fv)
    lo_v = jnp.take_along_axis(svals, lo_i[..., None], axis=-1)[..., 0]
    hi_v = jnp.take_along_axis(svals, jnp.minimum(hi_i, jnp.maximum(n - 1, 0))[..., None],
                               axis=-1)[..., 0]
    return lo_v + (hi_v - lo_v) * frac


def _holt_winters(vals: jax.Array, mask: jax.Array, sf, tf) -> jax.Array:
    """Holt-Winters double exponential smoothing over each window.

    sf = smoothing factor, tf = trend factor (both in (0,1)); sequential over
    the ≤ maxw window via lax.scan (reference:
    src/promql/src/functions/holt_winters.rs)."""
    x0 = vals[..., 0]
    x1 = jnp.where(mask[..., 1], vals[..., 1], x0)
    s0, b0 = x1, x1 - x0

    def step(carry, xm):
        s, b = carry
        x, m = xm
        s_new = sf * x + (1 - sf) * (s + b)
        b_new = tf * (s_new - s) + (1 - tf) * b
        s = jnp.where(m, s_new, s)
        b = jnp.where(m, b_new, b)
        return (s, b), None

    xs = jnp.moveaxis(vals[..., 2:], -1, 0)
    ms = jnp.moveaxis(mask[..., 2:], -1, 0)
    (s_fin, _), _ = jax.lax.scan(step, (s0, b0), (xs, ms))
    return s_fin


def instant_select(ts2d, val2d, t0, step, lookback_ms, *, nsteps: int
                   ) -> Tuple[jax.Array, jax.Array]:
    """InstantManipulate (host int64 ts auto-rebased, see
    `range_aggregate_cumsum`)."""
    ts2d, t0 = _rebase_i64_host(ts2d, t0, step, nsteps, lookback_ms)
    return _instant_select(ts2d, val2d, t0, step, lookback_ms, nsteps=nsteps)


@functools.partial(jax.jit, static_argnames=("nsteps",))
def _instant_select(ts2d: jax.Array, val2d: jax.Array,
                    t0, step, lookback_ms, *, nsteps: int
                    ) -> Tuple[jax.Array, jax.Array]:
    """InstantManipulate: at each step pick the latest sample within the
    lookback window [t - lookback, t] (reference:
    src/promql/src/extension_plan/instant_manipulate.rs:46)."""
    S, L = ts2d.shape
    step_ends = t0 + jnp.arange(nsteps, dtype=ts2d.dtype) * step
    ss = jax.vmap(lambda row, v: jnp.searchsorted(row, v, side="right"),
                  in_axes=(0, None))
    hi = ss(ts2d, step_ends).astype(jnp.int32)
    hi1 = jnp.maximum(hi - 1, 0)
    last_t = _gather(ts2d, hi1)
    ok = (hi >= 1) & (last_t >= step_ends[None, :] - lookback_ms)
    return _gather(val2d, hi1), ok
