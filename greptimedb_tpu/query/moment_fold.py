"""The fold: a launch's results into a partial frame, a base's and its
tail's partials into one, all regions' partials into the statement's
answer (`query/tpu_exec.py` has the map). Tests replace
`_collect_moment_frame`: callers outside read it through this module."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np
import pandas as pd

from ..errors import UnsupportedError
from ..storage.scan_cache import run_diffs
from .agg_plan import (RUN_DIFF_MOMENT_OPS, SKETCH_MOMENT_OPS, Moment,
                       TpuPlan)
from .planner import _group_slot
from .scan_launch import _Launched, _tag_column
from .sketches import decode_sketch, encode_sketch

@dataclass
class _RunPartial:
    """One launch's moments by live run, before they become a frame: the
    form in which the partials of a base and its tail fold (`_fold_runs`)
    by integer keys, ahead of any label."""
    sids: np.ndarray                  # [g] the runs' series
    buckets: Optional[np.ndarray]     # [g] from the statement's origin
    moments: List[np.ndarray]         # a plan moment each, [g]
    rowcount: np.ndarray
    series_dict: object
    #: a tail's: per RUN_DIFF_MOMENT_OPS moment (its index in the plan)
    #: the run's first difference, which reaches back before the run
    seams: Dict[int, np.ndarray] = field(default_factory=dict)


def _collect_runs(launched: _Launched, plan: TpuPlan, counts: np.ndarray,
                  res_np: List[np.ndarray]) -> Optional[_RunPartial]:
    nruns = launched.nruns
    counts = counts[:nruns]
    # the live runs only: a statement over an eighth of the series
    # leaves seven eighths of the table's runs empty, and their tags are
    # not worth decoding
    live = counts > 0
    if not live.any():
        return None
    moments = []
    for m, r in zip(plan.moments, res_np):
        r = r[:nruns][live]
        if m.op in ("min_ts", "max_ts"):
            # device ts is region-relative (ts - ts_base, base differs per
            # region); rebase to absolute so cross-region first/last merge
            # in _finalize compares comparable timestamps
            r = r.astype(np.int64) + launched.ts_base
        moments.append(r)
    grows = [i for i, m in enumerate(plan.moments)
             if m.op in RUN_DIFF_MOMENT_OPS]
    seams = {i: r[:nruns][live]
             for i, r in zip(grows, res_np[len(plan.moments):])}
    return _RunPartial(
        launched.run_sids[live],
        launched.run_buckets[live] if plan.bucket is not None else None,
        moments, counts[live], launched.series_dict, seams)


def _partial_frame(p: _RunPartial, plan: TpuPlan) -> pd.DataFrame:
    # ---- host: fold runs into final groups ----
    frame: Dict[str, Any] = {}
    for tg in plan.tag_groups:
        frame[_group_slot(tg.name)] = _tag_column(p.series_dict, p.sids,
                                                  tg.tag_index)
    if plan.bucket is not None:
        frame[_group_slot(plan.bucket.expr_key)] = \
            p.buckets * plan.bucket.stride_ms + plan.bucket.origin
    for m, r in zip(plan.moments, p.moments):
        frame[m.slot] = r
    frame["__rowcount"] = p.rowcount
    return pd.DataFrame(frame)


def _collect_moment_frame(launched: _Launched, plan: TpuPlan,
                          counts: np.ndarray,
                          res_np: List[np.ndarray]) -> Optional[pd.DataFrame]:
    runs = _collect_runs(launched, plan, counts, res_np)
    return None if runs is None else _partial_frame(runs, plan)


def _fold_runs(a: _RunPartial, b: _RunPartial,
               plan: TpuPlan) -> Optional[_RunPartial]:
    """The partials of a base (`a`) and its tail (`b`) as one: a run
    (series, bucket) that both hold folds here, as `_finalize` would fold
    its two rows (sums add, extremes compare, `first` / `last` go to the
    valid value with the extreme companion timestamp), on integer keys
    and before a label is decoded; the statement's frame then has a row
    a group and `_finalize` nothing to fold. None where the keys do not
    fit an int64 (the frames are then handed on as they are)."""
    keyed = bool(plan.tag_groups) or plan.bucket is not None
    ka = a.sids.astype(np.int64) if keyed else np.zeros(len(a.sids),
                                                        np.int64)
    kb = b.sids.astype(np.int64) if keyed else np.zeros(len(b.sids),
                                                        np.int64)
    if plan.bucket is not None:
        lo = min(int(a.buckets.min()), int(b.buckets.min()))
        if max(int(a.buckets.max()), int(b.buckets.max())) - lo >= 2**31:
            return None
        ka = (ka << 32) + (a.buckets - lo)
        kb = (kb << 32) + (b.buckets - lo)
    # a launch's runs are in row order: ascending in (series, bucket)
    at = np.minimum(np.searchsorted(ka, kb), len(ka) - 1)
    hit = ka[at] == kb
    at, rest = at[hit], ~hit

    def companion(m: Moment, kind: str):
        i = next(i for i, mm in enumerate(plan.moments)
                 if mm.op == kind and mm.column == m.column)
        return a.moments[i][at], b.moments[i][hit]

    def valid(v):
        return ~np.isnan(v) if v.dtype.kind == "f" else np.ones(len(v), bool)

    moments = []
    for i, m in enumerate(plan.moments):
        va, vb = a.moments[i], b.moments[i]
        x, y = va[at], vb[hit]
        if m.op in ("sum", "sum_sq", "count"):
            both = np.where(valid(x) & valid(y), x + y,
                            np.where(valid(x), x, y))
        elif m.op in ("min", "min_ts"):
            both = np.fmin(x, y)
        elif m.op in ("max", "max_ts"):
            both = np.fmax(x, y)
        elif m.op == "first":
            ta, tb = companion(m, "min_ts")
            both = np.where(valid(x) & (~valid(y) | (ta <= tb)), x, y)
        elif m.op == "last":
            ta, tb = companion(m, "max_ts")
            both = np.where(valid(y) & (~valid(x) | (tb >= ta)), y, x)
        elif m.op in RUN_DIFF_MOMENT_OPS:
            # one window across the seam: the base's growth, the tail's,
            # and the tail's first difference, which reaches back to the
            # base's last sample (`MergedScan.device_run_diffs`)
            seam = b.seams[i][hit]
            both = x + y + np.where(valid(seam), seam, 0)
        else:
            raise UnsupportedError(f"no fold for moment {m.op}")
        out = va.astype(np.result_type(va.dtype, vb.dtype), copy=True)
        out[at] = both
        moments.append(np.concatenate([out, vb[rest]]))
    rowcount = a.rowcount.copy()
    rowcount[at] += b.rowcount[hit]
    return _RunPartial(
        np.concatenate([a.sids, b.sids[rest]]),
        None if plan.bucket is None
        else np.concatenate([a.buckets, b.buckets[rest]]),
        moments, np.concatenate([rowcount, b.rowcount[rest]]),
        a.series_dict)


def _nan_if_none(v):
    return np.nan if v is None else v


def _merge_sketch_cells(cells) -> Optional[bytes]:
    """Fold encoded sketch partials (bytes) into ONE re-encoded partial.
    Decode errors raise SketchCodecError — try_execute degrades the
    statement to the raw-row path rather than answer wrong."""
    merged = None
    for c in cells:
        if c is None or (isinstance(c, float) and np.isnan(c)):
            continue
        sk = decode_sketch(c)
        merged = sk if merged is None else merged.merge(sk)
    return None if merged is None else encode_sketch(merged)


def _finalize(df: pd.DataFrame, plan: TpuPlan) -> pd.DataFrame:
    key_cols = [_group_slot(t.name) for t in plan.tag_groups]
    if plan.bucket is not None:
        key_cols.append(_group_slot(plan.bucket.expr_key))

    moment_cols = {m.slot: m for m in plan.moments}

    def _ts_slot_for(m: Moment, kind: str) -> str:
        return next(s for s, mm in moment_cols.items()
                    if mm.op == kind and mm.column == m.column)

    def merge(group: pd.DataFrame) -> pd.Series:
        out = {}
        for slot, m in moment_cols.items():
            v = group[slot]
            if m.op in SKETCH_MOMENT_OPS:
                out[slot] = _merge_sketch_cells(v)
            elif m.op in ("sum", "sum_sq", "count"):
                out[slot] = v.sum()
            elif m.op in ("min", "min_ts"):
                out[slot] = v.min()
            elif m.op in ("max", "max_ts"):
                out[slot] = v.max()
            elif m.op in ("first", "last"):
                # partial with a valid value whose ts is extreme wins
                kind = "min_ts" if m.op == "first" else "max_ts"
                ts_slot = _ts_slot_for(m, kind)
                nn = group[group[slot].notna()]
                if not len(nn):
                    out[slot] = None
                elif m.op == "first":
                    out[slot] = nn.loc[nn[ts_slot].idxmin(), slot]
                else:
                    out[slot] = nn.loc[nn[ts_slot].idxmax(), slot]
            elif m.op in RUN_DIFF_MOMENT_OPS:
                # partials are time-disjoint slices of one series run:
                # their growths add, plus the difference across each
                # slice boundary (last-of-prev to first-of-next)
                g = group.sort_values(_ts_slot_for(m, "min_ts"),
                                      kind="stable")
                prev = g[_ts_slot_for(m, "last")].shift()
                cur = g[_ts_slot_for(m, "first")]
                across = pd.Series(run_diffs(cur, prev, m.op),
                                   index=g.index)
                out[slot] = g[slot].sum() + \
                    across.where(cur.notna() & prev.notna(), 0.0).sum()
        return pd.Series(out)

    if key_cols:
        if df[key_cols + list(moment_cols)].duplicated(key_cols).any():
            # vectorized fold: one groupby.agg for the decomposable
            # moments (a per-group Python merge costs seconds at 10k+
            # groups — slice streaming produces one partial per group
            # per slice), plus a sort+first/last pass for ts-extremes
            gb = df.groupby(key_cols, dropna=False, sort=False)
            aggs = {}
            extremes = []
            sketches = []
            diffs = []
            for slot, m in moment_cols.items():
                if m.op in SKETCH_MOMENT_OPS:
                    sketches.append(slot)
                elif m.op in RUN_DIFF_MOMENT_OPS:
                    diffs.append((slot, m))
                elif m.op in ("sum", "sum_sq", "count"):
                    aggs[slot] = "sum"
                elif m.op in ("min", "min_ts"):
                    aggs[slot] = "min"
                elif m.op in ("max", "max_ts"):
                    aggs[slot] = "max"
                else:
                    extremes.append((slot, m))
            aggs["__rowcount"] = "sum"      # a plan of only sketch
            merged = gb.agg(aggs)           # moments still needs keys
            for slot, m in extremes:
                # groupby.first()/.last() take the first/last NON-NULL
                # value in frame order; sorting by the companion ts makes
                # that "valid partial with extreme ts" exactly
                kind = "min_ts" if m.op == "first" else "max_ts"
                ts_slot = _ts_slot_for(m, kind)
                srt = df.sort_values(ts_slot, kind="stable")
                gs = srt.groupby(key_cols, dropna=False, sort=False)[slot]
                merged[slot] = gs.first() if m.op == "first" else gs.last()
            for slot in sketches:
                # fold encoded partials per group through the codec
                # (bytes in, bytes out — pandas treats bytes as scalars)
                merged[slot] = gb[slot].agg(_merge_sketch_cells)
            for slot, m in diffs:
                # per-group partials sorted by slice start: their growths
                # add, plus the difference across each slice boundary
                srt = df.sort_values(_ts_slot_for(m, "min_ts"),
                                     kind="stable")
                gs = srt.groupby(key_cols, dropna=False, sort=False)
                prev = gs[_ts_slot_for(m, "last")].shift()
                cur = srt[_ts_slot_for(m, "first")]
                across = pd.Series(run_diffs(cur, prev, m.op),
                                   index=srt.index).where(
                    cur.notna() & prev.notna(), 0.0)
                merged[slot] = gs[slot].sum() + across.groupby(
                    [srt[k] for k in key_cols], dropna=False,
                    sort=False).sum()
            merged = merged.reset_index()
        else:
            merged = df
    else:
        merged = merge(df).to_frame().T

    # finalize ops from moments
    out = merged[key_cols].copy() if key_cols else pd.DataFrame(
        index=merged.index)
    for slot, op, mslots in plan.finals:
        if op in ("sum", "min", "max", "first", "last", "moment"):
            # "moment": raw merged-moment passthrough — PromQL's rate
            # finalization reads min_ts/max_ts/increase directly
            out[slot] = merged[mslots[0]]
        elif op == "count":
            out[slot] = merged[mslots[0]].astype(np.int64)
        elif op in ("count_distinct", "approx_distinct"):
            out[slot] = merged[mslots[0]].map(
                lambda b: 0 if b is None
                else decode_sketch(b).result()).astype(np.int64)
        elif op == "approx_percentile":
            p = plan.agg_params.get(slot, (50.0,))[0]
            out[slot] = merged[mslots[0]].map(
                lambda b: np.nan if b is None
                else _nan_if_none(decode_sketch(b).quantile(p))
            ).astype(np.float64)
        elif op == "avg":
            s, c = merged[mslots[0]], merged[mslots[1]]
            out[slot] = np.where(c > 0, s / np.maximum(c, 1), np.nan)
        elif op in ("stddev", "variance"):
            s, sq, c = (merged[m] for m in mslots)
            cc = np.maximum(c, 1)
            # sample variance (ddof=1) to match DataFusion; <2 rows → NULL;
            # s/cc promotes to float BEFORE the square — s*s wraps int cols
            var = np.maximum(sq - (s / cc) * s, 0.0) / np.maximum(c - 1, 1)
            var = np.where(c >= 2, var, np.nan)
            out[slot] = np.sqrt(var) if op == "stddev" else var
    # null out empty-count aggregates (kernel yields NaN already for floats)
    for slot, op, mslots in plan.finals:
        if op in ("sum", "min", "max", "first", "last", "avg"):
            cnt = None
            for ms in mslots:
                if moment_cols[ms].op == "count":
                    cnt = merged[ms]
            if cnt is not None:
                out.loc[cnt == 0, slot] = np.nan
    return out.reset_index(drop=True)


#: finals whose result comes out of a sketch partial, not a numeric fold
_SKETCH_FINAL_OPS = frozenset({"count_distinct", "approx_distinct",
                               "approx_percentile"})


def _aggs_desc(plan: TpuPlan) -> str:
    """sketch-vs-exact per aggregate, for the finalize stage detail."""
    return ",".join(
        f"{op}:{'sketch' if op in _SKETCH_FINAL_OPS else 'exact'}"
        for _, op, _ in plan.finals)


def frames_nbytes(frames) -> int:
    """Byte size of partial moment frames — numeric columns by their
    array width, sketch columns by their encoded frame lengths. This is
    the number the wire pays (the IPC framing adds low single-digit %),
    so EXPLAIN ANALYZE's partial_bytes reads the same for local and
    Flight datanodes."""
    total = 0
    for f in frames:
        for col in f.columns:
            s = f[col]
            if isinstance(s.dtype, pd.StringDtype):
                # pandas 3 `str` (what a tag column of a partial frame
                # is): lengths in one pass, a missing value as 8 B; a
                # Python loop over 808,000 x 4 labels of a lowered PromQL
                # statement took 3.2 s of its 7.1
                total += int(s.str.len().fillna(8).sum())
            # object: bytes, sketches, pandas 2 strings
            elif pd.api.types.is_string_dtype(s.dtype):
                total += int(sum(
                    len(v) if isinstance(v, (bytes, bytearray, str))
                    else 8 for v in s))
            else:
                total += int(s.to_numpy().nbytes)
    return total
