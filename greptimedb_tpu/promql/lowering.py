"""PromQL → plan-IR lowering, plus the engine's sanctioned data access.

Reference behavior: src/promql/src/planner.rs lowers PromQL into the
same DataFusion LogicalPlan SQL uses, so PromQL range queries ride
every pushdown the SQL optimizer knows. This module is the equivalent
seam for the TPU build: aggregate-over-selector shapes lower into the
shared plan IR (query/ir.py) and execute through the ONE aggregate
executor — cost-based scatter on DistTables, resident / streamed-cold /
indexed-point dispatch on local tables — while every non-lowerable
shape keeps the proven row path behind the same selector, fed by an IR
`RawScan` that still gets region pruning and wire filter pushdown.

This is also the ONLY module under promql/ allowed to touch region
internals (`table.regions`, the device scan cache, raw `scan_batches`)
— greptlint GL14 flags such access anywhere else, so every byte the
PromQL engine reads flows through the IR's two leaves.

Lowered shapes (everything else → row path):

  agg(selector)                 agg ∈ sum/avg/min/max/count [by/without]
  agg(fn(selector[R]))          fn ∈ rate/increase/delta/
                                sum|count|avg|min|max|last_over_time,
                                and the window tumbles (R == step)

with plain equality/inequality matchers on string tags, a single
numeric field, no @, and any offset. The inner selector/function is
rebuilt as a per-series instant vector from the finalized moment frame
(a window's raw growth rides the `increase` / `delta` moment, which the
device reduces over a derived mirror of per-sample differences, so a
month-old counter keeps its digits in f32; extrapolation replicates
ops/window.py exactly), then the engine's ordinary host grouping
aggregates it — outer semantics are shared with the row path by
construction.

A lowered statement's EXPLAIN ANALYZE rows: `plan`, then what SQL's
aggregate writes (`scan_prep`, `reduce` and its parts, `finalize`), then
`lower` (the moment frame back to the inner instant vector) with its
part `lower.rebuild` (series codes and the [series, steps] values / ok
arrays); `greptime_promql_lowered_windows_total` counts the (series,
window) rows rebuilt.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..errors import UnsupportedError
from ..sql.ast import BinaryOp, Column, IsNull, Literal
from ..storage.scan_cache import MergedScan
from .ast import Aggregate, Call, PromExpr, VectorSelector

#: outer aggregates whose inner vector we lower (topk/quantile/
#: count_values keep the row path: they need per-sample semantics the
#: moment frame cannot carry for arbitrary params)
LOWERABLE_AGG_OPS = frozenset({"sum", "avg", "min", "max", "count"})

#: range functions with an exact moment decomposition over one
#: tumbling window (range == step): value and ok-mask reconstruct
#: from first/min_ts/max_ts/count + the window's growth (`increase`,
#: reset-aware, or `delta`)
LOWERABLE_RANGE_FUNCS = frozenset({
    "rate", "increase", "delta", "sum_over_time", "count_over_time",
    "avg_over_time", "min_over_time", "max_over_time", "last_over_time",
})

#: sentinel: the matchers statically match nothing — the lowered
#: answer is an empty vector, no scan needed
EMPTY = object()


@dataclass
class LoweredSelect:
    """One aggregate-over-selector shape lowered onto the plan IR."""
    table: object
    plan: object                       # query.ir TpuPlan
    func: Optional[str]                # None = instant selector
    metric: str
    field: str
    tag_names: List[str]
    t0: int                            # first window end (offset applied)
    ends: np.ndarray                   # [nsteps] window ends, int64
    win: int                           # window width (lookback or range)


def resolve_metric_table(engine, sel: VectorSelector, ctx):
    """(metric name, table or None) — shared by the lowering and the
    row path so both resolve `__name__` overrides identically."""
    metric = sel.metric
    for m in sel.matchers:
        if m.name == "__name__" and m.op == "=":
            metric = m.value
    if not metric:
        raise UnsupportedError(
            "selector without metric name is not supported")
    table = engine.catalog.table(ctx.current_catalog, ctx.current_schema,
                                 metric)
    return metric, table


def _numeric_fields(schema, matchers) -> List[str]:
    from .engine import _matcher_keep
    fields = [f for f in schema.field_names()
              if not schema.column_schema(f).dtype.is_string and
              not schema.column_schema(f).dtype.is_binary]
    for m in matchers:
        if m.name == "__field__":
            keep = _matcher_keep(fields, m)
            fields = [f for f, k in zip(fields, keep) if k]
    return fields


# ---------------------------------------------------------------------------
# shape analysis: Aggregate node -> LoweredSelect | EMPTY | None
# ---------------------------------------------------------------------------

def try_lower(ev, e: Aggregate):
    """Decide whether the inner vector of this aggregate lowers onto
    the IR. Returns (LoweredSelect, "") on success, (EMPTY, "") when
    the matchers statically match nothing, or (None, reason) when the
    statement keeps the row path."""
    from ..query import agg_plan, ir, tpu_exec
    from .engine import _matches_empty

    if e.op not in LOWERABLE_AGG_OPS or e.param is not None:
        return None, f"outer aggregate {e.op} keeps per-sample semantics"
    inner = e.expr
    func = None
    if isinstance(inner, Call):
        if inner.func not in LOWERABLE_RANGE_FUNCS or \
                len(inner.args) != 1 or \
                not isinstance(inner.args[0], VectorSelector):
            return None, f"function {getattr(inner, 'func', '?')} has " \
                "no moment decomposition"
        sel = inner.args[0]
        func = inner.func
        if not sel.range_ms:
            return None, f"{func} needs a range selector"
        if sel.range_ms != ev.step:
            return None, (f"window does not tumble "
                          f"(range={sel.range_ms}ms != step={ev.step}ms)")
    elif isinstance(inner, VectorSelector):
        sel = inner
        if sel.range_ms:
            return None, "raw matrix selector"
    else:
        return None, f"inner {type(inner).__name__} is not a selector"
    if sel.at_ms is not None:
        return None, "@ modifier pins one evaluation time"

    metric, table = resolve_metric_table(ev.engine, sel, ev.ctx)
    if table is None or not hasattr(table, "schema"):
        return None, f"table {metric} not found"
    is_dist = hasattr(table, "execute_tpu_plan")
    if not is_dist and not hasattr(table, "regions"):
        return None, f"{metric} is not a region-backed table"
    if is_dist and not agg_plan._PARTIAL_PUSHDOWN[0]:
        return None, "SET dist_partial_agg = 0"
    if not is_dist:
        # same floor SQL's try_execute applies: small local tables are
        # faster (and float64-exact) on the existing row path
        est = tpu_exec._estimated_table_rows(table)
        if est is not None and est < tpu_exec.TPU_DISPATCH_MIN_ROWS:
            return None, (f"est_rows={est} < dispatch_floor="
                          f"{tpu_exec.TPU_DISPATCH_MIN_ROWS}")

    schema = table.schema
    if schema.timestamp_column is None:
        return None, f"{metric} has no time index"
    tag_names = schema.tag_names()
    tagset = set(tag_names)
    fields = _numeric_fields(schema, sel.matchers)
    if not fields:
        return EMPTY, ""
    if len(fields) > 1:
        return None, "multi-field table needs per-field series"

    preds = []
    for m in sel.matchers:
        if m.name == "__name__":
            if m.op != "=":
                return None, "non-equality __name__ matcher"
            continue
        if m.name == "__field__":
            continue
        if m.name not in tagset:
            # matching a non-existent label: ""-matching ops are
            # vacuously true, anything else statically matches nothing
            if _matches_empty(m):
                continue
            return EMPTY, ""
        if not schema.column_schema(m.name).dtype.is_string:
            return None, f"matcher on non-string tag {m.name}"
        col = Column(m.name)
        if m.op == "=":
            if m.value == "":
                # = "" keeps absent-or-empty labels; the stored-null
                # rendering only the row path implements
                return None, 'matcher = "" selects absent labels'
            preds.append(BinaryOp("=", col, Literal(m.value)))
        elif m.op == "!=":
            if m.value == "":
                preds.append(BinaryOp("!=", col, Literal("")))
            else:
                # a stored NULL renders as "" and "" != value, so keep
                # null rows explicitly (SQL != drops nulls)
                preds.append(BinaryOp("or", IsNull(col),
                                      BinaryOp("!=", col,
                                               Literal(m.value))))
        else:
            return None, f"regex matcher on {m.name}"

    ends = ev._grid(sel.offset_ms, None)
    t0 = int(ends[0])
    win = int(sel.range_ms) if func else int(ev.lookback)
    field = fields[0]
    aggs = [("__n", "count", field)]
    mspec: List[Tuple[str, str, str]] = []
    if func is None:
        aggs.append(("__v", "last", field))
        mspec.append(("__t", "max_ts", field))
    elif func in ("rate", "increase", "delta"):
        # first / last / min_ts are also what folds the growth of one
        # window across partials (moment_fold._finalize)
        aggs += [("__first", "first", field), ("__last", "last", field)]
        mspec += [("__mnt", "min_ts", field), ("__mxt", "max_ts", field)]
        # the window's raw growth as a moment of its own: last - first
        # of the device's f32 mirrors has no digits left once the level
        # is large (a gauge at 1e12 that moves by 6e4 a window came out
        # 31% off), so the device sums per-sample differences instead
        # (agg_plan.RUN_DIFF_MOMENT_OPS)
        mspec.append(("__grow", "delta" if func == "delta" else "increase",
                      field))
    elif func in ("last_over_time",):
        aggs.append(("__v", "last", field))
    elif func != "count_over_time":
        aggs.append(("__v", func[:-len("_over_time")], field))

    from ..query.agg_plan import BucketGroup
    plan = ir.plan_from_specs(
        schema, aggs,
        group_tags=tag_names,          # per-series: full tag key
        bucket=BucketGroup(ev.step, t0 - ev.step + 1, "__promql_window"),
        time_lo=t0 - win + 1,          # _window_eval's matrix bound
        time_hi=int(ends[-1]) + 1,     # closed hi -> exclusive
        tag_predicates=preds,
        moment_specs=mspec)
    return LoweredSelect(table, plan, func, metric, field, tag_names,
                         t0, ends, win), ""


# ---------------------------------------------------------------------------
# executing a lowered shape and rebuilding the inner instant vector
# ---------------------------------------------------------------------------

def _key_str(v) -> str:
    from .engine import _label_str
    if isinstance(v, float) and np.isnan(v):
        return ""
    return _label_str(v)


def _series_codes(df, tag_names: List[str]):
    """-> (sid per row of the frame, the series' label-value tuples in
    sorted order). The group columns are factorised a column at a time
    (hashing, no Python call per row) and a label is rendered once a
    value; values that render alike (NULL and "") fall into one."""
    import pandas as pd

    from ..query.planner import _group_slot
    n = len(df)
    codes = np.zeros(n, dtype=np.int64)
    columns = []                     # per tag: (code per row, its labels)
    for t in tag_names:
        col, values = pd.factorize(df[_group_slot(t)],
                                   use_na_sentinel=False)
        columns.append((col, [_key_str(v) for v in values]))
        codes, _ = pd.factorize(codes * len(values) + col)
    n_raw = int(codes.max()) + 1 if n else 0
    first = np.empty(n_raw, dtype=np.int64)
    first[codes[::-1]] = np.arange(n - 1, -1, -1)   # first row of a code
    rendered = [[labels[c] for c in col[first].tolist()]
                for col, labels in columns]
    keys = list(zip(*rendered)) if rendered else [()] * n_raw
    uniq = sorted(set(keys))
    sid_of = {k: i for i, k in enumerate(uniq)}
    remap = np.fromiter((sid_of[k] for k in keys), dtype=np.int64,
                        count=n_raw)
    return remap[codes], uniq


def _empty_vector(T: int):
    from .engine import VectorVal
    return VectorVal([], np.zeros((0, T)), np.zeros((0, T), bool))


def eval_lowered(ev, low: LoweredSelect):
    """Run the lowered plan and rebuild the inner instant vector —
    per-series values over the step grid with Prometheus staleness /
    extrapolation semantics replicated from ops/window.py."""
    from ..common import exec_stats
    from ..common.telemetry import increment_counter
    from ..query import ir
    from .engine import _KEEP_NAME_RANGE_FUNCS, VectorVal

    df = ir.execute_agg_plan(low.table, low.plan)
    T = ev.nsteps
    if df is None or not len(df):
        return _empty_vector(T)
    with exec_stats.stage("lower"):
        # buckets whose rows were all-null carry no sample: drop them so
        # a -inf max_ts sentinel never forward-fills
        df = df[df["__n"].to_numpy() > 0]
        if not len(df):
            return _empty_vector(T)
        with exec_stats.stage("lower.rebuild"):
            uniq, out_vals, out_ok = _rebuild(ev, low, df)
        exec_stats.record("lower", rows=len(df))
        increment_counter("promql_lowered_windows", len(df))
        del df      # released inside the row: 808,000 x 4 labels are 10 ms
        keep_name = low.func is None or low.func in _KEEP_NAME_RANGE_FUNCS
        labels: List[Dict[str, str]] = []
        for ukey in uniq:
            lbl: Dict[str, str] = {}
            if keep_name:
                lbl["__name__"] = low.metric
            for tn, tv in zip(low.tag_names, ukey):
                if tv != "":
                    lbl[tn] = tv
            labels.append(lbl)
        return VectorVal(labels, out_vals, out_ok)


def _rebuild(ev, low: LoweredSelect, df):
    """The moment frame, a row a (series, window), -> (the series' label
    values, values [series, steps], ok [series, steps])."""
    from ..query.planner import _group_slot
    T = ev.nsteps
    sids, uniq = _series_codes(df, low.tag_names)
    S = len(uniq)
    step = ev.step
    bv = df[_group_slot("__promql_window")].to_numpy().astype(np.int64)
    # bucket lower edge -> window end -> step index (negative = the
    # instant path's lookback prefix, filled forward below)
    k = ((bv + step - 1) - low.t0) // step
    cnt = df["__n"].to_numpy().astype(np.float64)

    out_vals = np.full((S, T), np.nan)
    out_ok = np.zeros((S, T), dtype=bool)
    if low.func is None:
        last_v = df["__v"].to_numpy(dtype=np.float64)
        last_t = df["__t"].to_numpy(dtype=np.float64)
        off = -min(int(k.min()), 0)
        K = off + T
        pos = k + off
        inb = (pos >= 0) & (pos < K)
        val_g = np.full((S, K), np.nan)
        ts_g = np.full((S, K), -np.inf)
        val_g[sids[inb], pos[inb]] = last_v[inb]
        ts_g[sids[inb], pos[inb]] = last_t[inb]
        idx = np.where(ts_g > -np.inf, np.arange(K)[None, :], -1)
        idx = np.maximum.accumulate(idx, axis=1)
        has = idx >= 0
        gather = np.clip(idx, 0, None)
        vf = np.take_along_axis(val_g, gather, 1)
        tf = np.take_along_axis(ts_g, gather, 1)
        out_vals = vf[:, off:off + T]
        # same closed staleness bound instant_select applies on device
        out_ok = has[:, off:off + T] & \
            (tf[:, off:off + T] >= low.ends[None, :] - ev.lookback)
        out_vals = np.where(out_ok, out_vals, np.nan)
    else:
        inb = (k >= 0) & (k < T)
        with np.errstate(all="ignore"):
            if low.func in ("rate", "increase", "delta"):
                rowvals, rowok = _window_rate(df, low, k, cnt)
            elif low.func == "count_over_time":
                rowvals, rowok = cnt, cnt >= 1
            else:
                rowvals = df["__v"].to_numpy(dtype=np.float64)
                rowok = cnt >= 1
        out_vals[sids[inb], k[inb]] = rowvals[inb]
        out_ok[sids[inb], k[inb]] = rowok[inb]
    return uniq, out_vals, out_ok


def _window_rate(df, low: LoweredSelect, k: np.ndarray, cnt: np.ndarray):
    """rate/increase/delta from per-window moments: the Prometheus
    extrapolation epilogue of ops/window.py `_extrapolate`, replicated
    on the frontend over the window's merged growth (`increase`,
    reset-aware, or `delta`), first value and first / last times."""
    first_v = df["__first"].to_numpy(dtype=np.float64)
    first_t = df["__mnt"].to_numpy(dtype=np.float64)
    last_t = df["__mxt"].to_numpy(dtype=np.float64)
    rng = float(low.win)
    end_abs = (low.t0 + k * low.win).astype(np.float64)
    raw = df["__grow"].to_numpy(dtype=np.float64)
    dur_to_start = first_t - (end_abs - rng)
    dur_to_end = end_abs - last_t
    sampled = last_t - first_t
    avg_dur = sampled / np.maximum(cnt - 1, 1)
    threshold = avg_dur * 1.1
    if low.func != "delta":
        # counters never extrapolate below zero
        dur_to_zero = np.where(
            (raw > 0) & (first_v >= 0),
            sampled * (first_v / np.where(raw == 0, 1.0, raw)), np.inf)
        dur_to_start = np.minimum(dur_to_start, dur_to_zero)
    ext_start = np.where(dur_to_start < threshold, dur_to_start,
                         avg_dur / 2)
    ext_end = np.where(dur_to_end < threshold, dur_to_end, avg_dur / 2)
    factor = (sampled + ext_start + ext_end) / \
        np.where(sampled == 0, 1.0, sampled)
    out = raw * factor
    if low.func == "rate":
        out = out / (rng / 1000.0)
    return out, (cnt >= 2) & (sampled > 0)


def try_lowered_inner(ev, e: Aggregate):
    """The engine's hook: the inner instant vector of this aggregate
    via the IR, or None to keep the row path. Degrades (never errors)
    when the executor rejects the plan — cost-based raw-pull, a
    version-skewed datanode, a sketch decode failure."""
    from ..common import exec_stats
    with exec_stats.stage("plan"):
        low, _reason = try_lower(ev, e)
    if low is EMPTY:
        return _empty_vector(ev.nsteps)
    if low is None:
        return None
    try:
        return eval_lowered(ev, low)
    except UnsupportedError:
        return None


# ---------------------------------------------------------------------------
# EXPLAIN: the same dispatch stages SQL prints
# ---------------------------------------------------------------------------

def explain_lines(ev, expr) -> List[str]:
    """Plan/dispatch lines for TQL EXPLAIN — built by the same helpers
    SQL's EXPLAIN uses (dispatch_decision_for_pushdown /
    local_dispatch_decision), so the two surfaces cannot drift."""
    from ..query import tpu_exec

    aggs: List[Aggregate] = []
    sels: List[VectorSelector] = []

    def walk(node):
        if isinstance(node, Aggregate):
            aggs.append(node)
        if isinstance(node, VectorSelector):
            sels.append(node)
        for child in list(getattr(node, "args", []) or []):
            if isinstance(child, PromExpr):
                walk(child)
        for attr in ("expr", "lhs", "rhs"):
            child = getattr(node, attr, None)
            if isinstance(child, PromExpr):
                walk(child)

    walk(expr)
    lines: List[str] = []
    covered = set()
    for agg in aggs:
        low, reason = try_lower(ev, agg)
        if isinstance(low, LoweredSelect):
            covered.update(id(s) for s in sels
                           if s is agg.expr or
                           s in list(getattr(agg.expr, "args", []) or []))
            lines.append("TpuAggregateExec: " + low.plan.describe())
            if hasattr(low.table, "execute_tpu_plan"):
                lines.append("  Dispatch: " +
                             tpu_exec.dispatch_decision_for_pushdown(
                                 low.table, low.plan))
            else:
                lines.append("  Dispatch: " +
                             tpu_exec.local_dispatch_decision(
                                 low.table, plan=low.plan))
        elif low is EMPTY:
            lines.append("EmptyExec: matchers select no series")
        else:
            lines.append("  Dispatch: promql-row-path (" + reason + ")")
    for sel in sels:
        if id(sel) in covered:
            continue
        desc = _raw_scan_describe(ev, sel)
        if desc is not None:
            lines.append(desc)
    return lines


def _raw_scan_describe(ev, sel: VectorSelector) -> Optional[str]:
    """The RawScan leaf a row-path selector turns into."""
    from ..query import ir
    try:
        metric, table = resolve_metric_table(ev.engine, sel, ev.ctx)
    except UnsupportedError:
        return None
    if table is None or not hasattr(table, "schema"):
        return None
    schema = table.schema
    tc = schema.timestamp_column
    if tc is None:
        return None
    fields = _numeric_fields(schema, sel.matchers)
    ends = ev._grid(sel.offset_ms, sel.at_ms)
    win = int(sel.range_ms) if sel.range_ms else int(ev.lookback)
    lo = int(ends.min()) - win + 1
    hi = int(ends.max()) + 1
    tagset = set(schema.tag_names())
    n_push = sum(1 for m in sel.matchers
                 if m.op == "=" and m.name in tagset and m.value)
    scan = ir.RawScan(
        projection=list(schema.tag_names()) + [tc.name] + fields,
        time_range=(lo, hi), filters=[None] * n_push)
    return scan.describe()


# ---------------------------------------------------------------------------
# sanctioned data access: the engine's row-path selector
# ---------------------------------------------------------------------------

def select_series(engine, sel: VectorSelector, lo_ms: int, hi_ms: int,
                  ctx):
    """Fetch samples for a selector in the closed window [lo_ms, hi_ms]
    as a dense SeriesMatrix sorted by time within each series (the
    engine's `select`). In-process regions are read directly (device
    scan cache / streamed cold reads / SST-index sid pruning); a
    DistTable whose datanodes are remote has no in-process regions, so
    the same selector is served by an IR RawScan over the wire —
    pruned, filter-pushed, never silently empty.

    The `select` row of the statement's collector, with its parts:
    `.scan` (the region's rows as host arrays), `.filter` (matchers to a
    series mask, then to the rows kept), `.labels` (the kept series'
    label sets), `.matrix` (the [series, samples] matrix built) and,
    where rows were written since the scan cache's base was built,
    `.tail` (what the selection takes from them: `ScanParts`; the row's
    detail says `tail_rows=`)."""
    from ..common import exec_stats
    from ..common.telemetry import increment_counter
    with exec_stats.stage("select"):
        selection = _select_series(engine, sel, lo_ms, hi_ms, ctx)
        sm = selection.matrix
        if sm is not None:
            exec_stats.record("select", rows=len(selection.labels))
            increment_counter("promql_series_selected",
                              len(selection.labels))
            increment_counter("promql_matrix_cells",
                              sm.num_series * sm.max_len)
    return selection


def _select_series(engine, sel: VectorSelector, lo_ms: int, hi_ms: int,
                   ctx):
    from ..common.exec_stats import stage
    from ..ops.window import SeriesMatrix
    from .engine import (
        _is_sorted, _label_str, _matcher_keep, _matches_empty, _Selection,
    )

    metric, table = resolve_metric_table(engine, sel, ctx)
    if table is None:
        return _Selection([], None)
    if not hasattr(table, "regions"):
        raise UnsupportedError(f"{metric} is not a region-backed table")

    schema = table.schema
    tag_names = schema.tag_names()
    tagset = set(tag_names)
    fields = _numeric_fields(schema, sel.matchers)
    if not fields:
        return _Selection([], None)
    multi_field = len(fields) > 1

    regions = table.regions
    if not regions and hasattr(table, "execute_tpu_plan"):
        return _wire_scan_selection(table, sel, metric, tag_names,
                                    fields, multi_field, lo_ms, hi_ms)

    key_to_gid: Dict[tuple, int] = {}
    glabels: List[Dict[str, str]] = []
    parts: List[Tuple[np.ndarray, np.ndarray, np.ndarray]] = []

    eq_matchers = [m for m in sel.matchers
                   if m.op == "=" and m.name in tagset and m.value]
    # tag columns the matchers actually reference: the keep mask only
    # needs these decoded; everything else decodes later, and only for
    # the series that survive
    ref_idx = sorted({tag_names.index(m.name) for m in sel.matchers
                      if m.name in tagset})
    for region in regions.values():
        with stage("select.filter"):
            sid_set = matcher_sids(region, tag_names, eq_matchers)
        if sid_set is not None and len(sid_set) == 0:
            continue                 # no series of this region match
        with stage("select.scan"):
            scan = region_scan(region, fields, lo_ms, hi_ms,
                               sid_set=sid_set)
        if scan is None or scan.num_rows == 0:
            continue
        sd = scan.series_dict
        S = sd.num_series
        if S == 0:
            continue
        with stage("select.filter"):
            ids = np.arange(S, dtype=np.int32)
            tag_strs: Dict[int, List[str]] = {
                i: [_label_str(v) for v in sd.decode_tag_column(ids, i)]
                for i in ref_idx}
            keep = np.ones(S, dtype=bool)
            for m in sel.matchers:
                if m.name in ("__name__", "__field__"):
                    continue
                if m.name not in tagset:
                    # matching a non-existent label: only ""-matching
                    # ops keep
                    if not _matches_empty(m):
                        keep[:] = False
                    continue
                keep &= _matcher_keep(tag_strs[tag_names.index(m.name)], m)
            if not keep.any():
                continue
        scans = scan.parts if isinstance(scan, ScanParts) else [scan]
        if len(regions) == 1 and not multi_field:
            direct = _matrix_from_runs(scans, fields[0], keep, sid_set,
                                       lo_ms, hi_ms)
            if direct is not None:
                return _selection_from_runs(direct, sd, metric, tag_names,
                                            tag_strs)
        kept_rows = []
        for i, part in enumerate(scans):
            with stage("select.tail" if i else "select.filter"):
                kept_rows.append(_rows_kept(part, keep, sid_set, lo_ms,
                                            hi_ms))
        if not sum(map(len, kept_rows)):
            continue
        with stage("select.filter"):
            survivors = np.unique(np.concatenate(
                [part.series_ids[rows] for part, rows
                 in zip(scans, kept_rows)])).astype(np.int32)

        # decode the remaining tag columns only for surviving series
        with stage("select.labels"):
            label_of = dict(zip(survivors.tolist(), _label_keys(
                sd, len(tag_names), tag_strs, survivors)))

        for fname in fields:
            taken = []
            for i, (part, rows) in enumerate(zip(scans, kept_rows)):
                with stage("select.tail" if i else "select.filter"):
                    vals, valid = part.fields[fname]
                    rk = rows if valid is None else rows[valid[rows]]
                    if len(rk):
                        taken.append((part.series_ids[rk], part.ts[rk],
                                      vals[rk].astype(np.float64)))
            if not taken:
                continue
            # map region series → global series ids
            with stage("select.labels"):
                uniq = np.unique(np.concatenate([t[0] for t in taken]))
                remap = np.full(S, -1, dtype=np.int32)
                for s in uniq:
                    lbl_key = label_of[int(s)]
                    gkey = lbl_key + ((fname,) if multi_field else ())
                    gid = key_to_gid.get(gkey)
                    if gid is None:
                        gid = len(glabels)
                        key_to_gid[gkey] = gid
                        lbl = {"__name__": metric}
                        for tn, tv in zip(tag_names, lbl_key):
                            if tv != "":
                                lbl[tn] = tv
                        if multi_field:
                            lbl["__field__"] = fname
                        glabels.append(lbl)
                    remap[s] = gid
                parts += [(remap[sids], ts, v) for sids, ts, v in taken]

    if not parts:
        return _Selection([], None)
    with stage("select.matrix"):
        gids, ts, vals = parts[0] if len(parts) == 1 else (
            np.concatenate([p[i] for p in parts]) for i in range(3))
        # already sorted when a single region/field contributed in order
        if len(parts) > 1 or not _is_sorted(gids, ts):
            order = np.lexsort((ts, gids))
            gids, ts, vals = gids[order], ts[order], vals[order]
        sm = SeriesMatrix.build(gids, ts, vals,
                                series_bucket(len(glabels)))
        return _Selection(glabels, sm, int(ts.min()), int(ts.max()))


def _label_keys(sd, ntags: int, tag_strs: Dict[int, List[str]],
                survivors: np.ndarray) -> List[tuple]:
    """The surviving series' label values, one tuple a series in tag
    order. `tag_strs` holds the columns the matchers already decoded
    (for every series of the dictionary); the rest decode here, for the
    survivors only."""
    from .engine import _label_str
    cols = []
    for i in range(ntags):
        if i in tag_strs:
            whole = tag_strs[i]
            cols.append([whole[s] for s in survivors.tolist()])
        else:
            cols.append([_label_str(v)
                         for v in sd.decode_tag_column(survivors, i)])
    return list(zip(*cols)) if cols else [()] * len(survivors)


def _bisect_runs(ts: np.ndarray, first: np.ndarray, end: np.ndarray,
                 t: int, after: bool) -> np.ndarray:
    """Per run [first, end) of a time-sorted array the first position
    whose time is >= t (or > t with `after`): every run bisected at
    once, one gather of a sample a run and step."""
    lo, hi = first.copy(), end.copy()
    last = len(ts) - 1
    while True:
        open_ = lo < hi
        if not open_.any():
            return lo
        mid = (lo + hi) >> 1
        v = ts[np.minimum(mid, last)]
        right = open_ & ((v <= t) if after else (v < t))
        lo = np.where(right, mid + 1, lo)
        hi = np.where(open_ & ~right, mid, hi)


def _cut_runs(scan, sids: np.ndarray, lo_ms: int, hi_ms: int):
    """-> (start, count): each of these series' samples in [lo_ms, hi_ms]
    as one slice of a scan sorted by (series, time)."""
    first = np.searchsorted(scan.series_ids, sids, side="left")
    end = np.searchsorted(scan.series_ids, sids, side="right")
    start = _bisect_runs(scan.ts, first, end, lo_ms, after=False)
    return start, _bisect_runs(scan.ts, start, end, hi_ms, after=True) - start


def _matrix_from_runs(scans, field: str, keep: np.ndarray, sid_set,
                      lo_ms: int, hi_ms: int):
    """The selection's matrix cut straight from the scan cache's rows,
    which lie sorted by series and then time: a series' samples in
    [lo_ms, hi_ms] are ONE slice of them, found by bisection, and the
    [series, samples] matrix is two row-wise gathers. The general path
    (a mask over every row, flat copies of the kept rows, a sort check,
    a scatter into the matrix) reads and writes the selection some
    twenty times over; a panel over a whole table is bound by exactly
    those passes. `scans`: the base, and where rows were written since
    it was built its tail (`ScanParts`), sorted the same way: a series'
    row holds its slice of the base and then its slice of the tail
    (`select.tail`: the tail's bisection and its cells), a series the
    base has never seen its slice of the tail alone; the cost follows
    the selection and the tail, never the table. -> (kept series ids,
    SeriesMatrix, first time, last time), () when no series has a sample
    there, or None where only the general path applies (rows of a cold
    read, a field with nulls, a tail that reaches back before its base's
    last sample of a selected series)."""
    from ..common.exec_stats import stage
    from ..ops.window import TS_PAD, SeriesMatrix
    if any(s.fields[field][1] is not None or not isinstance(s, MergedScan)
           for s in scans):
        return None
    scan = scans[0]
    vals = scan.fields[field][0]
    tail = scans[1] if len(scans) > 1 else None
    with stage("select.matrix"):
        sids = np.nonzero(keep)[0] if sid_set is None else \
            sid_set[sid_set < len(keep)]
        sids = sids[keep[sids]]
        start, count = _cut_runs(scan, sids, lo_ms, hi_ms)
    has = count > 0
    if tail is not None:
        with stage("select.tail"):
            t_start, t_count = _cut_runs(tail, sids, lo_ms, hi_ms)
            both = has & (t_count > 0)
            if (tail.ts[t_start[both]]
                    <= scan.ts[(start + count - 1)[both]]).any():
                return None     # a late row: the general path sorts
            has = has | (t_count > 0)
            t_start, t_count = t_start[has], t_count[has]
    if not has.any():
        return ()
    with stage("select.matrix"):
        sids, start, count = sids[has], start[has], count[has]
        total = count if tail is None else count + t_count
        width = int(total.max())
        width = 1 << (width - 1).bit_length() if width > 1 else 1
        rows = series_bucket(len(sids))
        lengths = np.zeros(rows, dtype=np.int32)
        lengths[:len(sids)] = total
        begin = np.zeros(rows, dtype=np.int64)
        begin[:len(sids)] = start
        cell = np.arange(width)[None, :]
        pad = cell >= lengths[:, None]
        at = np.minimum(begin[:, None] + cell, len(scan.ts) - 1)
        ts2d = scan.ts[at]
        ts2d[pad] = TS_PAD
        val2d = vals[at].astype(np.float64, copy=False)
        val2d[pad] = 0.0
        in_base = count > 0
        data_min = scan.ts[start[in_base]].min(initial=np.iinfo(np.int64).max)
        data_max = scan.ts[(start + count - 1)[in_base]].max(
            initial=np.iinfo(np.int64).min)
    if tail is not None:
        with stage("select.tail"):
            # a row's cells from its base count on are the tail's
            k = len(sids)
            off = cell - count[:, None]
            mine = (off >= 0) & (off < t_count[:, None])
            at = (t_start[:, None] + off)[mine]
            ts2d[:k][mine] = tail.ts[at]
            val2d[:k][mine] = tail.fields[field][0][at]
            in_tail = t_count > 0
            data_min = min(data_min, tail.ts[t_start[in_tail]].min(
                initial=np.iinfo(np.int64).max))
            data_max = max(data_max, tail.ts[
                (t_start + t_count - 1)[in_tail]].max(
                    initial=np.iinfo(np.int64).min))
    return sids, SeriesMatrix(ts2d, val2d, lengths), int(data_min), \
        int(data_max)


def _selection_from_runs(direct, sd, metric: str, tag_names: List[str],
                         tag_strs: Dict[int, List[str]]):
    from ..common.exec_stats import stage
    from .engine import _Selection
    if not direct:
        return _Selection([], None)
    sids, sm, data_min, data_max = direct
    with stage("select.labels"):
        glabels = []
        for key in _label_keys(sd, len(tag_names), tag_strs,
                               sids.astype(np.int32)):
            lbl = {"__name__": metric}
            for tn, tv in zip(tag_names, key):
                if tv != "":
                    lbl[tn] = tv
            glabels.append(lbl)
    return _Selection(glabels, sm, data_min, data_max)


def _rows_kept(scan, keep: np.ndarray, sid_set, lo_ms: int, hi_ms: int
               ) -> np.ndarray:
    """Positions of the scan's rows whose series is kept and whose time
    lies in [lo_ms, hi_ms], ascending. Where equality matchers resolved
    candidate series (`matcher_sids`) and the rows are the scan cache's
    (sorted by series, then time), only the candidates' runs are read:
    one node's panel then touches 64 runs of a table of 11.5M rows, not
    every row of it three times."""
    if sid_set is None or not isinstance(scan, MergedScan):
        return np.nonzero(keep[scan.series_ids] & (scan.ts >= lo_ms)
                          & (scan.ts <= hi_ms))[0]
    first = np.searchsorted(scan.series_ids, sid_set, side="left")
    counts = np.searchsorted(scan.series_ids, sid_set, side="right") - first
    rows = np.repeat(first - (np.cumsum(counts) - counts), counts) \
        + np.arange(int(counts.sum()))
    ts = scan.ts[rows]
    return rows[keep[scan.series_ids[rows]] & (ts >= lo_ms) & (ts <= hi_ms)]


def series_bucket(n: int) -> int:
    """Rows of a selection's matrix: the next power of two up to 1,024,
    the next multiple of 1,024 above. A fleet whose targets come and go
    selects another count of series at every refresh of a panel (1,020
    or 1,010 of 1,000 live targets with 1% replaced every 10 min), and
    every new count would be a new program to compile; the empty rows
    answer nothing."""
    if n <= 1024:
        return 1 << (n - 1).bit_length() if n > 1 else 1
    return -(-n // 1024) * 1024


def _wire_scan_selection(table, sel: VectorSelector, metric: str,
                         tag_names: List[str], fields: List[str],
                         multi_field: bool, lo_ms: int, hi_ms: int):
    """Row-path selection over remote datanodes: an IR RawScan through
    DistTable.scan_batches — region pruning and equality-matcher wire
    pushdown apply; the remaining matchers filter the rows here."""
    from ..ops.window import SeriesMatrix
    from ..query import ir
    from .engine import (
        _is_sorted, _label_str, _matcher_keep, _matches_empty, _Selection,
    )

    schema = table.schema
    tagset = set(tag_names)
    preds = []
    for m in sel.matchers:
        if m.op == "=" and m.name in tagset and m.value and \
                schema.column_schema(m.name).dtype.is_string:
            preds.append(BinaryOp("=", Column(m.name), Literal(m.value)))
    tc = schema.timestamp_column
    scan = ir.RawScan(
        projection=list(tag_names) + [tc.name] + list(fields),
        time_range=(lo_ms, hi_ms + 1), filters=preds)
    try:
        batches = ir.execute_raw_scan(table, scan)
    except NotImplementedError as e:
        raise UnsupportedError(
            f"PromQL over {metric}: its datanode client implements "
            "neither in-process regions nor the wire scan path; the "
            "lowered aggregate path (SET dist_partial_agg = 1) is the "
            "only route to these datanodes") from e

    key_to_gid: Dict[tuple, int] = {}
    glabels: List[Dict[str, str]] = []
    gid_parts: List[np.ndarray] = []
    ts_parts: List[np.ndarray] = []
    val_parts: List[np.ndarray] = []
    for rb in batches:
        if rb.num_rows == 0:
            continue
        data = rb.to_pydict()
        n = rb.num_rows
        tag_strs = [[_label_str(v) for v in data[t]] for t in tag_names]
        keep = np.ones(n, dtype=bool)
        for m in sel.matchers:
            if m.name in ("__name__", "__field__"):
                continue
            if m.name not in tagset:
                if not _matches_empty(m):
                    keep[:] = False
                continue
            keep &= _matcher_keep(tag_strs[tag_names.index(m.name)], m)
        ts = np.asarray(data[tc.name], dtype=np.int64)
        keep &= (ts >= lo_ms) & (ts <= hi_ms)
        if not keep.any():
            continue
        rows = np.nonzero(keep)[0]
        for fname in fields:
            fcol = data[fname]
            for i in rows:
                fv = fcol[i]
                if fv is None:
                    continue
                lbl_key = tuple(col[i] for col in tag_strs)
                gkey = lbl_key + ((fname,) if multi_field else ())
                gid = key_to_gid.get(gkey)
                if gid is None:
                    gid = len(glabels)
                    key_to_gid[gkey] = gid
                    lbl = {"__name__": metric}
                    for tn, tv in zip(tag_names, lbl_key):
                        if tv != "":
                            lbl[tn] = tv
                    if multi_field:
                        lbl["__field__"] = fname
                    glabels.append(lbl)
                gid_parts.append(gid)
                ts_parts.append(ts[i])
                val_parts.append(float(fv))
    if not gid_parts:
        return _Selection([], None)
    gids = np.asarray(gid_parts, dtype=np.int32)
    tsa = np.asarray(ts_parts, dtype=np.int64)
    vals = np.asarray(val_parts, dtype=np.float64)
    if not _is_sorted(gids, tsa):
        order = np.lexsort((tsa, gids))
        gids, tsa, vals = gids[order], tsa[order], vals[order]
    sm = SeriesMatrix.build(gids, tsa, vals, series_bucket(len(glabels)))
    return _Selection(glabels, sm, int(tsa.min()), int(tsa.max()))


def matcher_sids(region, tag_names, eq_matchers):
    """Sorted candidate sid superset for the selector's equality
    matchers in one region, or None when there is nothing selective
    to resolve — what lets the cold selector path prune whole SSTs
    through their index sidecars. Label values are matched on the
    same string rendering the keep-mask uses, so numeric tags
    resolve identically on both paths."""
    from ..storage.index import sst_index_enabled
    from .engine import _label_str
    if not eq_matchers or not sst_index_enabled():
        return None
    sd = getattr(region, "series_dict", None)
    if sd is None or not sd.tag_names:
        return None
    cand = None
    for m in eq_matchers:
        ti = tag_names.index(m.name)
        # O(1) dictionary hit for string tags (the common case);
        # the O(values) rendered-label scan only runs for tags whose
        # stored values are not strings
        vid = sd.tag_dicts[ti].get(m.value)
        if vid is not None:
            ids = [vid]
        else:
            ids = [i for i, v in
                   enumerate(sd.tag_dicts[ti].values())
                   if v is not None and not isinstance(v, str) and
                   _label_str(v) == m.value]
        sids = sd.sids_for_value_ids(ti, ids)
        cand = sids if cand is None else \
            np.intersect1d(cand, sids, assume_unique=True)
        if len(cand) == 0:
            break
    return cand


class ScanParts:
    """A resident region's rows as the scan cache holds them after a
    write: the base, and the rows written since as a second scan sorted
    the same way (`storage/scan_cache.py:_ScanCache.get_parts`), cut to
    its valid rows. The selector reads both and merges neither."""

    def __init__(self, base, tail):
        n = tail.valid_rows
        self.parts = [base, MergedScan(
            tail.series_ids[:n], tail.ts[:n], tail.fields,
            tail.series_dict, tail.ts_base)]
        self.series_dict = base.series_dict
        self.num_rows = base.num_rows + n


def region_scan(region, fields: List[str], lo_ms: int, hi_ms: int,
                sid_set=None):
    """Rows for one region: the device-resident scan cache for warm
    regions (a `MergedScan`, or `ScanParts` where it holds a tail); a
    window-bounded streamed cold read for regions past the streaming
    threshold. All expose series_ids/ts/fields/series_dict, `ScanParts`
    a scan at a time."""
    from ..common.telemetry import increment_counter
    from ..common.time import TimestampRange
    from ..query.tpu_exec import region_streams_cold
    from ..storage import scan_cache

    if not region_streams_cold(region):
        increment_counter("promql_select_resident")
        base, tail = scan_cache.SCAN_CACHE.get_parts(region, hi_ms + 1)
        increment_counter("promql_select_parts",
                          tail="no" if tail is None else "yes")
        if tail is None:
            return base
        from ..common import exec_stats
        exec_stats.record("select", tail_rows=tail.valid_rows)
        return ScanParts(base, tail)
    # cold path: merged host read of only the selector's window and
    # fields — proportional to the window, never enters the scan
    # cache, leaves no device residency behind
    increment_counter("promql_select_streamed")
    from ..common import exec_stats
    with exec_stats.stage("promql_cold_scan", region=region.name):
        # equality matchers ride the SST index: whole files whose
        # blooms exclude every candidate series never decode
        data = region.snapshot().read_merged(
            projection=list(fields),
            time_range=TimestampRange(lo_ms, hi_ms + 1),
            sid_set=sid_set)
    exec_stats.record("promql_cold_scan", rows=data.num_rows)
    return data
