"""The full resident launch, every row of the scan read: layouts from
the table's runs or from the statement's selection, the host's row mask
(`query/tpu_exec.py` has the map). Tests replace functions here: callers
outside read them through this module at the call."""

from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np

from ..common import exec_stats
from ..common.telemetry import increment_counter
from ..ops.kernels import (_sorted_grouped_aggregate_pre, moment_results,
                           seg_len_bucket)
from ..storage.scan_cache import MergedScan
from . import scan_launch, scan_narrow
from .agg_plan import TpuPlan
from .scan_launch import (_base_launch, _bucket_phase, _field_filter_keep,
                          _group_bucket, _last_ts, _Launched,
                          _launch_extremes, _make_seams, _moment_reads,
                          _ops_need_gids, _outside, _pinned_groups,
                          _reduce_part, _series_keep, _tail_groups,
                          _untimed_part)

def _launch_scan_kernel(scan: MergedScan, schema, plan: TpuPlan,
                        part=_untimed_part,
                        sel=None) -> Optional[_Launched]:
    """`part(name)` times the host's steps for the resident path's
    EXPLAIN ANALYZE: `runs` (run-id sweep), `mask` (the predicates only
    the host can apply; the time range goes to the program as two
    scalars, `_device_window`), `upload` (every device_put), `launch`
    (the call that returns futures). `sel`: the row ranges
    `scan_narrow.select` resolved the predicates to, where the caller
    has them: the mask is their union, and where
    `scan_narrow.scan_group_axis` says so the kernel's group axis is the
    runs they touch (every row is still read, under the table's run ids)
    and everything after the launch is sized by those (`_table_layout`;
    `_selection_layout` where the base holds no layout of the statement's
    bucket grid: the live runs cut from the ranges themselves, the run
    ids made on the device). A tail takes the axis its base's launch
    took and a share of its size (`_base_launch`, `_pinned_groups`): what
    a tail holds tomorrow must not choose another program. Its launch
    also returns, after the plan's
    moments, what folds a window's growth across the seam with its base
    (`_moment_reads`, `_make_seams`: the part `seam`)."""
    n = scan.num_rows
    if n == 0:
        return None
    reads = list(_moment_reads(schema, plan, seams=scan.base is not None))
    ops = tuple(op for op, _read, _masked_by in reads)
    with part("runs"):
        lay = _selection_layout(scan, plan, sel, ops)
        if lay is None:
            lay = _table_layout(scan, plan, sel, ops)
        elif part is _reduce_part:
            increment_counter("scan_selection_layouts")
            exec_stats.record("reduce", runs="selection")
    with part("mask"):
        mask = _scan_row_mask(scan, schema, plan, sel)
    if mask is _NO_ROWS:
        return None
    _make_seams(scan, reads, part)

    # ---- device kernel (module-level jit; compile cache shared across
    # queries with the same moment signature + shape bucket) ----
    with part("upload"):
        value_ix, mask_ix, (d_ts, values, col_masks) = \
            scan_launch._columns(scan, reads)
        # a statement that nothing but time filters starts from the scan's
        # resident mask, all true or true on the valid rows of a padded
        # scan or a tail, and uploads none (n bool bytes a statement: 17 MB
        # at 17M rows); its time range is `window`
        window = scan_launch._device_window(plan, scan)
        if mask is None:
            d_mask = scan.device_pad_mask() \
                if scan.valid_rows is not None \
                else scan.device_valid_all()
        else:
            d_mask = scan.upload(mask)

    if lay.grid is not None:
        # run ids nobody laid out: a label a row, made where the rows are
        d_rid = scan_narrow.run_labels(scan.device_sids(), d_ts, lay.grid)
    elif lay.rid is not None:
        with part("upload"):
            d_rid = scan.upload(lay.rid)
    else:
        d_rid = d_ts
    with part("launch"):
        out = scan_launch._run_program(
            scan, _sorted_grouped_aggregate_pre, d_rid, d_mask, d_ts, window,
            values, col_masks, lay.run_ends, lay.live_starts,
            num_groups=lay.num_groups, ops=ops, value_ix=value_ix,
            mask_ix=mask_ix, seg_len_k=lay.seg_len_k)
    if out is None:         # a stand-in: compiled, not run
        return None
    distinct, counts = out
    results, passes = moment_results(distinct, counts, ops, value_ix, mask_ix)
    signature = (lay.run_key, lay.num_groups,
                 tuple((m.op, m.column) for m in plan.moments))
    warm = signature in scan.launched
    if len(scan.launched) >= 64:     # sweeping bucket origins never repeat
        scan.launched.clear()
    scan.launched.add(signature)
    return _Launched(results, counts, lay.nruns,
                     scan.series_ids[lay.run_starts], lay.run_buckets,
                     scan.series_dict, scan.ts_base, passes, warm,
                     lay.table_runs, mask is not None, lay.num_groups,
                     _launch_extremes(ops, value_ix, lay.num_groups,
                                      lay.seg_len_k))


class _Layout(NamedTuple):
    """A full launch's segments, from the table's runs (`_table_layout`)
    or from the statement's selection (`_selection_layout`)."""
    run_key: str
    nruns: int                        # the kernel's segments in use
    num_groups: int                   # of this many (a power of two)
    run_starts: np.ndarray            # [nruns] the row each starts at
    run_buckets: Optional[np.ndarray]  # [nruns] from the plan's origin
    run_ends: np.ndarray              # int32 [num_groups]
    #: int32 [num_groups] where the segments are the statement's live
    #: runs out of `table_runs`; None: the table's runs, end to end
    live_starts: Optional[np.ndarray]
    table_runs: Optional[int]
    seg_len_k: Optional[int]          # None: no op reads run ids
    rid: Optional[np.ndarray]         # the table's run ids a row, or
    grid: Optional[tuple]             # what `run_labels` makes them from


def _table_layout(scan: MergedScan, plan: TpuPlan, sel, ops) -> _Layout:
    """The table's runs (`_scan_runs`) as the kernel's segments: all of
    them, or where `scan_narrow.scan_group_axis` says so those the
    selection's ranges touch."""
    n = scan.num_rows
    run_key, (rid, nruns, run_starts, buckets) = _scan_runs(scan, plan)
    # cached with the runs, per set of ops that read run ids or not:
    # at 7.7M runs the ends, the lengths and their maximum are 0.15 s
    layout_key = "__layout:" + run_key
    like = _base_launch(scan, plan)
    min_groups = _pinned_groups(scan, plan)
    needs_gids = _ops_need_gids(ops, _group_bucket(nruns, min_groups))
    cached = scan.device.get(layout_key)
    if cached is not None \
            and cached[0] == _group_bucket(nruns, min_groups) \
            and (not needs_gids or (
                cached[2] is not None and rid is not None)):
        nbucket, run_ends, seg_len_k = cached
        if not needs_gids:
            rid = seg_len_k = None
    else:
        nbucket, run_ends, rid, seg_len_k = scan_launch._segment_layout(
            run_starts, n, ops, rid, pinned=scan.pinned,
            min_groups=min_groups)
        scan.device[layout_key] = (nbucket, run_ends, seg_len_k)
        if rid is not None:
            scan.device[run_key] = (rid, nruns, run_starts, buckets)
    table_runs = live_starts = None
    if sel is not None:
        lo, hi = scan_narrow.run_spans(run_starts, sel)
        follows = like is not None and like.axis
        if (like.axis if follows else scan_narrow.scan_group_axis(
                nruns, int((hi - lo).sum()))) == "live":
            table_runs = nruns
            nruns, nbucket, live_starts, run_ends = \
                scan_narrow.live_layout(
                    run_starts, run_ends, lo, hi, n,
                    _tail_groups(like) if follows else 0)
            run_starts = live_starts[:nruns]
    return _Layout(run_key, nruns, nbucket, run_starts,
                   _run_buckets(plan, buckets, run_starts), run_ends,
                   live_starts, table_runs, seg_len_k, rid, None)


def _selection_layout(scan: MergedScan, plan: TpuPlan, sel,
                      ops) -> Optional[_Layout]:
    """The live axis laid out from the statement's selection, for a
    bucket grid this base holds no layout of: a panel whose range ends
    at any second and not at a whole step (a dashboard's "now" while its
    table is written) brings a grid of another phase at every refresh,
    and the table's layout for it is a pass over every row on the host
    and the run ids of every row uploaded. Here the segments are the
    runs the grid cuts inside the selection's ranges
    (`scan_narrow.selection_runs`: the cost follows the selection), and
    the run ids are labels made on the device from the resident series
    ids and times (`scan_narrow.run_labels`: the kernels of the live
    axis read run ids for equality alone). Taken where a grid of the
    same stride has been laid out, whose run count stands for this one's
    (they differ by at most a run a series), and `scan_group_axis` gives
    the live axis by it; None: the table's layout."""
    b = plan.bucket
    if sel is None or b is None or not sel.n_ranges or scan.pinned \
            or scan.valid_rows is not None:
        return None
    run_key = f"__runs:{b.stride_ms}:{_bucket_phase(b)}"
    table_runs = scan.device.get(f"__grid_runs:{b.stride_ms}")
    if run_key in scan.device or table_runs is None:
        return None
    starts, ends, buckets = scan_narrow.selection_runs(
        scan.ts, sel, b.origin, b.stride_ms)
    if scan_narrow.scan_group_axis(table_runs[0], len(starts)) != "live":
        return None
    grid = seg_len_k = None
    if _ops_need_gids(ops, _group_bucket(table_runs[0])):
        # the grid's edge at or before the scan's first row, and the
        # buckets a series can lie in: series x buckets must fit a label
        edge = -((scan.ts_base - b.origin) % b.stride_ms)
        reach = _last_ts(scan) - scan.ts_base - edge
        per_series = reach // b.stride_ms + 1
        if reach >= 2**31 or \
                (int(scan.series_ids[-1]) + 1) * per_series >= 2**31:
            return None
        grid = tuple(np.asarray(x, np.int32)
                     for x in (edge, b.stride_ms, per_series))
        seg_len_k = seg_len_bucket(int((ends - starts).max()))
    num_groups, live_starts, run_ends = scan_narrow.padded_layout(
        starts, ends, scan.num_rows)
    return _Layout(run_key, len(starts), num_groups, starts, buckets,
                   run_ends, live_starts, table_runs[0], seg_len_k, None,
                   grid)


def _scan_runs(scan: MergedScan, plan: TpuPlan):
    """-> (cache key, (rid, nruns, run_starts, buckets)): the run ids
    over (series [, bucket]), cached per scan + bucket grid: dashboards
    repeat the same grouping over a warm region, and the
    flags/cumsum/nonzero sweep is O(n) host work per query otherwise.
    `buckets` number the grid from its phase (`_bucket_phase`), not from
    the statement's origin: a panel whose end moves by whole steps from
    one refresh to the next (a lowered PromQL range query) keeps its runs,
    and `_run_buckets` shifts the numbers to the statement's origin."""
    n = scan.num_rows
    sids = scan.series_ids
    if plan.bucket is not None:
        b = plan.bucket
        run_key = f"__runs:{b.stride_ms}:{_bucket_phase(b)}"
    elif plan.tag_groups:
        run_key = "__runs:series"
    else:
        run_key = "__runs:all"
    cached_runs = scan.device.get(run_key)
    if cached_runs is not None:
        return run_key, cached_runs
    if plan.bucket is not None:
        b = plan.bucket
        buckets = ((scan.ts - _bucket_phase(b))
                   // b.stride_ms).astype(np.int64)
        flags = np.empty(n, dtype=bool)
        flags[0] = True
        np.not_equal(sids[1:], sids[:-1], out=flags[1:])
        flags[1:] |= buckets[1:] != buckets[:-1]
    else:
        buckets = None
        flags = np.empty(n, dtype=bool)
        flags[0] = True
        np.not_equal(sids[1:], sids[:-1], out=flags[1:])
        if not plan.tag_groups:
            flags[:] = False
            flags[0] = True
    rid = None          # lazy: only first/last reads per-row run ids
    run_starts = np.nonzero(flags)[0]
    runs = (rid, len(run_starts), run_starts, buckets)
    scan.device[run_key] = runs
    if plan.bucket is not None:
        # what `_selection_layout` takes for any grid of this stride
        scan.device[f"__grid_runs:{plan.bucket.stride_ms}"] = \
            (len(run_starts),)
    # bound the per-scan run-context cache: each distinct bucket
    # spec stores O(n) host arrays, and dashboards sweeping many
    # strides over one hot region would otherwise grow host memory
    # past the scan-cache budget unchecked
    stale = [k for k in scan.device if k.startswith("__runs:")][:-4]
    for k in stale:
        scan.device.pop(k, None)
        scan.device.pop("__layout:" + k, None)
    return run_key, runs


def _run_buckets(plan: TpuPlan, buckets: Optional[np.ndarray],
                 run_starts: np.ndarray) -> Optional[np.ndarray]:
    """Each run's bucket number from the statement's own origin."""
    if buckets is None:
        return None
    b = plan.bucket
    return buckets[run_starts] - (b.origin - _bucket_phase(b)) // b.stride_ms


#: _scan_row_mask: the predicates leave no row (None means "every row")
_NO_ROWS = object()


def _scan_row_mask(scan: MergedScan, schema, plan: TpuPlan, sel=None):
    """-> the host row mask of what only the host can apply of the
    statement's predicates: a bool array, None when nothing but time
    filters (the scan's resident mask serves: the time range is the
    program's, `_device_window`), or _NO_ROWS. Where `scan_narrow.select`
    has resolved the tag predicates and the time window to row ranges
    (`sel`), the mask is their union: no pass over the table's series ids
    (with the two over its times, 0.3 s of a statement at 46M rows, and
    the part of it that differed most from one server process to the
    next). No pass over the times on any road: a range outside the scan's
    span is turned away by its ends (`_outside`), one inside it that holds
    no row launches and comes back with every count 0."""
    n = scan.num_rows
    if _outside(plan, scan):
        return _NO_ROWS
    if sel is not None and not plan.field_filters and \
            (scan.valid_rows is None or scan.pinned):
        if sel.n_ranges == 0:
            return _NO_ROWS
        mask = np.zeros(n, dtype=bool)
        for a, b in zip(sel.starts.tolist(),
                        (sel.starts + sel.lens).tolist()):
            mask[a:b] = True
        return mask
    if not plan.tag_predicates and not plan.field_filters:
        return None
    if plan.tag_predicates:     # per-series tag predicate → row mask
        sd = scan.series_dict
        smask = _series_keep(sd, schema.tag_names(),
                             np.arange(sd.num_series, dtype=np.int32),
                             plan.tag_predicates)
        if not smask.any():
            return _NO_ROWS
        mask = smask[scan.series_ids]
    else:
        mask = np.ones(n, dtype=bool)
    if scan.valid_rows is not None and scan.valid_rows < n:
        mask[scan.valid_rows:] = False   # shape-bucket padding rows
    for ff in plan.field_filters:
        mask &= _field_filter_keep(scan, ff)
    return mask if mask.any() else _NO_ROWS
