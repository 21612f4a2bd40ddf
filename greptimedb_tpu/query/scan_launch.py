"""What every resident launch shares: the mirrors a moment reads, the
segments and the group axis, the time range as the program takes it, a
tail's executables, the in-flight result (`query/tpu_exec.py` has the
map). Tests replace `_device_window`, `_run_program` and
`_segment_layout`: the launches read them through this module."""

from __future__ import annotations

import contextlib
from dataclasses import dataclass
from typing import NamedTuple, Optional, Tuple

import jax
import numpy as np
import pandas as pd
import pyarrow as pa

from ..common import exec_stats
from ..errors import UnsupportedError
from ..ops.kernels import (_SEG_HIGH_CARD_THRESHOLD, distinct_arrays,
                           extreme_form, seg_len_bucket, shape_bucket)
from ..storage.scan_cache import _TAIL_SHARE, MergedScan, _run_diffs_key
from .agg_plan import RUN_DIFF_MOMENT_OPS, BucketGroup, TpuPlan, _refs
from .expr import Evaluator

def _last_ts(base: MergedScan) -> int:
    """The base's newest timestamp: one pass, once a base."""
    if "__ts_max" not in base.device:
        base.device["__ts_max"] = (int(base.ts.max()),)
    return base.device["__ts_max"][0]


def _outside(plan: "TpuPlan", scan: MergedScan) -> bool:
    """The statement's time range lies outside the span of the scan's
    rows: no pass over them. A tail's span is that of its rows (late rows
    carry it back into history); any other scan starts at its `ts_base`
    and ends at `_last_ts`."""
    first = scan.ts_min if scan.pinned else scan.ts_base
    if plan.time_hi is not None and plan.time_hi <= first:
        return True
    return plan.time_lo is not None and plan.time_lo > (
        scan.ts_max if scan.pinned else _last_ts(scan))


def _device_window(plan: "TpuPlan", scan: MergedScan):
    """The statement's time range [time_lo, time_hi) as the kernel takes
    it (`ops/kernels.py:_sorted_grouped_aggregate_pre`): inclusive bounds
    in the coordinates of `scan.device_ts()`, two 0-d int32 arrays. The
    upper one is made inclusive before the clip, so that a row at
    relative time 2**31 - 1 is kept by a range that ends beyond it; an
    open side is that extreme of an int32 (`ops/kernels.py:open_window`).
    Exact for a range that `_outside` has not turned away (one that
    starts past the int32 span starts past the scan's last row)."""
    i32 = np.iinfo(np.int32)
    lo, hi = i32.min, i32.max
    if plan.time_lo is not None:
        lo = min(max(int(plan.time_lo) - scan.ts_base, lo), hi)
    if plan.time_hi is not None:
        hi = min(max(int(plan.time_hi) - 1 - scan.ts_base, i32.min), hi)
    return np.asarray(lo, np.int32), np.asarray(hi, np.int32)


def _run_program(scan: MergedScan, fn, *args, **static):
    """`fn(*args, **static)` of a jitted `fn`, for every scan but a tail.
    A tail goes by its base's table of executables: the stand-in
    (`_warm_tail_programs`) lowers and compiles `fn` for the arguments'
    shapes, keeps the executable there and returns None; a tail calls the
    one kept under its own arguments' shapes (no trace and no compile:
    the first statement after a write launches as the hundredth does),
    and `fn` itself where none is (a base under the floor, a statement
    shape that was not warmed)."""
    if scan.programs is None:
        return fn(*args, **static)
    leaves, tree = jax.tree_util.tree_flatten(args)
    key = (fn, tree, tuple((tuple(x.shape), np.dtype(x.dtype))
                           for x in leaves), tuple(sorted(static.items())))
    if scan.stand_in:
        if key not in scan.programs:
            scan.programs[key] = fn.lower(*args, **static).compile()
        return None
    compiled = scan.programs.get(key)
    return fn(*args, **static) if compiled is None else compiled(*args)


def _reduce_part(name: str):
    """A part of the resident `reduce` stage: `reduce.<name>`."""
    return exec_stats.stage("reduce." + name)


def _untimed_part(name: str):
    """Streamed slices launch the same kernel from pool workers under
    their own stages (query/stream_exec.py): no `reduce` row to be a
    part of."""
    return contextlib.nullcontext()


class _LaunchShape(NamedTuple):
    """What a base's resident launch chose (`MergedScan.launch_shapes`),
    for the launch over its tail to follow and `_warm_tail_programs` to
    key by."""
    path: str                         # "narrow" | "full"
    range_bucket: Optional[int]       # of the selection's ranges
    axis: Optional[str]               # a full launch's: "live" | "table"
    groups: int                       # and its group axis (0: none)


def _statement_shape(plan: "TpuPlan") -> tuple:
    """What of a plan names a compiled launch, whatever its ranges and
    its bucket grid's phase."""
    return (None if plan.bucket is None else plan.bucket.stride_ms,
            bool(plan.tag_groups),
            tuple((m.op, m.column) for m in plan.moments),
            tuple(sorted((f.column, f.op) for f in plan.field_filters)))


def _base_launch(scan: MergedScan, plan: "TpuPlan") -> Optional[_LaunchShape]:
    """For a tail: what its base's launch of this statement chose, which
    ran just before it (None for any other scan). Two statements of one
    shape and other selections that interleave on one base read each
    other's: a tail then launches the other's program, or compiles its
    own, and answers the same."""
    return None if scan.base is None else \
        scan.base.launch_shapes.get(_statement_shape(plan))


@dataclass
class _Launched:
    """An in-flight device reduction: device handles + host fold context.

    XLA dispatch is asynchronous — the kernel call returns immediately
    with futures — so callers can launch many reductions (one per
    streamed slice), let host decode overlap device compute, and fetch
    every result in ONE device round trip."""
    #: device arrays, one per moment: moments whose result is one
    #: (`ops/kernels.py:moment_sharing`) hold the same array, which
    #: `device_get` copies back once
    results: tuple
    counts: object                    # device int32 [nbucket]
    nruns: int
    run_sids: np.ndarray              # per-run series id [nruns] — only
    run_buckets: Optional[np.ndarray]  # run-level context is retained, so
    series_dict: object               # a streamed slice's full arrays are
    ts_base: int                      # freed while its reduction is in flight
    #: the passes over the rows the program ran, and those it shared
    passes: Tuple[int, int]
    #: this scan launched the same kernel over the same columns before:
    #: nothing was compiled, uploaded or swept for this launch
    warm: bool = False
    #: the group axis is the statement's live runs (`nruns` of them) out
    #: of this many the table has; None: the axis is the table's runs
    table_runs: Optional[int] = None
    #: the host built and uploaded a row mask of the scan's length
    host_mask: bool = False
    #: the program's group axis (a power of two, `nruns` of it in use)
    num_groups: int = 0
    #: the form of the program's `first` / `last` / time extremes
    #: (`ops/kernels.py:extreme_form`); None: it holds none
    extremes: Optional[str] = None


def _moment_reads(schema, plan: TpuPlan, seams: bool = False):
    """-> per moment (kernel op, the column it reads, the column whose
    validity masks it). No column read: ts stands in (a ts extreme; a
    count or a string column, which read only the mask). No masking
    column: a row count. A column is a field's name or, for a
    RUN_DIFF_MOMENT_OPS moment, (counter, field): the derived mirror of
    `MergedScan.device_run_diffs`, whose `growth` a run is the moment.
    `seams` (a tail's launch): after the plan's moments, for each such
    moment the `first` of that mirror a run, the difference that reaches
    back before the run: where the run goes on from one of the base it
    belongs to the window (`_fold_runs`). It rides the arg-extreme of the
    `first` the lowering asks for beside a growth: no pass of its own."""
    for m in plan.moments:
        if m.op in ("min_ts", "max_ts"):
            yield ("min" if m.op == "min_ts" else "max"), None, m.column
        elif m.column is None:
            yield "count", None, None
        elif m.op in RUN_DIFF_MOMENT_OPS:
            yield "growth", (m.op == "increase", m.column), m.column
        else:
            dtype = schema.column_schema(m.column).dtype
            yield m.op, (None if dtype.is_string or dtype.is_binary
                         else m.column), m.column
    if seams:
        for m in plan.moments:
            if m.op in RUN_DIFF_MOMENT_OPS:
                yield "first", (m.op == "increase", m.column), m.column


def _make_seams(scan: MergedScan, reads, part) -> None:
    """The `reduce.seam` row: a tail's derived mirrors that these reads
    want and that are not there yet, made across the seam and uploaded."""
    if scan.base is None or scan.stand_in:
        return
    wanted = {r for _op, r, _m in reads if isinstance(r, tuple)
              and _run_diffs_key(r[1], r[0]) not in scan.device}
    if wanted:
        with part("seam"):
            for counter, name in sorted(wanted):
                scan.device_run_diffs(name, counter)


def _device_column(scan: MergedScan, column):
    """The resident mirror a kernel op of `_moment_reads` reads."""
    if isinstance(column, tuple):
        return scan.device_run_diffs(column[1], column[0])
    return scan.device_field(column)


def _columns(scan: MergedScan, reads):
    """-> (value_ix, mask_ix, cols): the resident columns that `reads`
    (`_moment_reads`) read as (ts, values, validities), each column once
    (a value index of -1: ts itself; a mask index of -1: the column has
    no NULL): what the moments share, told to the program statically."""
    d_ts = scan.device_ts()
    values, masks = [], []
    for _op, field_read, masked_by in reads:
        values.append(d_ts if field_read is None
                      else _device_column(scan, field_read))
        masks.append(None if masked_by is None
                     else scan.device_valid(masked_by))
    values, value_ix = distinct_arrays(values, d_ts)
    masks, mask_ix = distinct_arrays(masks, None)
    return value_ix, mask_ix, (d_ts, values, masks)


def _group_bucket(nruns: int, min_groups: int = 0) -> int:
    """A launch's group axis: the runs' power of two, at least 256."""
    return shape_bucket(nruns, minimum=max(256, min_groups))


def _tail_groups(like) -> int:
    """A tail's share of the group axis its base's launch took (`like`:
    `_base_launch`): a tail holds up to an eighth of
    its base's rows (`tail_capacity`: a sixteenth, as a power of two),
    and at the base's rows a run that many of its runs."""
    return like.groups // (_TAIL_SHARE // 2) if like is not None else 0


def _pinned_groups(scan: MergedScan, plan: TpuPlan) -> int:
    """The least group axis of a full launch over a tail (0 for any other
    scan): as a tail's row axis is a capacity, its group axis is what the
    region's series give, so that the runs a write adds meet a compiled
    program. Runs of whole series: one a series. Runs cut by a time
    bucket too: two a series, which holds the live flow (every series in
    one bucket) beside late rows of any share of the series in another,
    or the live flow across a bucket's edge, and at least the tail's
    share of its base's axis (`_tail_groups`: a panel by the minute cuts
    a run every six scrapes, and the tail of a table scraped for hours
    holds dozens a series); a tail that cuts more runs takes the next
    power of two, and compiles it once."""
    if not scan.pinned or (plan.bucket is None and not plan.tag_groups):
        return 0
    k = max(int(scan.series_dict.num_series), 1)
    if plan.bucket is None:
        return shape_bucket(k, minimum=256)
    return max(shape_bucket(2 * k, minimum=256),
               _tail_groups(_base_launch(scan, plan)))


def _ops_need_gids(ops, num_groups: int) -> bool:
    """Whether a launch's kernel ops read per-row run ids, by its group
    axis (`_group_bucket`): growth always, first / last / min / max above
    the high-cardinality threshold (the shift-doubling kernels' same-
    segment guard; at or under it `extreme_form` is `rows`, which reads
    a segment's bounds alone)."""
    return "growth" in ops or \
        (num_groups > _SEG_HIGH_CARD_THRESHOLD
         and any(op in ("first", "last", "min", "max") for op in ops))


def _launch_extremes(ops, value_ix, num_groups: int,
                     seg_len_k) -> Optional[str]:
    """The form a launch's `first` / `last` / time extremes take
    (`ops/kernels.py:extreme_form`); None: its program holds none."""
    if any(op in ("first", "last") or (op in ("min", "max") and v < 0)
           for op, v in zip(ops, value_ix)):
        return extreme_form(num_groups, seg_len_k)
    return None


def _segment_layout(run_starts: np.ndarray, n: int, ops, rid=None,
                    pinned: bool = False, min_groups: int = 0):
    """-> (num_groups, run_ends, rid, seg_len_k) for a launch over `n`
    rows cut into runs at `run_starts`; `rid` (the per-row run ids, made
    here unless handed in) and `seg_len_k` are None when no op reads
    them. `pinned` (a tail): `seg_len_k` is what a run of all `n` rows
    would need, not what the longest run has today, and the group axis
    is at least `min_groups` (`_pinned_groups`)."""
    nruns = len(run_starts)
    nbucket = _group_bucket(nruns, min_groups)
    # segment ends are free on the host (run boundaries are already
    # computed); shipping them skips the device binary search, the
    # dominant cost at high run cardinality
    run_ends = np.full(nbucket, n, dtype=np.int32)
    run_ends[:nruns - 1] = run_starts[1:]
    # with host ends the kernel reads gids for growth and for the
    # high-cardinality extremes (the shift-doubling kernels' same-segment
    # guard); for every other op ts stands in for shape and both the
    # O(n) rid cumsum and its upload are skipped
    if not _ops_need_gids(ops, nbucket):
        return nbucket, run_ends, None, None
    if rid is None:
        starts_mark = np.zeros(n, dtype=np.int32)
        starts_mark[run_starts[1:]] = 1
        rid = np.cumsum(starts_mark, dtype=np.int32)
    # static ceil-log2 of the longest run, bucketized to even
    # values so nearby layouts share one compile
    if pinned:
        return nbucket, run_ends, rid, seg_len_bucket(n)
    lens = np.diff(run_starts, append=np.int64(n))
    return nbucket, run_ends, rid, \
        seg_len_bucket(int(lens.max()) if len(lens) else 1)


def _bucket_phase(b: BucketGroup) -> int:
    """Where a bucket grid's edges lie within its stride: grids of one
    phase cut the same runs, and their bucket numbers differ by the whole
    strides between their origins."""
    return b.origin % b.stride_ms


def _series_keep(sd, tag_names, sids: np.ndarray, predicates) -> np.ndarray:
    """-> bool [len(sids)]: the series of `sids` that every tag predicate
    keeps (NULL compares UNKNOWN and drops, as WHERE does)."""
    k = len(sids)
    read = set().union(*(_refs(p) for p in predicates))
    sdf = pd.DataFrame({t: sd.decode_tag_column(sids, i)
                        for i, t in enumerate(tag_names) if t in read})
    ev = Evaluator(sdf)
    keep = np.ones(k, dtype=bool)
    for p in predicates:
        m = ev.eval(p)
        m = m.fillna(False).astype(bool).to_numpy() \
            if isinstance(m, pd.Series) else np.full(k, bool(m))
        keep &= m
    return keep


def _field_filter_keep(scan: MergedScan, ff,
                       rows: Optional[np.ndarray] = None) -> np.ndarray:
    """-> bool: the rows (all of the scan's, or those of `rows`) that the
    field filter keeps; a NULL keeps nothing."""
    vals, valid = scan.fields[ff.column]
    if vals.dtype == object:
        raise UnsupportedError(f"filter on non-numeric {ff.column}")
    if rows is not None:
        vals = vals[rows]
        valid = valid[rows] if valid is not None else None
    v = vals.astype(np.float64)
    cmp = {"eq": v == ff.value, "ne": v != ff.value,
           "lt": v < ff.value, "le": v <= ff.value,
           "gt": v > ff.value, "ge": v >= ff.value}[ff.op]
    if valid is not None:
        cmp &= valid
    if rows is None and len(cmp) < scan.num_rows:
        # a tail keeps its fields at their valid length
        cmp = np.concatenate(
            [cmp, np.zeros(scan.num_rows - len(cmp), dtype=bool)])
    return cmp


def _tag_column(sd, sids: np.ndarray, tag_index: int):
    """A partial frame's tag column for the runs' series. String tags go
    from the dictionary's value ids straight to the Arrow-backed `str`
    column pandas would infer from the decoded values: a take, where the
    decode makes a Python string a row and pandas reads each back (0.14 s
    a column at 808,000 rows, against 0.06 s). Any other value type keeps
    the decoded list."""
    ids, values = sd.tag_id_column(sids, tag_index)
    if not all(v is None or isinstance(v, str) for v in values):
        return sd.decode_tag_column(sids, tag_index)
    return pd.Series(pa.DictionaryArray.from_arrays(
        pa.array(ids, type=pa.int32()),
        pa.array(values, type=pa.string())).dictionary_decode(),
        dtype="str")
