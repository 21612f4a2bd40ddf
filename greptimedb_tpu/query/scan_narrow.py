"""The resident reduce narrowed to the selected series' row ranges.

The scan cache is sorted by (series, time), so a statement whose tag
predicates keep a few series needs a few contiguous slices of the
resident mirrors: each series one range of rows, its time window a
contiguous part of that, both found by binary search. The device cuts
those slices out of its own mirrors into a compact block and runs the
same grouped aggregate over it (`ops/kernels.py:sorted_grouped_aggregate`);
runs, mask, fetch and collect are sized by the selected rows, not by
the table. `scan_read_path` chooses between this and the full launch
(`scan_full._launch_scan_kernel`) from what it can count.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.kernels import (_SEG_HIGH_CARD_THRESHOLD,
                           _sorted_grouped_aggregate_pre, moment_results,
                           open_window, shape_bucket)
from ..storage import scan_cache
from . import scan_launch

#: The narrowed launch runs while the compact block (`padded_rows`:
#: range bucket x length bucket) is at most 1 / this of the table.
#: Measured on a v5e (PR 31; 4,000 series x 4,320 rows = 17.28M, the whole
#: resident `reduce` of one statement, ms narrow / full, by table rows
#: over padded rows): `max` of ten fields by hour over 8 h: 527x 7.6 /
#: 179, 66x 12.0 / 179, 16x 38.0 / 183, 8.2x 65.6 / 186, 4.1x 203.6 /
#: 195.7, 2.1x 697 / 208; `max` of five by minute (the full launch then
#: takes the high-cardinality kernels) over 1 h: 4,219x 6.2 / 1,528, 66x
#: 18.7 / 1,569, 8.2x 118 / 1,599 (4,000 ranges of 512); over 12 h: 33x
#: 15.6 / 1,541, 8.2x 61.8 / 1,562, 4.1x 124 / 1,565, 2.1x 404 / 1,404.
#: They meet at 4.1x in the first shape (8,192 groups: the low-cardinality
#: kernels' edge windows gather 64 values a group and column); the
#: constant is the smallest share at which narrow was at least twice as
#: fast in every shape.
_NARROW_MAX_SHARE = 8

#: shortest compact range: shorter selections share one program
_MIN_RANGE_LEN = 512


def scan_read_path(n_rows: int, n_ranges: Optional[int],
                   padded_rows: int) -> str:
    """"narrow" or "full": how the resident reduce reads a table of
    `n_rows` for a statement whose point / IN tag conjuncts resolved to
    `n_ranges` row ranges (None: it has no such conjunct) that pad to
    `padded_rows` compact rows. The one place that chooses, from shapes
    and counts alone."""
    if n_ranges is None:
        return "full"
    return "narrow" if padded_rows * _NARROW_MAX_SHARE <= n_rows else "full"


#: The full launch's group axis is the statement's live runs (those its
#: row ranges touch) while their bucket is at most 1 / this of the bucket
#: of the table's runs, and the latter is past the kernels'
#: high-cardinality threshold (below it pickups are not the cost).
#: Measured on a v5e (PR 36; one launch's kernel time, ms on the table's
#: axis / on the live axis, by the live bucket's share of the table's).
#: 46.08M rows in 7.68M runs of six (bucket 8,388,608), the longrange
#: fleet panel's ops (count, growth, first, last, min and max of ts): 1/2
#: 2,353 / 1,333, 1/4 2,353 / 710, 1/8 2,353 / 376, 1/16 2,353 / 219;
#: `max` alone: 394 / 321, 170, 92, 55. 17.28M rows in 2.88M runs of six
#: (bucket 4,194,304: tsbs-cpu-4000 by minute): `max` of five fields 488 /
#: 360 (1/2), 145 (1/4), 77 (1/8); `avg` 406 / 356, 206, 97; `count`
#: alone 193 / 238, 130, 62: a prefix-sum pickup gathers the prefix at both
#: bounds on the live axis where the dense axis shifts the one it gathered
#: at the end, so at a half the integer sums lose. The constant is the
#: largest share at which the live axis won in every shape (1.5x to 3.4x).
_LIVE_AXIS_MAX_SHARE = 4


def scan_group_axis(n_runs: int, n_live: Optional[int]) -> str:
    """"live" or "table": the group axis of a full launch over a table
    cut into `n_runs` runs, for a statement whose row ranges touch
    `n_live` of them (None: it resolved no ranges). The one place that
    chooses, from counts alone."""
    if n_live is None:
        return "table"
    table_b = shape_bucket(n_runs, minimum=256)
    live_b = shape_bucket(n_live, minimum=256)
    return "live" if table_b > _SEG_HIGH_CARD_THRESHOLD and \
        live_b * _LIVE_AXIS_MAX_SHARE <= table_b else "table"


def run_spans(run_starts: np.ndarray, sel: "Selection"):
    """-> (lo, hi): range i of `sel` touches runs [lo[i], hi[i]) of a
    table cut at `run_starts`. A range that starts or ends inside a run
    keeps that run (the row mask decides its rows). Two searches over the
    ranges; ranges are ascending and disjoint, so the spans are too (two
    ranges inside one run share it: the later span starts after it)."""
    lo = np.searchsorted(run_starts, sel.starts, side="right") - 1
    hi = np.searchsorted(run_starts, sel.starts + sel.lens, side="left")
    lo[1:] = np.maximum(lo[1:], hi[:-1])
    return lo, hi


def live_layout(run_starts: np.ndarray, run_ends: np.ndarray,
                lo: np.ndarray, hi: np.ndarray, n: int, min_groups: int = 0):
    """-> (n_live, num_groups, starts, ends): the kernel's segments for
    the live runs the spans [lo[i], hi[i]) of `run_spans` name, end to
    end, padded to their bucket (at least `min_groups`: a tail's axis is
    pinned, `scan_launch._tail_groups`) with empty groups at `n` (as the
    table's padded runs are). Nothing of the table's run count is built."""
    c = hi - lo
    n_live = int(c.sum())
    live = np.repeat(lo - (np.cumsum(c) - c), c) + np.arange(n_live)
    return (n_live,) + padded_layout(run_starts[live], run_ends[live], n,
                                     min_groups)


def selection_runs(ts: np.ndarray, sel: "Selection", origin: int,
                   stride: int):
    """-> (starts, ends, buckets): the runs a bucket grid (`origin`,
    `stride`) cuts inside the ranges of `sel`, as rows of the table, and
    each run's bucket number from `origin`. One pass over the selected
    rows and none over the table: what a statement pays whose grid the
    scan holds no layout of (`scan_full._selection_layout`). A run ends
    with its range; the rows of its (series, bucket) outside the range
    are the row mask's to drop, as on the table's runs."""
    first = np.cumsum(sel.lens) - sel.lens
    rows = np.repeat(sel.starts - first, sel.lens) + \
        np.arange(sel.rows, dtype=np.int64)
    buckets = (ts[rows] - origin) // stride
    flags = np.empty(len(rows), dtype=bool)
    np.not_equal(buckets[1:], buckets[:-1], out=flags[1:])
    flags[first] = True
    at = np.nonzero(flags)[0]
    starts = rows[at]
    ends = np.empty_like(starts)
    ends[:-1] = rows[at[1:] - 1] + 1
    ends[-1] = rows[-1] + 1
    return starts, ends, buckets[at]


def padded_layout(starts: np.ndarray, ends: np.ndarray, n: int,
                  min_groups: int = 0):
    """-> (num_groups, starts, ends): the kernel's segments padded to
    their bucket (at least `min_groups`) with empty groups at `n`, as the
    table's padded runs are."""
    num_groups = shape_bucket(len(starts), minimum=max(256, min_groups))
    out = np.full((2, num_groups), n, dtype=np.int32)
    out[0, :len(starts)], out[1, :len(starts)] = starts, ends
    return num_groups, out[0], out[1]


@jax.jit
def run_labels(sids, ts, grid):
    """int32 [n]: a label a row that two rows share exactly where they
    share a series and a bucket of the grid (`grid`: its edge at or
    before the first row in `ts`'s coordinates, its stride, the buckets
    a series can lie in; traced, so one program a scan's length).
    Stands in for run ids wherever a kernel compares them and indexes
    nothing by them: the live axis (`_sga_body`, `dense=False`)."""
    edge, stride, per_series = grid
    return sids * per_series + jax.lax.div(ts - edge, stride)


@dataclass
class Selection:
    """The row ranges a statement's tag predicates and time window keep:
    range i is rows [starts[i], starts[i] + lens[i]) of series sids[i]."""
    sids: np.ndarray                  # int32 [k], ascending
    starts: np.ndarray                # int64 [k], table coordinates
    lens: np.ndarray                  # int64 [k], all > 0

    @property
    def n_ranges(self) -> int:
        return len(self.starts)

    @property
    def rows(self) -> int:
        return int(self.lens.sum())

    @property
    def range_bucket(self) -> int:
        return shape_bucket(self.n_ranges, minimum=1)

    @property
    def len_bucket(self) -> int:
        return shape_bucket(int(self.lens.max(initial=0)),
                            minimum=_MIN_RANGE_LEN)

    @property
    def padded_rows(self) -> int:
        return self.range_bucket * self.len_bucket


def select(scan, schema, plan) -> Optional[Selection]:
    """The ranges the statement keeps, or None when it has no point / IN
    tag conjunct to resolve to series (`sid_candidates_for_filters`, a
    superset by contract; every tag predicate is then applied exactly to
    those candidates only). No array of the table's length is built."""
    if not plan.tag_predicates:
        return None
    from ..mito.engine import sid_candidates_for_filters
    sd = scan.series_dict
    tag_names = schema.tag_names()
    cand = sid_candidates_for_filters(sd, tag_names, plan.tag_predicates)
    if cand is None:
        return None
    if len(cand):
        cand = cand[scan_launch._series_keep(sd, tag_names, cand,
                                             plan.tag_predicates)]
    # a padded scan repeats its last row: the ranges end at the valid rows
    sids = scan.series_ids[:scan.valid_rows]
    lo = np.searchsorted(sids, cand, side="left")
    hi = np.searchsorted(sids, cand, side="right")
    if plan.time_lo is not None:
        lo = scan_cache._lower_bound(scan.ts, lo, hi, plan.time_lo)
    if plan.time_hi is not None:
        hi = scan_cache._lower_bound(scan.ts, lo, hi, plan.time_hi)
    live = hi > lo
    return Selection(cand[live].astype(np.int32, copy=False),
                     lo[live].astype(np.int64), (hi - lo)[live])


def _cut(col, at, len_b: int):
    """[k_b * len_b]: the slices col[at[i] : at[i] + len_b], end to end.
    Contiguous slices, not a row gather: on a v5e (PR 31, six f32 columns
    of 17.28M rows) 8 x 4,096 rows take 1.3 ms against 5.9, 64 x 4,096
    1.7 against 39.6, 512 x 4,096 5.5 against 307.7 (24 ns a gathered
    value); a Python loop of `dynamic_slice`s is as fast up to 64 ranges
    and compiles a slice a range."""
    return jax.vmap(
        lambda a: jax.lax.dynamic_slice(col, (a,), (len_b,)))(at).reshape(-1)


@functools.partial(jax.jit, static_argnames=(
    "len_b", "num_groups", "ops", "value_ix", "mask_ix", "seg_len_k"))
def _narrow_reduce(cuts, ends, rid, row_mask, cols, *, len_b, num_groups,
                   ops, value_ix, mask_ix, seg_len_k):
    """Cut `cuts` out of the resident columns and reduce the compact
    block. cuts int32 [3, k_b]: each range's slice start (clamped by the
    host so that start + len_b stays inside the table) and the offsets
    [lo, hi) of its live rows inside that slice; cols is (ts, the value
    columns, the validities), each column once; value_ix / mask_ix index
    the latter two per moment (value -1: ts itself; mask -1: the column
    has no NULL). The time window is open: the live offsets already hold
    the statement's. -> the distinct results and the row counts
    (`ops/kernels.py:moment_sharing`)."""
    at, lo, hi = cuts
    j = jnp.arange(len_b, dtype=jnp.int32)[None, :]
    live = ((j >= lo[:, None]) & (j < hi[:, None])).reshape(-1)
    if row_mask is not None:
        live = live & row_mask
    ts, values, valid = jax.tree_util.tree_map(
        lambda c: _cut(c, at, len_b), cols)
    return _sorted_grouped_aggregate_pre(
        ts if rid is None else rid, live, ts, open_window(ts.dtype), values,
        valid, ends, num_groups=num_groups, ops=ops, value_ix=value_ix,
        mask_ix=mask_ix, seg_len_k=seg_len_k)


def launch(scan, schema, plan, sel: Selection, part):
    """-> scan_launch._Launched over the selection's runs, or None when it
    is empty. `part(name)` times the host's steps as the full launch's
    do: `runs`, `mask` (field filters only), `upload`, `launch`."""
    k, total = sel.n_ranges, sel.rows
    if k == 0:
        return None
    n = scan.num_rows
    k_b, len_b = sel.range_bucket, sel.len_bucket
    reads = list(scan_launch._moment_reads(schema, plan,
                                           seams=scan.base is not None))
    scan_launch._make_seams(scan, reads, part)
    with part("runs"):
        # compact coordinates: range i lives in [i * len_b, (i+1) * len_b),
        # its rows from `off[i]` on (0 unless the slice was clamped at the
        # table's end)
        at = np.minimum(sel.starts, n - len_b)
        off = sel.starts - at
        first = np.cumsum(sel.lens) - sel.lens       # selected-row index
        within = np.arange(total, dtype=np.int64) - \
            np.repeat(first, sel.lens)
        rows = np.repeat(sel.starts, sel.lens) + within
        pos = np.repeat(np.arange(k, dtype=np.int64) * len_b + off,
                        sel.lens) + within
        flags = np.zeros(total, dtype=bool)
        buckets = None
        if plan.bucket is not None:
            b = plan.bucket
            buckets = (scan.ts[rows] - b.origin) // b.stride_ms
            flags[1:] = buckets[1:] != buckets[:-1]
        if plan.bucket is not None or plan.tag_groups:
            flags[first] = True
        flags[0] = True
        run_rows = np.nonzero(flags)[0]
        run_starts = pos[run_rows]
        run_starts[0] = 0        # runs tile the block: padding joins a run
        ops = tuple(op for op, _read, _masked_by in reads)
        value_ix, mask_ix, cols = scan_launch._columns(scan, reads)
        nbucket, run_ends, rid, seg_len_k = scan_launch._segment_layout(
            run_starts, k_b * len_b, ops, pinned=scan.pinned)
    row_mask = None
    if plan.field_filters:
        with part("mask"):
            keep = np.ones(total, dtype=bool)
            for ff in plan.field_filters:
                keep &= scan_launch._field_filter_keep(scan, ff, rows)
            row_mask = np.zeros(k_b * len_b, dtype=bool)
            row_mask[pos] = keep
    with part("upload"):
        cuts = np.zeros((3, k_b), dtype=np.int32)
        cuts[0, :k], cuts[1, :k], cuts[2, :k] = at, off, off + sel.lens
    with part("launch"):
        out = scan_launch._run_program(
            scan, _narrow_reduce, cuts, run_ends, rid, row_mask, cols,
            len_b=len_b, num_groups=nbucket, ops=ops, value_ix=value_ix,
            mask_ix=mask_ix, seg_len_k=seg_len_k)
    if out is None:         # a stand-in tail: compiled, not run
        return None
    distinct, counts = out
    results, passes = moment_results(distinct, counts, ops, value_ix, mask_ix)
    run_range = np.searchsorted(first, run_rows, side="right") - 1
    # warm stays False: the dispatch floor (`_note_device_query_time`)
    # is fed by full launches, whose fixed cost it stands for
    return scan_launch._Launched(
        results, counts, len(run_starts), sel.sids[run_range],
        buckets[run_rows] if buckets is not None else None,
        scan.series_dict, scan.ts_base, passes, num_groups=nbucket,
        extremes=scan_launch._launch_extremes(ops, value_ix, nbucket,
                                              seg_len_k))
