"""Core TPU kernels: segment-reduce group-by and sort-based merge/dedup.

These are the hot loops of the database. In the reference they are:
- DataFusion's hash aggregate (src/query executes via DataFusion) → here a
  dictionary-encoded **segment reduce** (`jax.ops.segment_sum/min/max`) over
  dense group ids, which XLA lowers to efficient scatter-adds and which
  composes with time-bucketing by id arithmetic (gid = tag_id * nbuckets + b).
- The k-way MergeReader + DedupReader (src/storage/src/read/{merge,dedup}.rs,
  ~1.2k lines of comparison-driven CPU code) → here a **sort-based merge**:
  concatenate runs, `lexsort` by (series, ts, seq), and a vectorized keep-mask
  (last sequence per (series, ts) wins, DELETEs drop the key) — the pragmatic
  TPU answer from SURVEY.md §7.

Everything is static-shaped: batches are padded to shape buckets (powers of
two) with a validity mask so XLA compiles once per bucket, not per batch.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# op_type values in the storage engine (mirrors reference OpType:
# src/store-api/src/storage/requests.rs — Put/Delete).
OP_PUT = 0
OP_DELETE = 1

AGG_OPS = ("sum", "count", "avg", "min", "max", "first", "last",
           "stddev", "variance")


def shape_bucket(n: int, minimum: int = 1024) -> int:
    """Round n up to a power of two (>= minimum) to bound recompilations."""
    if n <= minimum:
        return minimum
    return 1 << (n - 1).bit_length()


def pad_axis0(arr: np.ndarray, target: int, fill=0) -> np.ndarray:
    n = arr.shape[0]
    if n == target:
        return arr
    pad = np.full((target - n,) + arr.shape[1:], fill, dtype=arr.dtype)
    return np.concatenate([arr, pad], axis=0)


def check_i64_safe(*arrays, what: str = "timestamps") -> None:
    """Guard against silent int64→int32 truncation.

    With jax_enable_x64 off (the default, and the norm on TPU), jnp.asarray
    silently narrows int64 host arrays to int32 — epoch-ms timestamps wrap
    negative and dedup/window logic returns wrong answers. Callers must
    rebase such values (e.g. to region-relative offsets) before the device.
    """
    import jax as _jax
    if _jax.config.jax_enable_x64:
        return
    lim = np.iinfo(np.int32)
    for a in arrays:
        if isinstance(a, np.ndarray) and a.dtype == np.int64 and a.size:
            mx, mn = int(a.max()), int(a.min())
            if mx > lim.max or mn < lim.min:
                raise ValueError(
                    f"{what} exceed int32 range ({mn}..{mx}) and x64 is "
                    f"disabled: rebase to region-relative offsets before "
                    f"device transfer (see SeriesMatrix.device_arrays)")


# ---------------------------------------------------------------------------
# Grouped aggregation
# ---------------------------------------------------------------------------

def grouped_aggregate(gids, mask, ts, values, col_masks=(), *, num_groups,
                      ops, has_col_masks=False):
    """Host-validating wrapper around the jitted kernel (see below).

    Rejects int64 inputs that would silently truncate when x64 is off."""
    check_i64_safe(ts, what="grouped_aggregate ts")
    check_i64_safe(*[v for v in values], what="grouped_aggregate values")
    return _grouped_aggregate(gids, mask, ts, tuple(values), tuple(col_masks),
                              num_groups=num_groups, ops=tuple(ops),
                              has_col_masks=has_col_masks)


@functools.partial(jax.jit, static_argnames=("num_groups", "ops", "has_col_masks"))
def _grouped_aggregate(
    gids: jax.Array,            # int32 [N] group id per row (invalid rows: any)
    mask: jax.Array,            # bool  [N] row validity (filter & padding)
    ts: jax.Array,              # int64/int32 [N] timestamps (for first/last)
    values: Tuple[jax.Array, ...],   # per-agg value column [N]
    col_masks: Tuple[jax.Array, ...] = (),  # per-agg column validity [N]
    *,
    num_groups: int,
    ops: Tuple[str, ...],
    has_col_masks: bool = False,
) -> Tuple[Tuple[jax.Array, ...], jax.Array]:
    """Fused masked group-by aggregation.

    `mask` is the row-level filter (predicates & padding); `col_masks`, when
    provided, add per-aggregation column validity (SQL null semantics: a null
    in one column must not hide the row from other aggregates).

    Returns (per-op result arrays [num_groups], group row-count [num_groups]).
    Empty groups yield 0 for sum/count and NaN for avg/min/max/first/last;
    callers null them out via the returned counts.
    """
    n = gids.shape[0]
    # Route masked-out rows to a scratch group so they never pollute results.
    safe_gids = jnp.where(mask, gids, num_groups)
    seg = num_groups + 1
    counts_all = jax.ops.segment_sum(mask.astype(jnp.int32), safe_gids,
                                     num_segments=seg)
    counts = counts_all[:num_groups]

    def agg_mask(i):
        if has_col_masks:
            return mask & col_masks[i]
        return mask

    results = []
    cache: Dict[Tuple[str, int], jax.Array] = {}

    def seg_sum(col, key, m):
        k = ("sum", key)
        if k not in cache:
            cache[k] = jax.ops.segment_sum(
                jnp.where(m, col, 0).astype(col.dtype), safe_gids,
                num_segments=seg)[:num_groups]
        return cache[k]

    def seg_count(m, key):
        k = ("count", key)
        if k not in cache:
            if not has_col_masks:
                cache[k] = counts
            else:
                cache[k] = jax.ops.segment_sum(
                    m.astype(jnp.int32), safe_gids, num_segments=seg)[:num_groups]
        return cache[k]

    for i, op in enumerate(ops):
        col = values[i]
        m = agg_mask(i)
        if op == "count":
            results.append(seg_count(m, i))
        elif op == "sum":
            results.append(seg_sum(col, i, m))
        elif op == "avg":
            s = seg_sum(col, i, m)
            c = seg_count(m, i)
            results.append(jnp.where(c > 0, s / jnp.maximum(c, 1), jnp.nan))
        elif op in ("stddev", "variance"):
            # Shifted one-pass moments: center on the column's global mean
            # before squaring (variance is shift-invariant). Squaring raw
            # values wraps int columns and loses the variance of large,
            # tight distributions to f32 cancellation; centering fixes both.
            colf = col.astype(jnp.promote_types(col.dtype, jnp.float32))
            c = seg_count(m, i)
            gc = jnp.maximum(jnp.sum(c), 1)
            shift = jnp.sum(jnp.where(m, colf, 0.0)) / gc
            d = jnp.where(m, colf - shift, 0.0)
            s = jax.ops.segment_sum(d, safe_gids,
                                    num_segments=seg)[:num_groups]
            sq = jax.ops.segment_sum(d * d, safe_gids,
                                     num_segments=seg)[:num_groups]
            cc = jnp.maximum(c, 1)
            # sample variance (ddof=1, DataFusion convention); <2 rows → NaN
            var = jnp.maximum(sq - (s / cc) * s, 0.0) / jnp.maximum(c - 1, 1)
            var = jnp.where(c >= 2, var, jnp.nan)
            results.append(jnp.sqrt(var) if op == "stddev" else var)
        elif op == "min":
            filled = jnp.where(m, col, _max_ident(col.dtype))
            r = jax.ops.segment_min(filled, safe_gids, num_segments=seg)[:num_groups]
            results.append(r)
        elif op == "max":
            filled = jnp.where(m, col, _min_ident(col.dtype))
            r = jax.ops.segment_max(filled, safe_gids, num_segments=seg)[:num_groups]
            results.append(r)
        elif op in ("first", "last"):
            # two-pass arg-extreme: find the extreme ts per group, then the
            # first row index achieving it, then gather the value.
            if op == "first":
                ext_ts = jax.ops.segment_min(
                    jnp.where(m, ts, _max_ident(ts.dtype)), safe_gids,
                    num_segments=seg)
            else:
                ext_ts = jax.ops.segment_max(
                    jnp.where(m, ts, _min_ident(ts.dtype)), safe_gids,
                    num_segments=seg)
            hit = m & (ts == ext_ts[safe_gids])
            idx = jax.ops.segment_min(
                jnp.where(hit, jnp.arange(n, dtype=jnp.int32), n), safe_gids,
                num_segments=seg)[:num_groups]
            safe_idx = jnp.minimum(idx, n - 1)
            # dtype-preserving null fill: NaN for floats, 0 for ints (callers
            # null empty groups via the returned counts)
            empty = jnp.nan if jnp.issubdtype(col.dtype, jnp.floating) \
                else jnp.zeros((), col.dtype)
            results.append(jnp.where(idx < n, col[safe_idx], empty))
        else:
            raise ValueError(f"unsupported agg op: {op}")
    return tuple(results), counts


def _max_ident(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf, dtype)
    return jnp.array(jnp.iinfo(dtype).max, dtype)


def _min_ident(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(-jnp.inf, dtype)
    return jnp.array(jnp.iinfo(dtype).min, dtype)


def time_bucket_ids(ts: jax.Array, origin: int, stride: int,
                    num_buckets: int) -> jax.Array:
    """Map timestamps onto [0, num_buckets) bucket ids (clamped)."""
    b = (ts - origin) // stride
    return jnp.clip(b, 0, num_buckets - 1).astype(jnp.int32)


def combine_group_ids(tag_gids: jax.Array, bucket_ids: jax.Array,
                      num_buckets: int) -> jax.Array:
    return (tag_gids.astype(jnp.int32) * num_buckets
            + bucket_ids.astype(jnp.int32))


# ---------------------------------------------------------------------------
# Sort-based merge + dedup
# ---------------------------------------------------------------------------

def sort_merge_dedup(series_ids, ts, seq, op_types, valid):
    """Host-validating wrapper: rejects int64 ts/seq that would silently
    truncate when x64 is off (rebase timestamps first)."""
    check_i64_safe(ts, what="sort_merge_dedup ts")
    check_i64_safe(seq, what="sort_merge_dedup seq")
    return _sort_merge_dedup(series_ids, ts, seq, op_types, valid)


@jax.jit
def _sort_merge_dedup(series_ids: jax.Array,  # int32 [N]
                      ts: jax.Array,          # int[N] (rebased if x64 off)
                      seq: jax.Array,         # int [N] write sequence
                      op_types: jax.Array,    # int8  [N] OP_PUT / OP_DELETE
                      valid: jax.Array,       # bool  [N] padding mask
                      ) -> Tuple[jax.Array, jax.Array]:
    """Merge-sort rows from any number of concatenated runs and compute the
    MVCC keep-mask.

    Returns (order, keep): `order` is the permutation sorting rows by
    (series, ts, seq) with invalid rows last; `keep[i]` marks, in sorted
    position i, rows that survive dedup — the highest sequence for each
    (series, ts) key, unless that winner is a DELETE.
    """
    n = series_ids.shape[0]
    big_series = jnp.where(valid, series_ids, jnp.iinfo(jnp.int32).max)
    order = jnp.lexsort((seq, ts, big_series))
    s_sorted = big_series[order]
    t_sorted = ts[order]
    op_sorted = op_types[order]
    v_sorted = valid[order]
    # last row of each (series, ts) run wins (seq ascending within run)
    nxt_same = jnp.concatenate([
        (s_sorted[1:] == s_sorted[:-1]) & (t_sorted[1:] == t_sorted[:-1]),
        jnp.array([False]),
    ])
    keep = v_sorted & (~nxt_same) & (op_sorted == OP_PUT)
    return order, keep


def _merge_order(s: np.ndarray, t: np.ndarray, q: np.ndarray) -> np.ndarray:
    """Permutation sorting rows by (series, ts, seq).

    Fast path: pack (sid, ts - ts_min) into ONE uint64 key and radix-sort
    it (np stable argsort on ints) — ~5x faster than the 3-key lexsort on
    multi-million-row slices. Stable order keeps input order within equal
    (sid, ts) keys, so the rare duplicate clusters are re-ordered by seq
    exactly afterwards; wide domains fall back to lexsort."""
    n = len(s)
    if n <= 1:
        return np.arange(n, dtype=np.intp)
    smin = int(s.min())
    sbits = max(int(int(s.max()) - smin).bit_length(), 1)
    tmin = int(t.min())
    tbits = max(int(int(t.max()) - tmin).bit_length(), 1)
    if sbits + tbits > 64:
        return np.lexsort((q, t, s))
    key = ((s.astype(np.int64) - smin).astype(np.uint64)
           << np.uint64(tbits)) | (t - tmin).astype(np.uint64)
    order = np.argsort(key, kind="stable")
    k_sorted = key[order]
    dup = k_sorted[1:] == k_sorted[:-1]
    if dup.any():
        # positions participating in an equal-key cluster (MVCC versions
        # of one (sid, ts)): sort that tiny subset by (key, seq)
        member = np.concatenate([[False], dup]) | \
            np.concatenate([dup, [False]])
        idx = np.nonzero(member)[0]
        sub = order[idx]
        order[idx] = sub[np.lexsort((q[sub], k_sorted[idx]))]
    return order


def merge_dedup_numpy(series_ids: np.ndarray, ts: np.ndarray, seq: np.ndarray,
                      op_types: np.ndarray, *,
                      keep_deletes: bool = False) -> np.ndarray:
    """Host/NumPy twin of sort_merge_dedup returning kept row indices in
    (series, ts) order — used by the flush path and as the test oracle.

    keep_deletes=True keeps the newest row per key even when it is a delete
    tombstone (compaction must preserve tombstones that shadow older files
    outside the merge set)."""
    order = _merge_order(series_ids, ts, seq)
    s, t, o = series_ids[order], ts[order], op_types[order]
    nxt_same = np.concatenate([(s[1:] == s[:-1]) & (t[1:] == t[:-1]), [False]])
    keep = ~nxt_same if keep_deletes else (~nxt_same) & (o == OP_PUT)
    return order[keep]


# ---------------------------------------------------------------------------
# Filter program → mask (compiled per query structure)
# ---------------------------------------------------------------------------

CMP_OPS = {"eq", "ne", "lt", "le", "gt", "ge", "isin", "between"}


def apply_cmp(op: str, col: jax.Array, a, b=None) -> jax.Array:
    if op == "eq":
        return col == a
    if op == "ne":
        return col != a
    if op == "lt":
        return col < a
    if op == "le":
        return col <= a
    if op == "gt":
        return col > a
    if op == "ge":
        return col >= a
    if op == "between":
        return (col >= a) & (col <= b)
    if op == "isin":
        return jnp.isin(col, a)
    raise ValueError(f"unknown cmp op {op}")


# ---------------------------------------------------------------------------
# Sorted-segment group-by (the LSM fast path)
# ---------------------------------------------------------------------------
# Post-merge scan data is sorted by (series, ts), so (series, time-bucket)
# group ids are non-decreasing — group-by becomes contiguous-segment
# reduction, with no scatter at all (XLA scatter serializes on TPU; measured
# ~100x slower than this path on v5e). Structure per segment [s, e):
#   inner:  whole blocks — per-block partials (one pass over the rows)
#           combined by prefix-sum difference (sum family) or an RMQ sparse
#           table over block partials (min/max family);
#   edges:  the two partial blocks.
# Float sums never difference a plain float32 running prefix: over a
# resident scan of 17M rows of values near 50 the prefix reaches ~1e9,
# whose float32 spacing is 64 — an hourly avg() came out wrong in the
# third digit. The prefix over block sums is carried as an unevaluated
# float32 pair (hi, lo) instead (_block_prefix), so a boundary difference
# is good to float32 rounding of the SEGMENT sum, whatever the scan size.
#
# Two block sizes, each for the forms that read it:
#
# _SEG_BLOCK, 32 rows: the forms whose edges are gathered windows of
# [num_groups, 2 * block] elements (`_edge_windows`: the low-cardinality
# sums and extremes, `_sorted_seg_argext`) and the in-block tables of
# `_sorted_seg_minmax`. A TPU scalar gather is ~10-20 ns an element, so
# the windows want a small block (measured on v5e: block=1024 → 128 ms
# for a 5-col avg over 16.7M rows, all in edge gathers; block=32 → the
# gathers drop 32x), and the sparse table over the mini partials keeps
# inner ranges O(1) a group. What it costs is the row axis viewed as
# [n / 32, 32]: a minor dimension that fills a quarter of a 128-lane
# register, so the view is a relayout copy into four times the bytes.
#
# _SUM_BLOCK, 128 rows: the sum family's prefix form (`sum_form`), which
# reads no edge window. [n / 128, 128] is the row axis as the chip tiles
# it, so the view is free, and a block read at a bound is one row of 128
# lanes. The probe of PR 45 (`CHANGES.md` has its table; one v5e, ms a
# pass over the rows under `where(mask & window)`, float sum | count):
#   17.28M rows, 65,536 dense ends: block 32, the form before   5.05 | 4.25
#     an in-block scan kept at [n / 128, 128], one scalar read an end:
#       by reduce_window 5.89 | 5.81; on the MXU at HIGHEST 2.37 | (5.96)
#     the end's block read as a row of 128 | 256 | 512 | 1,024 lanes,
#       the pair prefix as scalars     2.13 | 2.04 | 2.16 | 3.33 (1.81 at 128)
#     as built: that row of 128 and the pair prefix as a row    1.43 | 1.81
#   18M rows, 131,072 dense ends               7.32 | 5.47  ->  2.21 | 2.43
#   17.28M rows, 65,536 `starts=`              6.86 | 5.32  ->  2.19 | 2.11
#   46.08M rows, 1,048,576 `starts=`           90.4 | 61.0  ->  39.3 | 16.6
#   8,192 rows, 1,024 groups (a count alone)          0.955 ->  0.962
# (a count's figure holds the launch itself, about 1 ms.) In the traces
# the two scalar gathers of the pair prefix were 1.14 ms of a pass at
# 65,536 ends (8.7 ns a value) where the row gather is 0.29 (4.4 ns a row
# of 512 B): hence every read at a bound a row, the pair prefix too. A
# larger block buys nothing, and in a form that differences in-block sums
# it costs precision; the form as built differences none.
_SEG_BLOCK = 32
_SUM_BLOCK = 128

#: past this many groups the prefix form reads its bounds as scalars
#: again (`_seg_sum_scalar_reads`): a gathered [G, 128] is 512 B a group,
#: 512 MB at 1,048,576 groups (the largest shape the probe and a cell
#: ran: the fleet panel's live runs) and 16 GB at the 33,554,432 groups of
#: a read-back that groups by row, which the chip's compiler refuses
#: (`tsbs4k-read-while-ingest`'s check after its restart, PR 45).
_SUM_ROW_READS_MAX_GROUPS = 1 << 20


def _edge_windows(x, starts, ends, bs, be, ident, n):
    """Gather the two ≤block-sized partial-block windows of each segment,
    ident-filled outside [start, end) — [G, 2*block] per group."""
    B = _SEG_BLOCK
    ar = jnp.arange(B, dtype=jnp.int32)
    # left partial block: [s, min(e, bs*B)); right partial: [max(s, be*B), e)
    lidx = starts[:, None] + ar[None, :]
    lhi = jnp.minimum(ends, bs * B)
    lvalid = lidx < lhi[:, None]
    ridx = (be * B)[:, None] + ar[None, :]
    rvalid = (ridx >= starts[:, None]) & (ridx < ends[:, None])
    lv = jnp.where(lvalid, x[jnp.minimum(lidx, n - 1)], ident)
    rv = jnp.where(rvalid, x[jnp.minimum(ridx, n - 1)], ident)
    return jnp.concatenate([lv, rv], axis=1)


def _segment_bounds(gids, num_groups, n):
    # For dense integer queries, left-search at g equals right-search at
    # g-1, so starts is a shift of ends — one searchsorted, not two (the
    # binary search is the gather-bound cost at high cardinality). Requires
    # non-negative gids (starts[0] = 0), the contract of this module.
    ar = jnp.arange(num_groups, dtype=gids.dtype)
    ends = jnp.searchsorted(gids, ar, side="right").astype(jnp.int32)
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32), ends[:-1]])
    return (starts, ends) + _block_cover(starts, ends)


def _block_cover(starts, ends):
    B = _SEG_BLOCK
    bs = (starts + B - 1) // B        # first fully-covered block
    be = ends // B                    # one past last fully-covered block
    # when the segment lives inside one block, there are no inner blocks
    has_inner = be > bs
    return bs, be, has_inner


def _pad_block(x, ident, n):
    pad = (-n) % _SEG_BLOCK
    if pad:
        x = jnp.concatenate([x, jnp.full((pad,), ident, x.dtype)])
    return x, (n + pad) // _SEG_BLOCK


#: above this group count, segment reductions switch from edge-window
#: gathers (O(groups*block), gather-bound at high cardinality) to
#: O(groups)-read decompositions: sums read a row of `_SUM_BLOCK` lanes
#: at each bound under the pair prefix of the block sums (`sum_form`);
#: min/max use in-block sparse tables + block suffix/prefix scans.
_SEG_HIGH_CARD_THRESHOLD = 8192


def _pair_add(a, b):
    """Sum of two unevaluated float32 pairs (hi, lo), renormalized:
    Knuth two-sum keeps what hi + hi rounds away."""
    ah, al = a
    bh, bl = b
    s = ah + bh
    bb = s - ah
    err = (ah - (s - bb)) + (bh - bb)
    lo = err + (al + bl)
    hi = s + lo
    return hi, lo - (hi - s)


def _block_prefix(block_sums):
    """Exclusive prefix over per-block sums, [NB + 1]. Integers: exact
    cumsum, (csum, None). Floats: an unevaluated (hi, lo) float32 pair
    whose sum carries ~48 bits of the running prefix."""
    zero = jnp.zeros(1, block_sums.dtype)
    if jnp.issubdtype(block_sums.dtype, jnp.integer):
        return jnp.concatenate([zero, jnp.cumsum(block_sums)]), None
    hi, lo = jax.lax.associative_scan(
        _pair_add, (block_sums, jnp.zeros_like(block_sums)))
    return jnp.concatenate([zero, hi]), jnp.concatenate([zero, lo])


def sum_form(num_groups: int) -> str:
    """The form a program's float sums take at this many groups (static:
    the launch's group axis): `edge` (gathered edge windows over blocks
    of `_SEG_BLOCK`) or `prefix` (a row of `_SUM_BLOCK` lanes read at
    each bound, the whole blocks between under the pair prefix). An
    integer sum, so every count, takes `prefix` at any cardinality."""
    return "prefix" if num_groups > _SEG_HIGH_CARD_THRESHOLD else "edge"


def _sum_bounds(starts, ends, dense):
    """Where the prefix form reads, made once a launch and shared by its
    passes: per segment the block of its start and the rows of that
    block before it (b_s, r_s), and the same of its end (b_e, r_e). On a
    dense layout a start is the end before it, and its side a shift."""
    B = _SUM_BLOCK
    b_e, r_e = ends // B, ends % B
    if dense:
        return _shifted(b_e), _shifted(r_e), b_e, r_e
    return starts // B, starts % B, b_e, r_e


def _shifted(v):
    """v[g - 1] a group, zero for the first: on a dense layout what the
    start's side of a segment is of the end's."""
    return jnp.concatenate([jnp.zeros(1, v.dtype), v[:-1]])


def _sorted_seg_sum(x, starts, ends, bs, be, has_inner, n, dense, bounds):
    """Per-segment sum of x (zeros where masked; a bool counts).

    `edge` (`sum_form`; float sums at low cardinality): per-segment
    block partials + edge windows, blocks of `_SEG_BLOCK`.

    `prefix`: the rows as [n / 128, 128] (`_SUM_BLOCK`; on the chip a
    view of the column, no copy), the block sums out of one fused read,
    their exclusive prefix as the pair of `_block_prefix`. A segment
    inside one block is that block read as one row of 128 lanes and
    summed between its bounds; a longer one is the rest of its start's
    block, the whole blocks between as a difference of the pair prefix,
    and the rows of its end's block before the end. No running sum over
    rows of other segments is differenced, so a segment's sum is good to
    float32 rounding of its own terms (and of the pair prefix, ~48 bits
    of the table's). Every read at a bound is a row of 128 lanes (the
    pair prefix too, 32 blocks a row): on a v5e a row costs 4 ns where
    a scalar costs 9 (probe, PR 45). On a dense layout
    (`dense`: starts[g] == ends[g-1]) the start's side is a shift of the
    end's and nothing is read at the starts; segments picked out of such
    a layout (the live runs of a scan) read both bounds, and on a dense
    layout give the same bits. `bounds`: `_sum_bounds` of the segments,
    made once a launch."""
    exact = x.dtype == jnp.bool_ or jnp.issubdtype(x.dtype, jnp.integer)
    acc = jnp.promote_types(x.dtype, jnp.int32 if exact else jnp.float32)
    if sum_form(starts.shape[0]) == "edge" and not exact:
        B = _SEG_BLOCK
        xp, nb = _pad_block(x.astype(acc), 0, n)
        hi, lo = _block_prefix(xp.reshape(nb, B).sum(axis=1))
        lo_b = jnp.minimum(bs, nb)
        inner = jnp.where(has_inner,
                          (hi[be] - hi[lo_b]) + (lo[be] - lo[lo_b]), 0)
        edges = _edge_windows(
            x.astype(acc), starts, ends,
            jnp.where(has_inner, bs, (starts // B) + 1),
            jnp.where(has_inner, be, starts // B + 1), 0, n)
        return inner + edges.sum(axis=1)

    if starts.shape[0] > _SUM_ROW_READS_MAX_GROUPS:
        return _seg_sum_scalar_reads(x.astype(acc), starts, ends, n, dense)

    B = _SUM_BLOCK
    nb, left = divmod(n, B)
    # the whole blocks, and the rows left over as one block of their own
    whole = x[:nb * B].reshape(nb, B) if nb else None
    rest = jnp.pad(x[nb * B:], (0, B - left))[None, :] if left else None
    hi, lo = _block_prefix(jnp.concatenate(
        [blocks.sum(axis=1, dtype=acc) for blocks in (whole, rest)
         if blocks is not None]))
    if lo is None:
        lo = jnp.zeros_like(hi)
    # the prefix as rows of 128 lanes, 32 blocks a row in four bands:
    # hi[b], lo[b], hi[b + 1], lo[b + 1]
    table = jnp.concatenate(
        [jnp.pad(p[k:], (0, k + -p.shape[0] % 32)).reshape(-1, 32)
         for k in (0, 1) for p in (hi, lo)], axis=1)
    lane = jnp.arange(128, dtype=jnp.int32)[None, :]

    def block_at(b):
        """[G, B]: block b a group (past the last: masked by r == 0)."""
        if whole is None:
            return rest
        rows = whole[jnp.minimum(b, nb - 1)]
        return rows if rest is None else \
            jnp.where((b >= nb)[:, None], rest, rows)

    def between(rows, lo_lane, hi_lane):
        keep = (lane >= lo_lane[:, None]) & (lane < hi_lane[:, None])
        return jnp.where(keep, rows, jnp.zeros((), rows.dtype)).sum(
            axis=1, dtype=acc)

    def rest_of(rows, r):
        """A block from a bound inside it on; none from its edge on: the
        block is a whole one of the segment's."""
        return between(rows, jnp.where(r > 0, r, B), jnp.full_like(r, B))

    def prefix_at(b, r):
        """The pair prefix at the first whole block from a bound on: at
        its own block where the bound is the block's edge."""
        rows = table[b // 32]
        at = b % 32 + 64 * (r > 0)
        return (between(rows, at, at + 1), between(rows, at + 32, at + 33))

    b_s, r_s, b_e, r_e = bounds
    ends_block = block_at(b_e)
    inside = between(ends_block, r_s, r_e)      # where b_s == b_e
    head = between(ends_block, jnp.zeros_like(r_e), r_e)
    e_hi, e_lo = prefix_at(b_e, jnp.zeros_like(r_e))
    if dense:
        tail = _shifted(rest_of(ends_block, r_e))
        s_hi, s_lo = map(_shifted, prefix_at(b_e, r_e))
    else:
        tail = rest_of(block_at(b_s), r_s)
        s_hi, s_lo = prefix_at(b_s, r_s)
    inner = (e_hi - s_hi) + (e_lo - s_lo)
    return jnp.where(b_s == b_e, inside, (tail + head) + inner)


def _seg_sum_scalar_reads(x, starts, ends, n, dense):
    """The prefix form past `_SUM_ROW_READS_MAX_GROUPS`: in-block
    inclusive scans kept in memory (blocks of `_SEG_BLOCK`) under the
    pair prefix make a global prefix P, read as three scalars a bound;
    each segment is P[end] - P[start]. 12 B a group where a row read is
    512, and the form every launch past the threshold took before PR 45:
    it carries ~1 ulp of a block's sum into a small segment."""
    B = _SEG_BLOCK
    xp, nb = _pad_block(x, 0, n)
    inblock = jnp.cumsum(xp.reshape(nb, B), axis=1)      # inclusive scans
    hi, lo = _block_prefix(inblock[:, -1])

    def prefix_at(idx):
        # exclusive global prefix as a pair, idx ∈ [0, nb*B]
        b = idx // B                        # b == nb only when r == 0
        r = idx % B
        inb = jnp.where(
            r > 0,
            inblock[jnp.minimum(b, nb - 1), jnp.maximum(r - 1, 0)], 0)
        return hi[b], (inb if lo is None else lo[b] + inb)

    pe_hi, pe_lo = prefix_at(ends)
    if dense:       # P[start] is a shift of P[end]
        return (pe_hi - _shifted(pe_hi)) + (pe_lo - _shifted(pe_lo))
    ps_hi, ps_lo = prefix_at(starts)
    return (pe_hi - ps_hi) + (pe_lo - ps_lo)


def _floor_log2(ln, K):
    """Integral floor(log2(ln)) clamped to [0, K-1]: float32 log2 can round
    up for huge segments (>= ~2^23 blocks), making the RMQ read past the
    segment."""
    k = jnp.zeros_like(ln)
    for j in range(1, min(K, 31)):
        k = k + (ln >= (1 << j)).astype(ln.dtype)
    return jnp.clip(k, 0, K - 1).astype(jnp.int32)


def _sorted_seg_minmax(x, starts, ends, bs, be, has_inner, n, *, is_min):
    red = jnp.minimum if is_min else jnp.maximum
    ident = _max_ident(x.dtype) if is_min else _min_ident(x.dtype)
    xp, nb = _pad_block(x, ident, n)
    bm = xp.reshape(nb, _SEG_BLOCK)
    bm = bm.min(axis=1) if is_min else bm.max(axis=1)     # [NB]
    # sparse table: ST[k][i] = reduce over blocks [i, i + 2^k)
    K = max(1, (nb - 1).bit_length() + 1)
    st = [bm]
    for k in range(1, K):
        shift = 1 << (k - 1)
        prev = st[-1]
        rolled = jnp.concatenate(
            [prev[shift:], jnp.full((min(shift, nb),), ident, prev.dtype)])
        st.append(red(prev, rolled))
    ST = jnp.stack(st)                                    # [K, NB]
    B = _SEG_BLOCK
    num_groups = starts.shape[0]
    if num_groups <= _SEG_HIGH_CARD_THRESHOLD:
        # low cardinality: per-segment edge windows (cheap at small G)
        ln = jnp.maximum(be - bs, 1)
        k = _floor_log2(ln, K)
        lo = jnp.minimum(bs, nb - 1)
        hi = jnp.clip(be - (1 << k), 0, nb - 1)
        inner = red(ST[k, lo], ST[k, hi])
        inner = jnp.where(has_inner, inner, ident)
        edges = _edge_windows(x, starts, ends,
                              jnp.where(has_inner, bs, starts // B + 1),
                              jnp.where(has_inner, be, starts // B + 1),
                              ident, n)
        er = edges.min(axis=1) if is_min else edges.max(axis=1)
        return red(inner, er)

    # high cardinality: [G, 2*block] edge gathers are the bottleneck
    # (O(groups*block) random access). Replace them with in-block
    # prefix/suffix scans plus an in-block sparse table so every segment
    # resolves with a handful of O(G) gathers:
    #   single-block segment  -> two in-block-ST lookups
    #   multi-block segment   -> suffix[left] ∧ block-ST inner ∧ prefix[right]
    blocks2d = xp.reshape(nb, B)
    if is_min:
        pref = jax.lax.cummin(blocks2d, axis=1)
        suff = jax.lax.cummin(blocks2d[:, ::-1], axis=1)[:, ::-1]
    else:
        pref = jax.lax.cummax(blocks2d, axis=1)
        suff = jax.lax.cummax(blocks2d[:, ::-1], axis=1)[:, ::-1]
    K2 = max(1, (B - 1).bit_length() + 1)
    st_in = [blocks2d]
    for k in range(1, K2):
        shift = 1 << (k - 1)
        prev = st_in[-1]
        rolled = jnp.concatenate(
            [prev[:, shift:],
             jnp.full((nb, min(shift, B)), ident, prev.dtype)], axis=1)
        st_in.append(red(prev, rolled))
    STIN = jnp.stack(st_in)                               # [K2, NB, B]

    e1 = jnp.maximum(ends - 1, 0)
    lb = jnp.minimum(starts // B, nb - 1)
    r0 = starts % B
    rb = jnp.minimum(e1 // B, nb - 1)
    r1 = e1 % B
    single = lb == rb

    seg_len = jnp.maximum(ends - starts, 1)               # <= B when single
    k2 = _floor_log2(seg_len, K2)
    single_val = red(STIN[k2, lb, jnp.minimum(r0, B - 1)],
                     STIN[k2, lb, jnp.clip(r1 + 1 - (1 << k2), 0, B - 1)])

    left = suff[lb, jnp.minimum(r0, B - 1)]
    right = pref[rb, r1]
    iln = rb - lb - 1                                     # inner block count
    kin = _floor_log2(jnp.maximum(iln, 1), K)
    ilo = jnp.clip(lb + 1, 0, nb - 1)
    ihi = jnp.clip(rb - (1 << kin), 0, nb - 1)
    inner = jnp.where(iln >= 1, red(ST[kin, ilo], ST[kin, ihi]), ident)
    multi_val = red(red(left, right), inner)

    out = jnp.where(single, single_val, multi_val)
    return jnp.where(ends > starts, out, ident)


def seg_len_bucket(max_len: int) -> int:
    """Static pass-count bucket for the shift-doubling kernels: the
    smallest even k with 2^k >= max_len. Even buckets bound recompiles;
    the kernels' correctness REQUIRES 2^k >= the longest segment, so
    every caller (scan launch, tests) must derive k through
    this one helper."""
    return -(-max(max_len - 1, 1).bit_length() // 2) * 2


def _seg_minmax_doubling(x, gids, starts, ends, ident, *, is_min, k_max):
    """Segmented min/max by shift-doubling: k_max passes of pure
    elementwise work (shift + gid compare + select), no gathers beyond
    the final per-segment pickup. After pass k, y[i] covers
    [i, min(i + 2^k, segment end)); requires 2^k_max >= the longest
    segment (the host caller bucketizes that bound into `k_max`).

    At high cardinality this replaces the in-block sparse table
    (`_sorted_seg_minmax`'s [K2, NB, B] build is n·log B memory traffic;
    the VERDICT r3/r5 kernel gap) with ~k_max linear passes that map to
    the VPU with no random access — the winning shape on TPU, where
    gathers, not FLOPs, priced the old kernel."""
    n = x.shape[0]
    red = jnp.minimum if is_min else jnp.maximum
    y = x
    for k in range(k_max):
        sh = 1 << k
        if sh >= n:
            break
        ys = jnp.concatenate([y[sh:], jnp.full((sh,), ident, y.dtype)])
        gs = jnp.concatenate(
            [gids[sh:], jnp.full((sh,), -1, gids.dtype)])
        y = jnp.where(gs == gids, red(y, ys), y)
    out = y[jnp.minimum(starts, n - 1)]
    return jnp.where(ends > starts, out, ident)


def _seg_growth_doubling(x, m, gids, starts, ends, *, k_max):
    """Per segment the sum of x over its valid rows (m) but the first of
    them: PromQL's raw window growth, where x holds each sample's
    difference to the sample before it (reset-aware for a counter) and
    the first one's reaches back before the window. Shift-doubling like
    its neighbours: k_max passes for "has an earlier valid row in its
    segment", k_max for the sum, one pickup a segment. No prefix over
    the whole scan, so a segment's sum is good to float32 rounding of
    its own few terms; no gather but the pickup (sum - first by the
    prefix-sum and arg-extreme kernels would take six a segment, and the
    float prefix compiles for two minutes at 46M rows). Requires
    2^k_max >= the longest segment."""
    n = x.shape[0]

    def shifted(a, sh, fill, back):
        pad = jnp.full((sh,), fill, a.dtype)
        return jnp.concatenate([pad, a[:-sh]] if back else [a[sh:], pad])

    # earlier[i]: a valid row lies before i in i's segment
    earlier = (shifted(gids, 1, -1, True) == gids) & shifted(m, 1, False,
                                                             True)
    for k in range(k_max):
        sh = 1 << k
        if sh >= n:
            break
        earlier = earlier | ((shifted(gids, sh, -1, True) == gids)
                             & shifted(earlier, sh, False, True))
    # y[i] after pass k: the sum over [i, min(i + 2^(k+1), segment end))
    y = jnp.where(m & earlier, x, 0)
    for k in range(k_max):
        sh = 1 << k
        if sh >= n:
            break
        y = jnp.where(shifted(gids, sh, -1, False) == gids,
                      y + shifted(y, sh, 0, False), y)
    return jnp.where(ends > starts, y[jnp.minimum(starts, n - 1)], 0)


def _seg_argext_doubling(key, gids, starts, ends, ident, *, is_min, k_max):
    """Segmented lexicographic arg-extreme of (key, position) by
    shift-doubling — one fused pass family carrying the (value, pos)
    pair, replacing the old two-pass minmax + O(n) gather formulation
    (first/last at high cardinality). Returns (ext_key, pos); pos = -1
    for empty segments."""
    n = key.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    for k in range(k_max):
        sh = 1 << k
        if sh >= n:
            break
        ks = jnp.concatenate([key[sh:], jnp.full((sh,), ident, key.dtype)])
        ps = jnp.concatenate([pos[sh:], jnp.full((sh,), -1, jnp.int32)])
        gs = jnp.concatenate(
            [gids[sh:], jnp.full((sh,), -1, gids.dtype)])
        if is_min:
            better = (ks < key) | ((ks == key) & (ps < pos))
        else:
            better = (ks > key) | ((ks == key) & (ps > pos))
        take = (gs == gids) & better
        key = jnp.where(take, ks, key)
        pos = jnp.where(take, ps, pos)
    sel = jnp.minimum(starts, n - 1)
    live = ends > starts
    return (jnp.where(live, key[sel], ident),
            jnp.where(live, pos[sel], -1))


def _sorted_seg_argext(x, starts, ends, bs, be, has_inner, n, *, is_min,
                       gids=None):
    """Per-segment lexicographic arg-extreme of (x, position).

    first = row with the smallest (ts, position); last = largest — matching
    grouped_aggregate's ts-extreme semantics even when ts is NOT sorted
    within a segment (e.g. several series collapsed into one GROUP BY key).
    Returns (ext_x, pos); ext_x == ident means the segment had no valid row.
    """
    B = _SEG_BLOCK
    ident = _max_ident(x.dtype) if is_min else _min_ident(x.dtype)
    num_groups = starts.shape[0]
    if gids is not None and num_groups > _SEG_HIGH_CARD_THRESHOLD:
        # two-pass formulation so the cardinality-robust minmax does the
        # heavy lifting: extreme value per segment, then the tie-breaking
        # position (min pos for first / max pos for last) among the rows
        # attaining it, located via one O(n) gather.
        ext = _sorted_seg_minmax(x, starts, ends, bs, be, has_inner, n,
                                 is_min=is_min)
        iota = jnp.arange(n, dtype=jnp.int32)
        hit = x == ext[gids]
        if is_min:
            pos_fill = jnp.where(hit, iota, n)
            pos = _sorted_seg_minmax(pos_fill, starts, ends, bs, be,
                                     has_inner, n, is_min=True)
            pos = jnp.where(pos >= n, -1, pos)
        else:
            pos_fill = jnp.where(hit, iota, -1)
            pos = _sorted_seg_minmax(pos_fill, starts, ends, bs, be,
                                     has_inner, n, is_min=False)
        return ext, pos

    def pick(ta, pa, tb, pb):
        if is_min:
            a_wins = (ta < tb) | ((ta == tb) & (pa <= pb))
        else:
            a_wins = (ta > tb) | ((ta == tb) & (pa >= pb))
        return jnp.where(a_wins, ta, tb), jnp.where(a_wins, pa, pb)

    xp, nb = _pad_block(x, ident, n)
    xb = xp.reshape(nb, B)
    if is_min:
        off = jnp.argmin(xb, axis=1).astype(jnp.int32)   # first occurrence
    else:
        off = (B - 1 - jnp.argmax(xb[:, ::-1], axis=1)).astype(jnp.int32)
    bt = jnp.take_along_axis(xb, off[:, None], axis=1)[:, 0]
    bp = jnp.arange(nb, dtype=jnp.int32) * B + off
    # pair sparse table over mini partials
    K = max(1, (nb - 1).bit_length() + 1)
    st_t, st_p = [bt], [bp]
    for k in range(1, K):
        shift = 1 << (k - 1)
        pt, pp = st_t[-1], st_p[-1]
        rt = jnp.concatenate(
            [pt[shift:], jnp.full((min(shift, nb),), ident, pt.dtype)])
        rp = jnp.concatenate(
            [pp[shift:], jnp.full((min(shift, nb),), -1, pp.dtype)])
        nt, np_ = pick(pt, pp, rt, rp)
        st_t.append(nt)
        st_p.append(np_)
    ST_T, ST_P = jnp.stack(st_t), jnp.stack(st_p)
    ln = jnp.maximum(be - bs, 1)
    k = _floor_log2(ln, K)
    lo = jnp.minimum(bs, nb - 1)
    hi = jnp.clip(be - (1 << k), 0, nb - 1)
    it, ip = pick(ST_T[k, lo], ST_P[k, lo], ST_T[k, hi], ST_P[k, hi])
    it = jnp.where(has_inner, it, ident)
    ip = jnp.where(has_inner, ip, -1)
    # edge windows carry (value, global position) pairs
    ar = jnp.arange(B, dtype=jnp.int32)
    bsx = jnp.where(has_inner, bs, starts // B + 1)
    bex = jnp.where(has_inner, be, starts // B + 1)
    lidx = starts[:, None] + ar[None, :]
    lvalid = lidx < jnp.minimum(ends, bsx * B)[:, None]
    ridx = (bex * B)[:, None] + ar[None, :]
    rvalid = (ridx >= starts[:, None]) & (ridx < ends[:, None])
    widx = jnp.concatenate([lidx, ridx], axis=1)
    wvalid = jnp.concatenate([lvalid, rvalid], axis=1)
    wt = jnp.where(wvalid, x[jnp.minimum(widx, n - 1)], ident)
    if is_min:
        woff = jnp.argmin(wt, axis=1)[:, None]
    else:
        woff = (wt.shape[1] - 1 -
                jnp.argmax(wt[:, ::-1], axis=1))[:, None]
    et = jnp.take_along_axis(wt, woff, axis=1)[:, 0]
    ep = jnp.take_along_axis(widx, woff, axis=1)[:, 0]
    ep = jnp.where(et == ident, -1, ep)
    ft, fp = pick(it, ip, et, ep)
    return ft, fp


def distinct_arrays(arrays, absent):
    """-> (the arrays each once, per input its index among them): by
    identity, so that a kernel sees two moments over one array as one
    parameter and can share what depends on it alone. -1 where the input
    is `absent` (a value that is the time index itself; a validity of
    None: the column has no NULL)."""
    out, ix, seen = [], [], {}
    for a in arrays:
        if a is absent:
            ix.append(-1)
            continue
        if id(a) not in seen:
            seen[id(a)] = len(out)
            out.append(a)
        ix.append(seen[id(a)])
    return tuple(out), tuple(ix)


def open_window(dtype=np.int32):
    """The time window that keeps every row of a time index of `dtype`
    (as the device holds it: an int64 is an int32 there without x64):
    its extremes, as the 0-d arrays `_sorted_grouped_aggregate_pre`
    takes."""
    dtype = jax.dtypes.canonicalize_dtype(dtype)
    lo, hi = (-np.inf, np.inf) if np.issubdtype(dtype, np.floating) \
        else (np.iinfo(dtype).min, np.iinfo(dtype).max)
    return np.asarray(lo, dtype), np.asarray(hi, dtype)


#: the launch's row count: the one pass every program runs, and what a
#: count under no validity of its own is
_ROW_COUNT = ("count", -1)


def _moment_passes(ops, value_ix, mask_ix):
    """Per moment the row passes its result is made of, as the keys
    `_sga_body` computes each once under: a pass depends on the row mask,
    one validity (`mask_ix`, -1: none) and at most one column
    (`value_ix`, -1: the time index), and on nothing else of its moment.
    A time extreme beside a first / last under the same validity is that
    arg-extreme's own extreme."""
    ext = {("argext", op == "first", mk)
           for op, mk in zip(ops, mask_ix) if op in ("first", "last")}
    out = []
    for op, v, mk in zip(ops, value_ix, mask_ix):
        count = ("count", mk)
        ext_t = ("argext", op == "min", mk)
        if op == "count":
            out.append((count,))
        elif op == "avg":
            out.append((("sum", v, mk), count))
        elif op in ("stddev", "variance"):
            out.append((count, ("dev", v, mk), ("dev_sq", v, mk)))
        elif op in ("min", "max") and v < 0 and ext_t in ext:
            out.append((ext_t,))
        elif op in ("first", "last"):
            out.append((("argext", op == "first", mk),))
        elif op in ("sum", "sum_sq", "min", "max", "growth"):
            out.append(((op, v, mk),))
        else:
            raise ValueError(f"unsupported agg op: {op}")
    return out


def _result_keys(ops, value_ix, mask_ix):
    """Per moment what names its result: moments of one key are one
    array."""
    return [("count", mk) if op == "count" else (op, v, mk)
            for op, v, mk in zip(ops, value_ix, mask_ix)]


def moment_sharing(ops, value_ix, mask_ix):
    """-> (out_ix, run, shared) of a launch over these static moments.
    `out_ix`: per moment the index of its array among the distinct
    results the program returns (-1: the row counts it returns anyway);
    `run`: the passes over the rows the program makes, the row count
    among them; `shared`: the passes more that a program making every
    moment's own would run."""
    passes = _moment_passes(ops, value_ix, mask_ix)
    run = len({_ROW_COUNT, *(k for p in passes for k in p)})
    index: Dict[tuple, int] = {}
    out_ix = tuple(-1 if k == _ROW_COUNT else index.setdefault(k, len(index))
                   for k in _result_keys(ops, value_ix, mask_ix))
    return out_ix, run, 1 + sum(map(len, passes)) - run


def moment_results(distinct, counts, ops, value_ix, mask_ix):
    """-> (a launch's results a moment, from the distinct ones its program
    returned; the passes it ran and those it shared)."""
    out_ix, *passes = moment_sharing(ops, value_ix, mask_ix)
    return tuple(counts if i < 0 else distinct[i] for i in out_ix), \
        tuple(passes)


def sorted_grouped_aggregate(gids, mask, ts, values, col_masks=(), *,
                             num_groups, ops, has_col_masks=False,
                             ends=None, seg_len_k=None, starts=None):
    """Host-validating wrapper (mirrors grouped_aggregate; gids sorted).

    At high cardinality the device-side binary search for segment bounds is
    the dominant cost (gather-bound, ~1.2s at 1.2M groups / 25M rows on
    v5e). Callers that know the segment layout pass `ends` (int32
    [num_groups], cumulative row count per group — the LSM scan path has
    run boundaries on the host already); otherwise host gids fall back to a
    bincount, and device gids to the on-device binary search.

    `starts` beside `ends` (both int32 [num_groups]) picks segments out
    of the layout `gids` numbers: group g is rows [starts[g], ends[g]),
    ascending and disjoint, each inside one run of `gids`; a padding group
    has starts == ends. Every [num_groups]-shaped op is then sized by the
    segments asked for (a scan's live runs), not by the layout's.

    What the moments share is computed once (`moment_sharing`): a
    validity of None says the column has no NULL, and its count is the
    row count; moments handed the same validity array, or the same value
    array, are told to the program as one parameter; a value that is `ts`
    itself reads the time index."""
    check_i64_safe(ts, what="sorted_grouped_aggregate ts")
    check_i64_safe(*[v for v in values], what="sorted_grouped_aggregate values")
    if starts is not None and ends is None:
        raise ValueError("starts needs ends")
    if ends is None and num_groups > _SEG_HIGH_CARD_THRESHOLD \
            and isinstance(gids, np.ndarray):
        hist = np.bincount(gids, minlength=num_groups)[:num_groups]
        ends = np.cumsum(hist, dtype=np.int64).astype(np.int32)
    ops = tuple(ops)
    values, value_ix = distinct_arrays(values, ts)
    masks, mask_ix = distinct_arrays(
        col_masks if has_col_masks else (None,) * len(ops), None)
    static = dict(num_groups=num_groups, ops=ops, value_ix=value_ix,
                  mask_ix=mask_ix)
    if ends is not None:
        distinct, counts = _sorted_grouped_aggregate_pre(
            gids, mask, ts, open_window(ts.dtype), values, masks, ends,
            starts, seg_len_k=seg_len_k, **static)
    else:
        distinct, counts = _sorted_grouped_aggregate(
            gids, mask, ts, values, masks, **static)
    return moment_results(distinct, counts, ops, value_ix, mask_ix)[0], counts


@functools.partial(jax.jit,
                   static_argnames=("num_groups", "ops", "value_ix",
                                    "mask_ix", "seg_len_k"))
def _sorted_grouped_aggregate_pre(gids, mask, ts, window, values, col_masks,
                                  ends, starts=None, *, num_groups, ops,
                                  value_ix, mask_ix, seg_len_k=None):
    """_sorted_grouped_aggregate with host-precomputed segment ends, and
    `starts` where the segments are not the dense layout's.

    `window`: (lo, hi), two traced scalars of `ts`'s dtype: a row counts
    where `mask` holds and lo <= ts <= hi. A statement's time range
    reaches the program so, as values and never as shapes: one program a
    statement shape whatever the range, and no row mask made of it on the
    host (`open_window`: the range that keeps every row).

    seg_len_k (static): ceil-log2 of the longest segment, bucketized by
    the caller — enables the shift-doubling min/max + first/last kernels
    at high cardinality. Callers must only pass it when `gids` holds
    REAL run ids (the scan path ships a dummy when no op needs them).
    """
    lo, hi = window
    mask = mask & (ts >= lo) & (ts <= hi)
    ends = jnp.asarray(ends)
    dense = starts is None
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32), ends[:-1]]) \
        if dense else jnp.asarray(starts)
    bs, be, has_inner = _block_cover(starts, ends)
    return _sga_body(gids, mask, ts, values, col_masks, starts, ends, bs,
                     be, has_inner, num_groups=num_groups, ops=ops,
                     value_ix=value_ix, mask_ix=mask_ix, seg_len_k=seg_len_k,
                     dense=dense)


@functools.partial(jax.jit,
                   static_argnames=("num_groups", "ops", "value_ix",
                                    "mask_ix"))
def _sorted_grouped_aggregate(gids, mask, ts, values, col_masks=(), *,
                              num_groups, ops, value_ix, mask_ix):
    """grouped_aggregate twin requiring non-decreasing gids (the natural
    order of merged LSM scans). Same semantics, scatter-free execution.

    Masked-out rows stay in place (their gid keeps the array sorted) and
    contribute the identity. first/last pick the row with the extreme ts
    (position breaks ties), matching the scatter twin's semantics even when
    ts is not sorted within a segment."""
    n = gids.shape[0]
    starts, ends, bs, be, has_inner = _segment_bounds(gids, num_groups, n)
    return _sga_body(gids, mask, ts, values, col_masks, starts, ends, bs,
                     be, has_inner, num_groups=num_groups, ops=ops,
                     value_ix=value_ix, mask_ix=mask_ix)


def _sga_body(gids, mask, ts, values, col_masks, starts, ends, bs, be,
              has_inner, *, num_groups, ops, value_ix, mask_ix,
              seg_len_k=None, dense=True):
    """-> (the distinct results in `moment_sharing`'s order, the row
    counts). `values` / `col_masks`: the launch's distinct arrays, which
    `value_ix` / `mask_ix` (static, a moment each) index.

    `dense`: segment g starts where g - 1 ends and `gids` numbers the
    segments. Otherwise the segments are picked out of the layout `gids`
    numbers: the shift-doubling kernels still guard their passes with
    `gids` and pick up at `starts`; what would index a per-segment result
    by `gids` takes the form that reads the bounds alone."""
    use_doubling = seg_len_k is not None and \
        num_groups > _SEG_HIGH_CARD_THRESHOLD
    n = gids.shape[0]
    cache = {}

    def once(key, make):
        if key not in cache:
            cache[key] = make()
        return cache[key]

    def seg_sum(x):
        return _sorted_seg_sum(
            x, starts, ends, bs, be, has_inner, n, dense,
            once("sum_bounds", lambda: _sum_bounds(starts, ends, dense)))

    def column(v):
        return ts if v < 0 else values[v]

    def rows(mk):
        """The rows a moment under validity `mk` reads."""
        return mask if mk < 0 else \
            once(("rows", mk), lambda: mask & col_masks[mk])

    def centred(v, mk):
        # Shifted one-pass moments (see the scatter twin): center on
        # the global mean before squaring — avoids int wraparound and
        # f32 cancellation on large, tight value distributions.
        col, m = column(v), rows(mk)
        colf = col.astype(jnp.promote_types(col.dtype, jnp.float32))
        gc = jnp.maximum(jnp.sum(run(("count", mk))), 1)
        shift = jnp.sum(jnp.where(m, colf, 0.0)) / gc
        return jnp.where(m, colf - shift, 0.0)

    def argext(is_min, mk):
        # arg-extreme by (ts, position) — same semantics as the scatter
        # twin even when ts is unsorted within a segment
        ident = _max_ident(ts.dtype) if is_min else _min_ident(ts.dtype)
        key = jnp.where(rows(mk), ts, ident)
        if use_doubling:
            return _seg_argext_doubling(key, gids, starts, ends, ident,
                                        is_min=is_min, k_max=seg_len_k)
        return _sorted_seg_argext(key, starts, ends, bs, be, has_inner, n,
                                  is_min=is_min,
                                  gids=gids if dense else None)

    def minmax(is_min, v, mk):
        col = column(v)
        ident = _max_ident(col.dtype) if is_min else _min_ident(col.dtype)
        filled = jnp.where(rows(mk), col, ident)
        if use_doubling:
            return _seg_minmax_doubling(filled, gids, starts, ends, ident,
                                        is_min=is_min, k_max=seg_len_k)
        return _sorted_seg_minmax(filled, starts, ends, bs, be, has_inner,
                                  n, is_min=is_min)

    def compute(kind, *of):
        if kind == "count":
            return seg_sum(rows(*of))
        if kind == "argext":
            return argext(*of)
        if kind in ("min", "max"):
            return minmax(kind == "min", *of)
        if kind in ("dev", "dev_sq"):
            d = once(("centred",) + of, lambda: centred(*of))
            return seg_sum(d if kind == "dev" else d * d)
        v, mk = of
        col, m = column(v), rows(mk)
        if kind == "sum":
            return seg_sum(jnp.where(m, col, 0))
        if kind == "sum_sq":
            # partial moment for distributed/merged stddev computation;
            # square in float: col*col wraps int columns past ~46k
            colf = col.astype(jnp.promote_types(col.dtype, jnp.float32))
            return seg_sum(jnp.where(m, colf * colf, 0))
        # growth: rows of a segment lie in time order here (one series a
        # segment), and the caller ships real run ids with seg_len_k
        if seg_len_k is None:
            raise ValueError("growth needs run ids and seg_len_k")
        return _seg_growth_doubling(col, m, gids, starts, ends,
                                    k_max=seg_len_k)

    def run(key):
        return once(key, lambda: compute(*key))

    counts = run(_ROW_COUNT).astype(jnp.int32)
    results = {}
    for op, v, passes, rk in zip(ops, value_ix,
                                 _moment_passes(ops, value_ix, mask_ix),
                                 _result_keys(ops, value_ix, mask_ix)):
        if rk == _ROW_COUNT or rk in results:
            continue
        p = [run(k) for k in passes]
        fdt = column(v).dtype
        if op == "count":
            r = p[0].astype(jnp.int32)
        elif op == "sum":
            r = p[0].astype(fdt)
        elif op == "avg":
            s, c = p
            r = jnp.where(c > 0, s / jnp.maximum(c, 1), jnp.nan)
        elif op in ("stddev", "variance"):
            c, s, sq = p
            cc = jnp.maximum(c, 1)
            # sample variance (ddof=1, DataFusion convention); <2 rows → NaN
            var = jnp.maximum(sq - (s / cc) * s, 0.0) / jnp.maximum(c - 1, 1)
            var = jnp.where(c >= 2, var, jnp.nan)
            r = jnp.sqrt(var) if op == "stddev" else var
        elif op in ("first", "last"):
            ext_t, pos = p[0]
            ident = _max_ident(ts.dtype) if op == "first" \
                else _min_ident(ts.dtype)
            found = (ext_t != ident) & (pos >= 0)
            val = column(v)[jnp.clip(pos, 0, n - 1)]
            empty = jnp.nan if jnp.issubdtype(fdt, jnp.floating) \
                else jnp.zeros((), fdt)
            r = jnp.where(found, val, empty)
        elif passes[0][0] == "argext":      # a time extreme beside it
            r = p[0][0]
        else:                               # sum_sq, min, max, growth
            r = p[0]
        results[rk] = r
    return tuple(results.values()), counts
