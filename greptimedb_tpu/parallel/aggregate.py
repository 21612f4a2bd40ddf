"""Distributed group-by aggregation: psum of per-shard partial moments.

This is the TPU-native replacement for the reference's distributed scan
fan-out + frontend-side merge (src/frontend/src/table.rs:109-156,414-450) —
and an upgrade over it: v0.2 pushes only scans to datanodes and aggregates on
the frontend, while here every device reduces its own rows to per-group
moments and a single `psum`/`pmin`/`pmax` over the mesh finishes the job.

Decomposable moments per op (classic partial-aggregation algebra):
  sum, count           -> psum
  avg                  -> psum(sum), psum(count)
  stddev/variance      -> psum(sum), psum(sum_sq), psum(count)
  min/max              -> pmin/pmax with identity fill
  first/last           -> arg-extreme on (ts, global row index): pmin of the
                          encoded winner index, then a one-hot psum of its value
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.kernels import AGG_OPS, _max_ident, _min_ident, check_i64_safe
from .mesh import ROW_AXES, pad_rows_to_multiple

_BIG_IDX = np.iinfo(np.int32).max


def _partial_aggregate(gids, mask, ts, row_idx, values, col_masks, *,
                       num_groups, ops, has_col_masks, axes):
    """Runs per-shard; reduces over `axes` with XLA collectives.

    Returns (results, counts) replicated across the mesh.
    """
    seg = num_groups + 1  # one scratch group for masked-out rows
    safe_gids = jnp.where(mask, gids, num_groups)

    def agg_mask(i):
        if has_col_masks:
            return mask & col_masks[i]
        return mask

    cache: Dict[Tuple[str, int], jax.Array] = {}

    def g_count(i, m):
        k = ("count", i if has_col_masks else -1)
        if k not in cache:
            local = jax.ops.segment_sum(m.astype(jnp.int32), safe_gids,
                                        num_segments=seg)[:num_groups]
            cache[k] = jax.lax.psum(local, axes)
        return cache[k]

    def g_sum(col, i, m, square=False):
        k = ("sumsq" if square else "sum", i)
        if k not in cache:
            if square:
                # square in float: col*col wraps int columns past ~46k
                colf = col.astype(jnp.promote_types(col.dtype, jnp.float32))
                v, dt = colf * colf, colf.dtype
            else:
                v, dt = col, col.dtype
            local = jax.ops.segment_sum(jnp.where(m, v, 0).astype(dt),
                                        safe_gids, num_segments=seg)[:num_groups]
            cache[k] = jax.lax.psum(local, axes)
        return cache[k]

    counts = g_count(0, mask) if not has_col_masks else jax.lax.psum(
        jax.ops.segment_sum(mask.astype(jnp.int32), safe_gids,
                            num_segments=seg)[:num_groups], axes)

    results = []
    for i, op in enumerate(ops):
        col, m = values[i], agg_mask(i)
        if op == "count":
            results.append(g_count(i, m))
        elif op == "sum":
            results.append(g_sum(col, i, m))
        elif op == "avg":
            s, c = g_sum(col, i, m), g_count(i, m)
            results.append(jnp.where(c > 0, s / jnp.maximum(c, 1), jnp.nan))
        elif op in ("stddev", "variance"):
            # Shifted one-pass moments: center on the GLOBAL (psum'd) mean
            # so every shard shifts identically — avoids int wraparound
            # and f32 cancellation on large, tight value distributions.
            colf = col.astype(jnp.promote_types(col.dtype, jnp.float32))
            c = g_count(i, m)
            gc = jnp.maximum(jax.lax.psum(jnp.sum(jnp.where(m, 1.0, 0.0)),
                                          axes), 1.0)
            shift = jax.lax.psum(jnp.sum(jnp.where(m, colf, 0.0)), axes) / gc
            d = jnp.where(m, colf - shift, 0.0)
            s = jax.lax.psum(jax.ops.segment_sum(
                d, safe_gids, num_segments=seg)[:num_groups], axes)
            sq = jax.lax.psum(jax.ops.segment_sum(
                d * d, safe_gids, num_segments=seg)[:num_groups], axes)
            cc = jnp.maximum(c, 1)
            # sample variance (ddof=1), matching moment_fold._finalize
            var = jnp.maximum(sq - (s / cc) * s, 0.0) / jnp.maximum(c - 1, 1)
            var = jnp.where(c >= 2, var, jnp.nan)
            results.append(jnp.sqrt(var) if op == "stddev" else var)
        elif op == "min":
            local = jax.ops.segment_min(
                jnp.where(m, col, _max_ident(col.dtype)), safe_gids,
                num_segments=seg)[:num_groups]
            results.append(jax.lax.pmin(local, axes))
        elif op == "max":
            local = jax.ops.segment_max(
                jnp.where(m, col, _min_ident(col.dtype)), safe_gids,
                num_segments=seg)[:num_groups]
            results.append(jax.lax.pmax(local, axes))
        elif op in ("first", "last"):
            # Winner = min global row index among rows achieving the global
            # extreme timestamp for the group; exactly one shard contributes.
            if op == "first":
                ext_local = jax.ops.segment_min(
                    jnp.where(m, ts, _max_ident(ts.dtype)), safe_gids,
                    num_segments=seg)
                ext = jax.lax.pmin(ext_local, axes)
            else:
                ext_local = jax.ops.segment_max(
                    jnp.where(m, ts, _min_ident(ts.dtype)), safe_gids,
                    num_segments=seg)
                ext = jax.lax.pmax(ext_local, axes)
            hit = m & (ts == ext[safe_gids])
            win_local = jax.ops.segment_min(
                jnp.where(hit, row_idx, _BIG_IDX), safe_gids,
                num_segments=seg)[:num_groups]
            win = jax.lax.pmin(win_local, axes)
            # one-hot gather of the winning value via psum
            n_local = col.shape[0]
            local_pos = jax.ops.segment_min(
                jnp.where(hit, jnp.arange(n_local, dtype=jnp.int32), n_local),
                safe_gids, num_segments=seg)[:num_groups]
            have = (win_local == win) & (win < _BIG_IDX) & (local_pos < n_local)
            safe_pos = jnp.minimum(local_pos, n_local - 1)
            # exactly one shard contributes, so a native-dtype psum is an
            # exact gather (no float32 round-trip for int/f64 columns)
            contrib = jnp.where(have, col[safe_pos], jnp.zeros((), col.dtype))
            val = jax.lax.psum(contrib, axes)
            empty = jnp.nan if jnp.issubdtype(col.dtype, jnp.floating) else 0
            results.append(jnp.where(win < _BIG_IDX, val, empty))
        else:
            raise ValueError(f"unsupported agg op: {op}")
    return tuple(results), counts


@functools.partial(
    jax.jit,
    static_argnames=("num_groups", "ops", "has_col_masks", "mesh"))
def _dist_agg(gids, mask, ts, row_idx, values, col_masks, *, num_groups, ops,
              has_col_masks, mesh):
    nv = len(values)
    nm = len(col_masks)
    row = P(ROW_AXES)
    in_specs = (row, row, row, row, (row,) * nv, (row,) * nm)
    out_specs = ((P(),) * len(ops), P())
    fn = functools.partial(_partial_aggregate, num_groups=num_groups, ops=ops,
                           has_col_masks=has_col_masks, axes=ROW_AXES)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(
        gids, mask, ts, row_idx, values, col_masks)


def distributed_grouped_aggregate(
    gids: np.ndarray, mask: np.ndarray, ts: np.ndarray,
    values: Sequence[np.ndarray], col_masks: Sequence[np.ndarray] = (), *,
    num_groups: int, ops: Sequence[str], mesh: Mesh,
) -> Tuple[Tuple[jax.Array, ...], jax.Array]:
    """Mesh-sharded twin of ops.kernels.grouped_aggregate.

    Pads rows to a multiple of the mesh size (padding is masked out), shards
    them over both mesh axes, and reduces partial per-group moments with XLA
    collectives. Results/counts come back replicated.
    """
    check_i64_safe(ts, what="distributed_grouped_aggregate ts")
    check_i64_safe(*values, what="distributed_grouped_aggregate values")
    for op in ops:
        if op not in AGG_OPS:
            raise ValueError(f"unsupported agg op: {op}")
    n = int(gids.shape[0])
    total = pad_rows_to_multiple(max(n, mesh.size), mesh.size)

    def pad(a, fill=0):
        a = np.asarray(a)
        if a.shape[0] == total:
            return a
        out = np.full((total,) + a.shape[1:], fill, dtype=a.dtype)
        out[:n] = a
        return out

    gids_p = pad(gids.astype(np.int32))
    mask_p = pad(np.asarray(mask, dtype=bool), False)
    ts_p = pad(ts)
    row_idx = np.arange(total, dtype=np.int32)
    values_p = tuple(pad(v) for v in values)
    masks_p = tuple(pad(np.asarray(m, dtype=bool), False) for m in col_masks)

    shard = NamedSharding(mesh, P(ROW_AXES))
    put = lambda a: jax.device_put(a, shard)
    return _dist_agg(put(gids_p), put(mask_p), put(ts_p), put(row_idx),
                     tuple(put(v) for v in values_p),
                     tuple(put(m) for m in masks_p),
                     num_groups=num_groups, ops=tuple(ops),
                     has_col_masks=bool(masks_p), mesh=mesh)
