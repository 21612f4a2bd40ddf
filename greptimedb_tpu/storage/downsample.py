"""Downsampling: aggregate a region's rows into coarser time buckets.

The north-star maintenance job (BASELINE config 5: 1s→1m downsample).
The reference has no downsample in v0.2 — its compaction only merges
files — so this is a capability extension: a background job that reduces
every (series, bucket) group with the scatter-free sorted-segment TPU
kernel and writes the result into a destination whose time index carries
the bucket timestamps. The continuous-flow subsystem (flow/manager.py)
drives the same reducer incrementally from a per-flow watermark.

TPU-first data flow: the job rides the SAME device-resident merged-scan
cache the query path uses (`storage/scan_cache.py:SCAN_CACHE`) — on a region
that has been queried (or downsampled) before, the sorted/deduped column
arrays are already in HBM and the job ships only the run ids; on a cold
region the cache build it pays is then amortized by every later query.
All device work is dispatched asynchronously and fetched in ONE batched
device_get, so host-side prep for the destination write overlaps the
kernel execution instead of serializing behind it.
"""

from __future__ import annotations

import logging
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from . import scan_cache

logger = logging.getLogger(__name__)

_SUPPORTED = ("avg", "sum", "min", "max", "count", "first", "last")

#: one output column: (destination column name, op, source field or None).
#: A None source means count-rows — the op must be "count" (count(*)).
AggSpec = Tuple[str, str, Optional[str]]


def _normalize_aggs(src_schema, aggs: Union[None, Dict[str, str],
                                            Sequence[AggSpec]]
                    ) -> List[AggSpec]:
    """Accept the legacy field→op dict (dest column = field name) or the
    flow-style (dest, op, src) triples; default to avg of every numeric
    field."""
    if aggs is None:
        fields = [c.name for c in src_schema.field_columns()
                  if not src_schema.column_schema(c.name).dtype.is_string]
        return [(f, "avg", f) for f in fields]
    if isinstance(aggs, dict):
        return [(f, op, f) for f, op in aggs.items()]
    return [tuple(a) for a in aggs]


def downsample_region(src, dst, *, stride_ms: int,
                      aggs: Union[None, Dict[str, str],
                                  Sequence[AggSpec]] = None,
                      time_range=None, origin_ms: int = 0) -> int:
    """Aggregate `src` rows into `stride_ms` buckets and write to `dst`.

    `dst` may be a Region (direct WriteBatch) or a Table — a partitioned
    table routes destination rows through its partition rule
    (partition/splitter.py), so multi-region rollup tables work.
    Re-running over an already-folded window is idempotent: bucket rows
    carry the same (tags, bucket_ts) key, so MVCC dedup keeps the newest
    fold. Returns the number of bucket rows written."""
    import jax

    from ..ops.kernels import shape_bucket, sorted_grouped_aggregate
    from .write_batch import WriteBatch

    schema = src.schema
    agg_specs = _normalize_aggs(schema, aggs)
    for dest, op, col in agg_specs:
        if op not in _SUPPORTED:
            raise ValueError(f"unsupported downsample op {op}")
        if col is None and op != "count":
            raise ValueError(f"{op} needs a source column")

    # merged + MVCC-deduped view, sorted by (series, ts); PUT rows only
    # (tombstones are dropped by the merge). Device mirrors of ts/fields
    # are cached per region version and shared with the query path.
    scan = scan_cache.SCAN_CACHE.get(src)
    n = scan.num_rows
    if n == 0:
        return 0
    sids, ts = scan.series_ids, scan.ts

    mask_np = None
    if time_range is not None:
        mask_np = np.ones(n, dtype=bool)
        if time_range.start is not None:
            mask_np &= ts >= time_range.start
        if time_range.end is not None:
            mask_np &= ts < time_range.end
        if not mask_np.any():
            return 0

    # run ids over (series, bucket): rows are sorted by (series, ts) so
    # pair changes are run boundaries — vectorized host pass, and the
    # segment ends ship with the call (no device binary search)
    buckets = (ts - origin_ms) // stride_ms
    flags = np.empty(n, dtype=bool)
    flags[0] = True
    np.not_equal(sids[1:], sids[:-1], out=flags[1:])
    flags[1:] |= buckets[1:] != buckets[:-1]
    run_starts = np.nonzero(flags)[0]
    nruns = len(run_starts)

    nbucket = shape_bucket(nruns, minimum=256)
    d_mask = jax.device_put(mask_np) if mask_np is not None \
        else scan.device_valid_all()
    d_ts = scan.device_ts()
    # with host-precomputed ends and no `seg_len_k` every op works off the
    # segment bounds (`extreme_form`: `rows`), so no run id a row is made
    # or uploaded and ts stands in for shape

    values, col_masks, ops, slots = [], [], [], []
    for dest, op, col in agg_specs:
        if col is None:
            values.append(d_ts)            # count(*): mask-only reduce
            col_masks.append(None)
        else:
            values.append(d_ts if op == "count"
                          else scan.device_field(col))
            col_masks.append(scan.device_valid(col))
        ops.append(op)
        slots.append(dest)

    run_ends = np.full(nbucket, n, dtype=np.int32)
    run_ends[:nruns - 1] = run_starts[1:]
    results, counts = sorted_grouped_aggregate(
        d_ts, d_mask, d_ts, tuple(values), tuple(col_masks),
        num_groups=nbucket, ops=tuple(ops), has_col_masks=True,
        ends=run_ends)

    # host prep for the destination write runs while the device computes
    # (dispatch above is async); the single batched fetch below is the
    # only synchronization point
    out_sids = sids[run_starts]
    out_ts = buckets[run_starts] * stride_ms + origin_ms
    counts, results = jax.device_get((counts, list(results)))
    counts = counts[:nruns]
    live = counts > 0
    out_sids, out_ts = out_sids[live], out_ts[live]

    cols: Dict[str, list] = {}
    sd = src.series_dict
    for i, tag in enumerate(sd.tag_names):
        cols[tag] = sd.decode_tag_column(out_sids, i)
    ts_name = dst.schema.timestamp_column.name
    cols[ts_name] = out_ts
    for dest, res in zip(slots, results):
        vals = np.asarray(res)[:nruns][live].astype(np.float64)
        nan = np.isnan(vals)
        cols[dest] = vals if not nan.any() else \
            [None if m else float(v) for v, m in zip(vals, nan)]

    n_out = len(out_ts)
    if n_out == 0:
        return 0
    if hasattr(dst, "regions"):
        # table destination: insert() splits rows per the partition rule
        dst.insert(cols)
    else:
        wb = WriteBatch(dst.schema)
        wb.put(cols)
        dst.write(wb)
    logger.info("downsampled %s -> %s: %d rows into %d buckets (stride %dms)",
                src.name, getattr(dst, "name", dst.info.name
                                  if hasattr(dst, "info") else "?"),
                n, n_out, stride_ms)
    return n_out
