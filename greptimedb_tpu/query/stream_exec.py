"""Block-streamed cold scan: aggregate regions too large to cache in HBM.

The cached fast path (storage/scan_cache.py) materializes a region's merged
scan in host memory with device-resident mirrors — right for hot regions
that fit, impossible for regions larger than device (or host) memory.
This module streams instead:

1. The region's key domain is partitioned into contiguous slices sized
   by parquet row-group statistics (a row-budget per slice). The
   partition axis adapts to the file layout: short-window flush files
   slice on TIME (their row-group time stats are tight); compacted or
   long-window files slice on SERIES ID — the leading storage sort key,
   whose row-group stats are tight on every layout (_pick_slice_dim).
2. Each slice is read with row-group pruning (memtables + SSTs clipped to
   the slice range), then merged and MVCC-deduped *exactly*: a
   (series, ts) key lives in exactly one slice on either axis, so
   slice-local dedup — the same sort-based kernel the cached path uses —
   is globally exact, including overwrites and tombstones across SSTs.
3. Each slice reduces to a partial moment frame on the device (padded to
   shape buckets so XLA compiles once, not once per slice), and
   moment_fold._finalize folds the partials — the same decomposable-moment
   algebra that already merges partials across regions and datanodes.
4. Host decode of slice i+1 overlaps device compute of slice i (a
   one-deep prefetch pipeline; parquet decode drops the GIL).

Reference behavior: src/storage/src/chunk.rs:35-218 (streamed merge
reader) and src/storage/src/sst/parquet.rs:217-330 (row-group readers);
SURVEY §7 hard part #3 (overlapped Parquet-decode + H2D streaming).
"""

from __future__ import annotations

import time as _time
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq

from ..common import exec_stats, failpoint, process_list
from ..common.failpoint import register as _fp_register
from ..common.runtime import parallel_map, transient_executor
from ..common.telemetry import increment_counter, propagate, span
from ..common.time import TimestampRange
from ..datatypes import Vector
from ..errors import UnsupportedError
from ..ops.kernels import OP_PUT, merge_dedup_numpy, shape_bucket
from ..storage.index import prune_files, sst_index_enabled
from ..storage.region import ScanProfile
from ..storage.scan_cache import MergedScan, run_diffs
from . import moment_fold, scan_full
from .agg_plan import (RUN_DIFF_MOMENT_OPS, SKETCH_MOMENT_OPS, moment_input,
                       plan_needs_host, plan_scan_columns, sketch_run_column)
from .expr import Evaluator
from .planner import _group_slot

# per-slice boundary of the streamed cold scan: delay(ms) makes a scan
# deterministically slow for the KILL-cancellation tests
_fp_register("stream_slice")

#: stream (instead of caching) any region estimated above this many rows
_STREAM_THRESHOLD_ROWS = [64_000_000]
#: target rows per streamed slice (soft: slices track row-group edges)
_SLICE_ROWS = [16_000_000]
#: row-count shape bucket floor, so nearby slice sizes share one compile
_ROW_BUCKET_MIN = 1 << 20
#: where a cold slice's one-pass partial reduction runs. "host": a
#: vectorized reduceat over the just-decoded columns — cold scans are
#: decode/link-bound, and a single streaming pass belongs where the bytes
#: already are (shipping n rows over the device link to produce nruns
#: partials is a losing trade at host↔device bandwidths; the resident
#: warm path keeps the TPU, where reuse amortizes the transfer).
#: "device": launch the moment kernel per slice (right when the link is
#: wide, e.g. co-located accelerators).
_COLD_REDUCE = ["host"]


def configure_streaming(threshold_rows: Optional[int] = None,
                        slice_rows: Optional[int] = None,
                        cold_reduce: Optional[str] = None) -> None:
    """Tune the cold-scan streaming knobs (TOML [query] section)."""
    if threshold_rows is not None:
        _STREAM_THRESHOLD_ROWS[0] = int(threshold_rows)
    if slice_rows is not None:
        _SLICE_ROWS[0] = int(slice_rows)
    if cold_reduce is not None:
        if cold_reduce not in ("host", "device"):
            raise ValueError(f"cold_reduce {cold_reduce!r}")
        _COLD_REDUCE[0] = cold_reduce


def stream_threshold_rows() -> int:
    return _STREAM_THRESHOLD_ROWS[0]


def region_estimated_rows(region) -> int:
    """Upper-bound row estimate from memtable counters + SST metas."""
    vc = getattr(region, "version_control", None)
    if vc is None:
        return 0
    v = vc.current
    total = 0
    for mt in v.memtables.all_memtables():
        total += mt.num_rows
    for meta in v.ssts.all_files():
        total += meta.num_rows
    return total


def region_estimated_bytes(region) -> int:
    """Estimated DECODED residency of a fully-cached scan: rows × the
    schema's in-memory row width (ts + sid + every field column and its
    validity). Parquet file sizes understate this badly — compression
    plus column pruning hide the real host+HBM footprint — and the
    streaming threshold exists to protect residency, so it must be
    measured in the same units as the scan-cache budget."""
    vc = getattr(region, "version_control", None)
    if vc is None:
        return 0
    schema = vc.current.schema
    width = 12                        # int64 ts + int32 sid
    for c in schema.field_columns():
        np_dtype = c.dtype.np_dtype
        width += (np.dtype(np_dtype).itemsize
                  if np_dtype is not None else 16) + 1
    return region_estimated_rows(region) * width


def region_time_span(region) -> int:
    """Inclusive width of a region's time domain in its native unit,
    from SST metas + memtable counters alone (no reads) — the bucket-
    count input of the cost-based scatter planner."""
    vc = getattr(region, "version_control", None)
    if vc is None:
        return 0
    lo = hi = None
    v = vc.current
    for meta in v.ssts.all_files():
        flo, fhi = meta.time_range
        lo = flo if lo is None else min(lo, flo)
        hi = fhi if hi is None else max(hi, fhi)
    for mt in v.memtables.all_memtables():
        ms = mt.snapshot()
        if ms.num_rows:
            lo = int(ms.ts.min()) if lo is None \
                else min(lo, int(ms.ts.min()))
            hi = int(ms.ts.max()) if hi is None \
                else max(hi, int(ms.ts.max()))
    return 0 if lo is None else int(hi - lo + 1)


def region_stat_entries(regions) -> tuple:
    """(per-region stat dicts, total_rows, total_bytes) for an iterable
    of Region objects — the ONE builder behind both the datanode
    heartbeat's DatanodeStat.region_stats and the standalone
    cluster_info row, so the two views of region heat cannot diverge.
    `series` (series-dict count) and `time_span` ride along so the
    frontend's cost-based scatter planner can estimate result
    cardinality for REMOTE datanodes from the heartbeat alone."""
    entries, total_rows, total_bytes = [], 0, 0
    for region in sorted(regions, key=lambda r: r.name):
        rows = int(region_estimated_rows(region))
        size = int(region_estimated_bytes(region))
        sd = getattr(region, "series_dict", None)
        total_rows += rows
        total_bytes += size
        entry = {"region": region.name, "rows": rows,
                 "size_bytes": size,
                 "series": int(getattr(sd, "num_series", 0) or 0),
                 "time_span": region_time_span(region)}
        # replication feed: followers beat their applied position,
        # leaders their acked frontier — meta derives per-replica lag
        # (region_peers) and picks the promotion winner from these
        vc = getattr(region, "version_control", None)
        committed = int(vc.committed_sequence) if vc is not None else 0
        if getattr(region, "standby", False):
            entry["standby"] = True
            entry["replicated_seq"] = committed
        else:
            entry["committed_seq"] = committed
        entries.append(entry)
    return entries, total_rows, total_bytes


def _plan_slices(stats: List[Tuple[int, int, int]], budget: int,
                 clip_lo: Optional[int], clip_hi: Optional[int]
                 ) -> List[Tuple[int, int]]:
    """Choose contiguous half-open time slices [t0, t1) covering every row.

    `stats` are (min_ts, max_ts_inclusive, rows) per storage chunk (parquet
    row group or memtable). Two kinds of cuts, both on chunk edges:

    - *clean breaks*: gaps where no chunk spans the boundary. A slice cut
      there covers whole sorted runs, so the reader takes the no-sort
      no-mask path — the dominant cold-scan cost is the host merge sort,
      and flush SSTs are time-disjoint, so most LSM layouts split fully
      into merge-free slices. Only taken once a slice has accumulated
      enough rows to amortize its kernel launch + padding.
    - *budget cuts*: inside an overlapping run of chunks, accumulate to
      the row budget (those slices still merge-sort, but stay bounded).

    Slices are exact partitions of the time domain regardless of cut
    quality; the stats only balance sizes.
    """
    clipped = []
    for lo, hi, rows in stats:
        if clip_lo is not None and hi < clip_lo:
            continue
        if clip_hi is not None and lo >= clip_hi:
            continue
        clipped.append((lo, hi, rows))
    if not clipped:
        return []
    tmin = min(lo for lo, _, _ in clipped)
    tmax = max(hi for _, hi, _ in clipped)
    if clip_lo is not None:
        tmin = max(tmin, clip_lo)
    if clip_hi is not None:
        tmax = min(tmax, clip_hi - 1)
    if tmin > tmax:
        return []
    # connected components of overlapping chunks: (lo, hi, rows, chunks)
    comps: List[list] = []
    for lo, hi, rows in sorted(clipped, key=lambda s: (s[0], s[1])):
        if comps and lo <= comps[-1][1]:
            c = comps[-1]
            c[1] = max(c[1], hi)
            c[2] += rows
            c[3].append((lo, hi, rows))
        else:
            comps.append([lo, hi, rows, [(lo, hi, rows)]])

    min_clean = max(_ROW_BUCKET_MIN, budget // 8)
    cuts: set = set()
    acc = 0
    prev_hi: Optional[int] = None
    for clo, chi, crows, chunks in comps:
        # close the running slice at the gap when it is big enough to
        # deserve its own launch, when adding the next component would
        # bust the row budget, or when a budget-busting component
        # follows (its internal cuts must not bleed into neighbors)
        if prev_hi is not None and acc and (acc >= min_clean
                                            or acc + crows > budget
                                            or crows > budget):
            cuts.add(prev_hi + 1)
            acc = 0
        if crows > budget:
            # oversized overlapping pile: budget cuts inside it (those
            # slices pay the merge sort, but stay bounded)
            inner = 0
            for lo, hi, rows in sorted(chunks, key=lambda s: (s[1], s[0])):
                inner += rows
                if inner >= budget and hi < chi:
                    cuts.add(hi + 1)
                    inner = 0
            acc = budget            # force a cut before whatever follows
        else:
            acc += crows
        prev_hi = chi
    bounds = [tmin] + sorted(c for c in cuts if tmin < c <= tmax) \
        + [tmax + 1]
    return [(bounds[i], bounds[i + 1]) for i in range(len(bounds) - 1)
            if bounds[i] < bounds[i + 1]]


def _region_slice_stats(region, snap, unit
                        ) -> List[Tuple[int, int, int, int, int]]:
    """(min_ts, max_ts, min_sid, max_sid, rows) per chunk: SST row
    groups + memtables."""
    v = snap._version
    stats: List[Tuple[int, int, int, int, int]] = []
    for meta in v.ssts.all_files():
        rg = region.access_layer.row_group_stats(meta)
        if rg:
            stats.extend(rg)
        else:  # no stats: the whole file is one chunk
            lo, hi = meta.time_range
            stats.append((lo, hi, 0, 1 << 30, meta.num_rows))
    for mt in v.memtables.all_memtables():
        ms = mt.snapshot()
        if ms.num_rows:
            stats.append((int(ms.ts.min()), int(ms.ts.max()),
                          int(ms.series_ids.min()),
                          int(ms.series_ids.max()), ms.num_rows))
    return stats


def _plan_jobs(stats: List[Tuple[int, int, int, int, int]], budget: int,
               time_lo: Optional[int], time_hi: Optional[int], unit
               ) -> List[Tuple[str, int, int, Optional[TimestampRange]]]:
    """Per-component hybrid slice plan: (dim, lo, hi, time_clip) jobs.

    Merge-freedom beats pruning tightness — the cold scan's dominant
    host cost is the (sid, ts) merge sort, which vanishes when a slice
    covers whole sorted runs. So:

    - chains of budget-sized time-disjoint components (in-order flushes
      and bulk loads) become TIME slices on their gap boundaries;
    - an oversized overlapping component (a big compacted file, or
      several files covering the same window) is sliced on SERIES id
      within its time range instead: SSTs sort by series first, so
      series row-group stats are tight there, and a series slice of a
      single file is itself one sorted run.
    """
    clipped = []
    for tlo, thi, slo, shi, rows in stats:
        if time_lo is not None and thi < time_lo:
            continue
        if time_hi is not None and tlo >= time_hi:
            continue
        clipped.append((tlo, thi, slo, shi, rows))
    if not clipped:
        return []
    # connected components over time: [lo, hi, rows, chunks]
    comps: List[list] = []
    for ch in sorted(clipped):
        if comps and ch[0] <= comps[-1][1]:
            c = comps[-1]
            c[1] = max(c[1], ch[1])
            c[2] += ch[4]
            c[3].append(ch)
        else:
            comps.append([ch[0], ch[1], ch[4], [ch]])

    def clamp(lo: int, end: int) -> Tuple[int, int]:
        if time_lo is not None:
            lo = max(lo, time_lo)
        if time_hi is not None:
            end = min(end, time_hi)
        return lo, end

    jobs: List[Tuple[str, int, int, Optional[TimestampRange]]] = []
    min_clean = max(_ROW_BUCKET_MIN, budget // 8)
    pend_lo: Optional[int] = None
    pend_rows = 0
    prev_hi: Optional[int] = None

    def flush_pending() -> None:
        nonlocal pend_lo, pend_rows
        if pend_lo is not None:
            lo, end = clamp(pend_lo, prev_hi + 1)
            if lo < end:
                jobs.append(("time", lo, end, None))
        pend_lo = None
        pend_rows = 0

    for clo, chi, crows, chunks in comps:
        if crows > budget:
            flush_pending()
            lo, end = clamp(clo, chi + 1)
            clip = TimestampRange(lo, end, unit)
            sstats = [(c[2], c[3], c[4]) for c in chunks]
            sslices = _plan_slices(sstats, budget, None, None)
            if len(sslices) > 1:
                for slo, shi in sslices:
                    jobs.append(("series", slo, shi, clip))
            else:
                # the series axis cannot subdivide (few series, or every
                # chunk spans the whole sid domain): fall back to time
                # budget cuts — those slices pay the merge sort but stay
                # bounded
                tstats = [(c[0], c[1], c[4]) for c in chunks]
                for tlo2, thi2 in _plan_slices(tstats, budget, lo, end):
                    jobs.append(("time", tlo2, thi2, None))
        else:
            if pend_lo is not None and (pend_rows >= min_clean
                                        or pend_rows + crows > budget):
                flush_pending()
            if pend_lo is None:
                pend_lo = clo
            pend_rows += crows
        prev_hi = chi
    flush_pending()
    return jobs


def _plan_needs_ts(plan) -> bool:
    """Whether the aggregate ever consults row times: time bucketing,
    time filtering, or a moment whose fold is keyed by time."""
    if plan.bucket is not None or plan.time_lo is not None \
            or plan.time_hi is not None:
        return True
    return any(getattr(m, "op", None) in ("min_ts", "max_ts",
                                          "first", "last")
               for m in plan.moments if m.column is not None)


def _slice_lean_proof(snap, dim: str, lo: int, hi: int, unit,
                      time_range: Optional[TimestampRange]
                      ) -> Tuple[bool, bool, list]:
    """(skip_dedup, fully_covered, files) for one slice, from file
    metadata alone.

    skip_dedup: no (series, ts) key in the slice can have two versions —
    every file is dup-free (num_dup_keys == 0) and delete-free, the
    files' key rectangles are pairwise disjoint, and no memtable rows
    exist. Merge dedup then keeps every row, so the per-row key-compare
    pass (and its ts dependency) can be skipped outright. Files from
    before the num_dup_keys upgrade report None and fail the proof.

    fully_covered: every candidate file's time range lies inside the
    slice's clip, so no per-row time mask can trigger — together with
    skip_dedup and a time-blind plan this lets the reader skip decoding
    the ts column entirely (the widest internal column).

    `files` is the slice's candidate file list the proof certified —
    the lean reader must consume exactly this list (re-deriving it
    could drift from what was proven)."""
    v = snap._version
    if any(mt.num_rows for mt in v.memtables.all_memtables()):
        return False, False, []
    if dim == "time":
        clip_lo, clip_hi = lo, hi
        files = v.ssts.files_in_range(TimestampRange(lo, hi, unit))
    else:
        clip_lo = time_range.start if time_range is not None else None
        clip_hi = time_range.end if time_range is not None else None
        files = [f for f in v.ssts.files_in_range(time_range)
                 if f.sid_range is None or
                 (f.sid_range[1] >= lo and f.sid_range[0] < hi)]
    covered = all(
        (clip_lo is None or f.time_range[0] >= clip_lo) and
        (clip_hi is None or f.time_range[1] < clip_hi)
        for f in files)
    for f in files:
        if f.num_dup_keys != 0 or f.num_deletes != 0:
            return False, covered, files
    if len(files) > 64:
        # the pairwise disjointness check is O(F^2); past this bound
        # just decline the proof (the general merge path is always
        # correct) rather than burn seconds of Python before any I/O
        return False, covered, files
    for i in range(len(files)):
        for j in range(i + 1, len(files)):
            if files[i].keys_overlap(files[j]):
                return False, covered, files
    return True, covered, files


class _LeanChunk:
    """Duck-typed ScanData stand-in for one parquet row group: numpy
    views over the arrow buffers (zero-copy for null-free numeric
    columns), just enough surface for _host_partial_frame. seq/op_types
    are 0-stride placeholders — the lean proof guarantees no consumer
    needs MVCC values (dup-free, delete-free slice)."""

    __slots__ = ("series_ids", "ts", "seq", "op_types", "fields")

    def __init__(self, series_ids, ts, fields):
        n = len(series_ids)
        self.series_ids = series_ids
        self.ts = ts
        self.seq = np.broadcast_to(np.int64(0), (n,))
        self.op_types = np.broadcast_to(np.int8(0), (n,))
        self.fields = fields


def _lean_chunk_frames(snap, access, files, dim: str, lo: int, hi: int,
                       needed_fields, plan, sd, need_ts: bool,
                       sid_keys: bool = False,
                       sid_set: Optional[np.ndarray] = None):
    """Decode→reduce fast path for a fully-covered, dedup-free slice:
    stream each SST's row groups as arrow record batches and reduce each
    batch straight into a partial moment frame over zero-copy column
    views. No ScanData assembly, no cross-run concatenation, no
    chunked→contiguous copies — on a two-metric scan those passes cost
    more than the parquet decode itself. Exactness is unchanged: every
    batch is (sid, ts)-sorted, partial frames fold by group key
    downstream (the same algebra that folds slices and regions), and the
    lean proof guarantees no key has competing versions to merge.

    `files` is the list _slice_lean_proof certified for this slice —
    the single source of truth for what belongs to it.

    Returns (frames, rows_read), or None when any precondition fails
    and the caller must take the general scan path."""
    _t0 = _time.perf_counter()
    _rows_read = 0
    _bytes_read = 0
    _reduce_s = 0.0
    schema = snap._version.schema
    ts_name = schema.timestamp_column.name
    if dim == "series":
        # every file must be sid-contained too: row groups of a
        # straddling file would leak rows into the neighbor slice
        if any(f.sid_range is None or f.sid_range[0] < lo or
               f.sid_range[1] >= hi for f in files):
            return None
    sid_idxes = {}
    if sid_set is not None:
        # drop whole certified files (and then row groups) through the
        # index tier: a pruned file's rows would all be masked out by
        # the tag predicates anyway, so the lean proof still holds on
        # the subset
        files = prune_files(access.load_index, files, sid_set)[0]
        for meta in files:
            idx = access.load_index(meta)
            if idx is not None:
                sid_idxes[meta.file_name] = idx
    cols = list(needed_fields) + ["__series_id"]
    if need_ts:
        cols.append(ts_name)
    want_types = {}
    for name in needed_fields:
        cs = schema.column_schema(name)
        if cs.dtype.pa_type is None or cs.dtype.np_dtype is None:
            return None                      # non-numeric moment column
        want_types[name] = cs.dtype.pa_type
    frames = []
    for meta in files:
        key = access._key(meta.file_name)
        path = access.store.local_path(key)
        src = path if path is not None \
            else pa.BufferReader(access.store.read(key))
        pf = pq.ParquetFile(src)
        present = set(pf.schema_arrow.names)
        if any(c not in present for c in cols):
            return None                      # pre-ALTER file: general path
        sidx = sid_idxes.get(meta.file_name)
        # same alignment guard as read_sst: a sidecar whose group count
        # disagrees with the parquet layout (version skew) must degrade
        # to reading every group, never skip the wrong ones
        gk = sidx.row_groups_for(sid_set) \
            if sidx is not None and \
            len(sidx.rg_lo) == pf.metadata.num_row_groups else None
        for g in range(pf.metadata.num_row_groups):
            if gk is not None and not gk[g]:
                continue                     # no candidate sid in group
            # one row group at a time: the decode high-water mark stays
            # one group per prefetch worker, not the whole decoded file,
            # and each group reduces while the next one decodes
            table = pf.read_row_groups([g], columns=cols,
                                       use_threads=True)
            for batch in table.to_batches():
                nb = batch.num_rows
                if nb == 0:
                    continue
                _rows_read += nb
                _bytes_read += batch.nbytes
                data = _lean_batch(batch, schema, needed_fields,
                                   want_types, ts_name, need_ts, nb)
                if data is None:
                    return None
                _tr = _time.perf_counter()
                f = _host_partial_frame(data, None, plan, sd,
                                        sid_keys=sid_keys)
                _reduce_s += _time.perf_counter() - _tr
                if f is not None and len(f):
                    frames.append(f)
    # the lean reader bypasses read_sst, so it reports its own decode
    # stats (same stage names, so EXPLAIN ANALYZE sees one decode line)
    # stream_rows marks these decode rows as the STREAMED share (the
    # resident path's read_sst records plain decode rows too):
    # ExecStats.totals() uses it as the live rows-scanned floor while
    # stream_scan is still unpublished
    exec_stats.record("decode", rows=_rows_read, files=len(files),
                      bytes=_bytes_read, stream_rows=_rows_read,
                      elapsed_s=_time.perf_counter() - _t0 - _reduce_s)
    exec_stats.record("reduce", rows=_rows_read, elapsed_s=_reduce_s)
    return frames, _rows_read


def _lean_batch(batch, schema, needed_fields, want_types, ts_name: str,
                need_ts: bool, nb: int) -> Optional["_LeanChunk"]:
    """numpy views over one record batch; None when a column can't be
    viewed losslessly (unexpected type) and the slice must fall back."""
    names = batch.schema.names
    idx = {nm: i for i, nm in enumerate(names)}
    sid_arr = batch.column(idx["__series_id"])
    sids = np.asarray(sid_arr)
    if need_ts:
        tcol = batch.column(idx[ts_name])
        if pa.types.is_timestamp(tcol.type):
            tcol = tcol.view(pa.int64())     # zero-copy reinterpret
        elif tcol.type != pa.int64():
            return None
        ts = np.asarray(tcol)
    else:
        ts = np.broadcast_to(np.int64(0), (nb,))
    fields = {}
    for name in needed_fields:
        col = batch.column(idx[name])
        if col.type != want_types[name]:
            return None
        if col.null_count:
            vec = Vector.from_arrow(col)
            fields[name] = (vec.data, vec.validity)
        else:
            fields[name] = (np.asarray(col), None)
    return _LeanChunk(sids, ts, fields)


#: moment ops whose partials fold with a plain groupby sum/min/max —
#: first/last need their ts-companion argmin logic and stay label-keyed
_FOLDABLE_OPS = {"sum", "sum_sq", "count", "min", "max", "min_ts", "max_ts"}


def _sid_keyed(plan) -> bool:
    """Whether this region stream can key partials by series id and
    decode tag labels once after the fold, instead of decoding strings
    per batch and folding on object keys."""
    return bool(plan.tag_groups) and all(
        m.column is None or m.op in _FOLDABLE_OPS for m in plan.moments)


def _fold_sid_frames(frames: List[pd.DataFrame], plan, sd
                     ) -> List[pd.DataFrame]:
    """Intra-region fold of __sid-keyed partials (one groupby over dense
    ints — ~3x the speed of the object-string fold), then a single tag
    decode pass over the folded groups. Output frames carry the standard
    label columns, so the cross-region fold is unchanged."""
    df = pd.concat(frames, ignore_index=True) if len(frames) > 1 \
        else frames[0]
    keys = ["__sid"]
    if plan.bucket is not None:
        keys.append(_group_slot(plan.bucket.expr_key))
    aggs = {}
    for m in plan.moments:
        if m.column is None or m.op in ("sum", "sum_sq", "count"):
            aggs[m.slot] = "sum"
        elif m.op in ("min", "min_ts"):
            aggs[m.slot] = "min"
        else:
            aggs[m.slot] = "max"
    aggs["__rowcount"] = "sum"
    folded = df.groupby(keys, sort=False, as_index=False).agg(aggs)
    sids = folded["__sid"].to_numpy().astype(np.int32, copy=False)
    for tg in plan.tag_groups:
        folded[_group_slot(tg.name)] = sd.decode_tag_column(
            sids, tg.tag_index)
    return [folded.drop(columns=["__sid"])]


def _slice_dedup(data) -> Optional[np.ndarray]:
    """Kept-row indices for a slice — or None when EVERY row survives
    (append-only data, the common case), letting the caller skip the
    per-column fancy-index copies entirely.

    Skips the O(n log n) sort when the concatenated runs are already
    (sid, ts, seq)-sorted — true whenever a single SST covers the slice
    — which reduces dedup to a vectorized adjacency scan."""
    s, t, q = data.series_ids, data.ts, data.seq
    n = len(s)
    if n > 1:
        s_up = s[1:] > s[:-1]
        s_eq = s[1:] == s[:-1]
        t_up = t[1:] > t[:-1]
        t_eq = t[1:] == t[:-1]
        sorted_ok = bool(np.all(
            s_up | (s_eq & (t_up | (t_eq & (q[1:] >= q[:-1]))))))
        if sorted_ok:
            dup = s_eq & t_eq
            deletes = data.op_types != OP_PUT
            if not dup.any() and not deletes.any():
                return None                  # keep everything, zero copies
            nxt_same = np.concatenate([dup, [False]])
            keep = ~nxt_same & ~deletes
            return np.nonzero(keep)[0]
    return merge_dedup_numpy(s, t, q, data.op_types)


def _host_partial_frame(data, kept: Optional[np.ndarray], plan, sd,
                        sid_keys: bool = False
                        ) -> Optional[pd.DataFrame]:
    """One-pass vectorized host reduction of a sorted slice into the
    same partial moment frame shape `moment_fold._collect_moment_frame`
    emits, so `_finalize` folds host and device partials identically.

    Everything is segment arithmetic over the (sid [, bucket]) run
    boundaries: `np.<ufunc>.reduceat` per moment, masks folded into the
    identity element. Runs are (sid, ts)-sorted, so first/last reduce to
    the min/max valid row index per run."""
    sids, ts = data.series_ids, data.ts
    fields = data.fields
    n = len(ts)
    if n == 0:
        return None

    # ---- base row mask (dedup + tag predicates + time/field filters) ----
    mask: Optional[np.ndarray] = None

    def and_mask(m: np.ndarray) -> None:
        nonlocal mask
        mask = m if mask is None else mask & m

    if kept is not None:
        if len(kept) > 1 and not bool(np.all(kept[1:] > kept[:-1])):
            # fallback merge-dedup: `kept` is in (sid, ts) SORT order, so
            # the arrays must be gathered before run detection — a keep
            # mask over the unsorted input would group nothing
            sids = sids[kept]
            ts = ts[kept]
            fields = {nm: (d[kept], vd[kept] if vd is not None else None)
                      for nm, (d, vd) in fields.items()}
            n = len(ts)
        else:
            km = np.zeros(n, dtype=bool)
            km[kept] = True
            and_mask(km)
    if plan.tag_predicates:
        S = sd.num_series
        tag_cols = {}
        for i, tname in enumerate(sd.tag_names):
            tag_cols[tname] = sd.decode_tag_column(
                np.arange(S, dtype=np.int32), i)
        sdf = pd.DataFrame(tag_cols)
        ev = Evaluator(sdf)
        smask = np.ones(S, dtype=bool)
        for p in plan.tag_predicates:
            m = ev.eval(p)
            m = m.fillna(False).astype(bool).to_numpy() \
                if isinstance(m, pd.Series) else np.full(S, bool(m))
            smask &= m
        if not smask.any():
            return None
        and_mask(smask[sids])
    if plan.time_lo is not None:
        and_mask(ts >= plan.time_lo)
    if plan.time_hi is not None:
        and_mask(ts < plan.time_hi)
    for ff in plan.field_filters:
        vals, valid = fields[ff.column]
        if vals.dtype == object:
            raise UnsupportedError(f"filter on non-numeric {ff.column}")
        v = vals.astype(np.float64, copy=False)
        cmp = {"eq": v == ff.value, "ne": v != ff.value,
               "lt": v < ff.value, "le": v <= ff.value,
               "gt": v > ff.value, "ge": v >= ff.value}[ff.op]
        if valid is not None:
            cmp &= valid
        and_mask(cmp)
    if mask is not None and not mask.any():
        return None

    # ---- run boundaries over (sid [, bucket]) ----
    buckets = None
    if plan.bucket is not None:
        b = plan.bucket
        buckets = (ts - b.origin) // b.stride_ms
        flags = np.empty(n, dtype=bool)
        flags[0] = True
        np.not_equal(sids[1:], sids[:-1], out=flags[1:])
        flags[1:] |= buckets[1:] != buckets[:-1]
        starts = np.nonzero(flags)[0]
    elif plan.tag_groups:
        flags = np.empty(n, dtype=bool)
        flags[0] = True
        np.not_equal(sids[1:], sids[:-1], out=flags[1:])
        starts = np.nonzero(flags)[0]
    else:
        starts = np.zeros(1, dtype=np.int64)
    nruns = len(starts)

    if mask is None:
        counts = np.diff(starts, append=n).astype(np.int64)
    else:
        counts = np.add.reduceat(mask.astype(np.int64), starts)
    live = counts > 0
    if not live.any():
        return None

    f64max = np.finfo(np.float64).max
    i64max = np.iinfo(np.int64).max
    frame: Dict[str, np.ndarray] = {}
    if sid_keys:
        frame["__sid"] = sids[starts]
    else:
        for tg in plan.tag_groups:
            frame[_group_slot(tg.name)] = sd.decode_tag_column(
                sids[starts], tg.tag_index)
    if plan.bucket is not None:
        frame[_group_slot(plan.bucket.expr_key)] = \
            buckets[starts] * plan.bucket.stride_ms + plan.bucket.origin

    arange = None
    mcache: Dict[str, tuple] = {}
    for m in plan.moments:
        if m.column is None:             # plain row count
            frame[m.slot] = counts
            continue
        d, vd = moment_input(m, plan, fields, sids, ts, sd, cache=mcache)
        valid = vd if mask is None else (
            mask if vd is None else (vd & mask))
        if m.op in SKETCH_MOMENT_OPS:
            # per-run encoded sketch partials (distinct set / t-digest):
            # the bytes fold downstream through the codec exactly like
            # numeric moments fold through sums
            frame[m.slot] = sketch_run_column(m.op, d, valid, starts, n)
            continue
        if m.op in ("min_ts", "max_ts"):
            tsv = ts if valid is None else np.where(valid, ts, i64max
                                                    if m.op == "min_ts"
                                                    else -i64max)
            r = (np.minimum if m.op == "min_ts"
                 else np.maximum).reduceat(tsv, starts)
        elif m.op == "count":
            r = counts if valid is None or valid is mask else \
                np.add.reduceat(valid.astype(np.int64), starts)
        elif m.op in ("first", "last"):
            if arange is None:
                arange = np.arange(n, dtype=np.int64)
            if m.op == "first":
                idx = np.minimum.reduceat(
                    arange if valid is None
                    else np.where(valid, arange, n), starts)
                empty = idx >= n
            else:
                idx = np.maximum.reduceat(
                    arange if valid is None
                    else np.where(valid, arange, -1), starts)
                empty = idx < 0
            vals = d[np.clip(idx, 0, n - 1)].astype(np.float64, copy=False)
            if empty.any():
                vals = vals.copy()
                vals[empty] = np.nan
            r = vals
        else:
            dv = d.astype(np.float64, copy=False)
            if m.op == "sum":
                r = np.add.reduceat(
                    dv if valid is None else np.where(valid, dv, 0.0),
                    starts)
            elif m.op == "sum_sq":
                sq = dv * dv
                r = np.add.reduceat(
                    sq if valid is None else np.where(valid, sq, 0.0),
                    starts)
            elif m.op == "min":
                r = np.minimum.reduceat(
                    dv if valid is None else np.where(valid, dv, f64max),
                    starts)
            elif m.op == "max":
                r = np.maximum.reduceat(
                    dv if valid is None else np.where(valid, dv, -f64max),
                    starts)
            elif m.op in RUN_DIFF_MOMENT_OPS:
                # a run's growth: the differences between its adjacent
                # VALID samples, reset-aware for `increase`
                if arange is None:
                    arange = np.arange(n, dtype=np.int64)
                runid = np.repeat(np.arange(nruns, dtype=np.int64),
                                  np.diff(starts, append=n))
                idx = arange if valid is None else np.nonzero(valid)[0]
                grow = np.zeros(n, dtype=np.float64)
                if len(idx) > 1:
                    prev_i, cur_i = idx[:-1], idx[1:]
                    grow[cur_i] = np.where(
                        runid[cur_i] == runid[prev_i],
                        run_diffs(dv[cur_i], dv[prev_i], m.op), 0.0)
                r = np.add.reduceat(grow, starts)
            else:  # pragma: no cover — planner only emits the ops above
                raise UnsupportedError(f"host moment op {m.op!r}")
        frame[m.slot] = r
    frame["__rowcount"] = counts
    df = pd.DataFrame(frame)[live]
    return df if len(df) else None


def _load_slice(snap, dim: str, lo: int, hi: int, unit, needed_fields,
                series_dict, row_bucket_min: int,
                time_range: Optional[TimestampRange],
                plan=None, reduce: str = "device",
                sid_keys: bool = False,
                sid_set: Optional[np.ndarray] = None):
    """Read + merge + dedup one slice; reduce it on the host (returning
    partial moment frames) or prepare it for the device kernel
    (returning a padded transient MergedScan).

    Returns None for an empty slice, else a tagged
    ``(kind, payload, info)`` tuple — kind "frames" (lean chunk-frame
    path), "frame" (host-reduced general path) or "scan" (device
    MergedScan) — where `info` carries the per-slice facts the
    coordinator folds into ExecStats and Region.last_scan_profile
    (rows, lean_slices / merged_slices / dedup_skip_slices).

    `dim` selects the partition axis: "time" slices [lo, hi) on the time
    index, "series" on __series_id (with the query's time filter still
    pruning files and row groups).

    Before reading anything the slice is tested against its file
    metadata (_slice_lean_proof): when no key can have two versions the
    merge-dedup pass is skipped, and when additionally the plan never
    consults row times and every file sits fully inside the slice, the
    ts column is never decoded at all — on two-metric scans that cuts
    the decoded bytes by ~a quarter and the post-decode passes to the
    reduction itself."""
    skip_dedup = covered = False
    lean_files: list = []
    if reduce == "host" and plan is not None:
        skip_dedup, covered, lean_files = _slice_lean_proof(
            snap, dim, lo, hi, unit, time_range)
    need_ts = True
    if skip_dedup:
        need_ts = _plan_needs_ts(plan) or not covered
        if covered:
            lean = _lean_chunk_frames(
                snap, snap._region.access_layer, lean_files, dim, lo, hi,
                needed_fields, plan, series_dict, need_ts,
                sid_keys=sid_keys, sid_set=sid_set)
            if lean is not None:
                frames, rows_read = lean
                return ("frames", frames,
                        {"rows": rows_read, "lean_slices": 1,
                         "dedup_skip_slices": 1})
    if dim == "series":
        data = snap.scan(projection=needed_fields, series_range=(lo, hi),
                         time_range=time_range, sid_set=sid_set,
                         synthetic_seq=True,
                         need_ts=need_ts, need_mvcc=not skip_dedup)
    else:
        data = snap.scan(projection=needed_fields,
                         time_range=TimestampRange(lo, hi, unit),
                         sid_set=sid_set, synthetic_seq=True,
                         need_ts=need_ts, need_mvcc=not skip_dedup)
    if data.num_rows == 0:
        return None
    # the dedup-skip proof guarantees every row survives, but NOT that
    # the concatenated runs are globally (sid, ts)-sorted: two key-
    # disjoint files can share a boundary sid with non-monotonic time
    # across the concat. Decomposable moments are order-free; first/last
    # are POSITIONAL in _host_partial_frame, so they must still go
    # through _slice_dedup's sortedness check (which falls back to the
    # merge sort when the concat is out of order).
    positional = plan is not None and any(
        getattr(m, "op", None) in ("first", "last")
        for m in plan.moments if m.column is not None)
    kept = None if (skip_dedup and not positional) else _slice_dedup(data)
    info = {"rows": data.num_rows,
            "merged_slices": 0 if skip_dedup else 1,
            "dedup_skip_slices": int(skip_dedup)}
    if reduce == "host":
        return ("frame",
                _host_partial_frame(data, kept, plan, series_dict,
                                    sid_keys=sid_keys), info)
    n = data.num_rows if kept is None else len(kept)
    if n == 0:
        return None

    # pad to a shape bucket so every slice shares one XLA compile; padded
    # rows repeat the last (sid, ts) — they extend the final run, stay
    # sorted, and are masked out via valid_rows. take + device-dtype cast
    # + pad fuse into ONE pass per column (each was a full copy).
    x64 = jax.config.jax_enable_x64
    target = shape_bucket(n, minimum=row_bucket_min)

    def prepare(a, dtype=None, pad_fill=None):
        dtype = dtype or a.dtype
        if kept is None and target == n and a.dtype == dtype:
            return a
        out = np.empty(target, dtype)
        if kept is None:
            out[:n] = a
        elif a.dtype == dtype:
            np.take(a, kept, out=out[:n])
        else:
            out[:n] = a[kept]
        if target != n:
            out[n:] = pad_fill if pad_fill is not None else out[n - 1]
        return out

    sids = prepare(data.series_ids, np.int32)
    ts = prepare(data.ts)
    fields = {}
    for name, (d, vd) in data.fields.items():
        if d.dtype == object:
            d2 = d if kept is None else d[kept]
            if target != n:
                d2 = np.concatenate(
                    [d2, np.full(target - n, None, dtype=object)])
        else:
            want = np.float32 if d.dtype == np.float64 and not x64 \
                else d.dtype
            d2 = prepare(d, want)
        v2 = prepare(vd, np.bool_, pad_fill=False) \
            if vd is not None else None
        fields[name] = (d2, v2)
    base = int(ts[:n].min())
    scan = MergedScan(sids, ts, fields, series_dict, base)
    scan.valid_rows = n if target != n else None
    # start the H2D transfers here, on the prefetch thread: device_put is
    # asynchronous, so the copies stream while the next slice decodes and
    # the launch thread stays free for mask/run construction. Only dtypes
    # device_put maps 1:1 are staged — int64 fields keep device_field's
    # narrowing logic; anything else falls back to lazy upload at launch.
    try:
        rel = ts - base
        last = int(rel.max())
        # the slice's newest time, for `scan_launch._last_ts`
        scan.device["__ts_max"] = (base + last,)
        if last < 2 ** 31:
            scan.device["__ts"] = jax.device_put(rel.astype(np.int32))
        for name, (d2, v2) in fields.items():
            if d2.dtype in (np.float32, np.bool_, np.int32) or \
                    (d2.dtype == np.float64 and x64):
                scan.device[f"f:{name}"] = jax.device_put(d2)
            if v2 is not None:
                scan.device[f"v:{name}"] = jax.device_put(v2)
        if target != n:
            pm = np.zeros(target, np.bool_)
            pm[:n] = True
            scan.device["__pad_mask"] = jax.device_put(pm)
    except Exception:  # noqa: BLE001 — staging is an optimization; the
        # host arrays still serve the scan
        increment_counter("stream_device_stage_errors")
        scan.device.clear()
    return ("scan", scan, info)


def stream_region_moment_frames(region, table, plan) -> List[pd.DataFrame]:
    """Partial moment frames for one region via slice streaming.

    Returns the same frame shape tpu_exec._execute_region produces, so
    moment_fold._finalize folds slices exactly like regions.

    Pipelining: XLA dispatch is asynchronous, so each slice's reduction
    is *launched* and left in flight while the next slice decodes on the
    prefetch thread; device results are fetched in ONE round trip at the
    end (a per-slice fetch would block on that slice's kernel and stall
    the pipeline). Only run-level context is kept per launched slice —
    full slice arrays are freed as the pipeline advances.

    Observability: publishes a stage breakdown to
    `region.last_scan_profile` (the scan twin of the ingest profiler)
    and mirrors the same numbers into the active ExecStats collector so
    EXPLAIN ANALYZE, the profile, and the tracing spans agree.
    """
    prof = ScanProfile(path="streamed")
    _t_start = _time.perf_counter()
    snap = region.snapshot()
    schema = snap.schema
    tc = schema.timestamp_column
    unit = tc.dtype.time_unit if tc is not None else None
    stats = _region_slice_stats(region, snap, unit)
    jobs = _plan_jobs(stats, _SLICE_ROWS[0], plan.time_lo, plan.time_hi,
                      unit) if stats else []
    prof.mark("slice_plan", _time.perf_counter() - _t_start)
    prof.bump("slices", len(jobs))
    exec_stats.record("slice_plan", elapsed_s=prof.stages["slice_plan"],
                      slices=len(jobs))
    if not jobs:
        prof.total_s = _time.perf_counter() - _t_start
        region.last_scan_profile = prof
        return []
    needed = plan_scan_columns(plan, schema)
    sd = region.series_dict

    # point/IN tag conjuncts resolve to a candidate sid set so every
    # slice prunes SSTs through their index sidecars before decoding
    # (superset semantics: the per-slice reductions still apply the
    # full predicate set)
    sid_set = None
    if plan.tag_predicates and sd is not None and sd.tag_names:
        if sst_index_enabled():
            from ..mito.engine import sid_candidates_for_filters
            sid_set = sid_candidates_for_filters(sd, sd.tag_names,
                                                 plan.tag_predicates)
            if sid_set is not None and len(sid_set) == 0:
                # the point predicate matches no series of this region
                prof.total_s = _time.perf_counter() - _t_start
                region.last_scan_profile = prof
                return []

    mode = _COLD_REDUCE[0]
    if plan_needs_host(plan) or any(m.op in RUN_DIFF_MOMENT_OPS
                                    for m in plan.moments):
        # sketch / expression moments have no device kernel, and a
        # window's growth across a slice boundary is last - first of the
        # two slices, which f32 mirrors cannot hold: every slice reduces
        # on the host (same partial-frame algebra)
        mode = "host"
    sid_keys = mode == "host" and _sid_keyed(plan)
    launched = []
    frames: List[pd.DataFrame] = []
    # two-deep prefetch: decode slices i+1, i+2 while slice i launches
    # (decode is the cold-path bottleneck; two workers keep parquet
    # threads busy without unbounded slice residency). propagate()
    # carries the trace context + ExecStats collector into the workers.
    depth = 2
    _t_stream = _time.perf_counter()
    load = propagate(_load_slice)
    with span("stream_scan", region=region.name, slices=len(jobs),
              mode=mode), \
            transient_executor(depth, "stream-scan") as pool:
        futs = [pool.submit(load, snap, dim, lo, hi, unit, needed,
                            sd, _ROW_BUCKET_MIN, clip, plan, mode,
                            sid_keys, sid_set)
                for dim, lo, hi, clip in jobs[:depth]]
        try:
            for i in range(len(jobs)):
                # cooperative KILL at the slice boundary: prefetched
                # slices are cancelled in the finally, so a killed scan
                # releases its workers within one slice
                process_list.check_cancelled()
                failpoint.fail_point("stream_slice")
                res = futs[i].result()
                if i + depth < len(jobs):
                    dim, lo, hi, clip = jobs[i + depth]
                    futs.append(pool.submit(
                        load, snap, dim, lo, hi, unit, needed,
                        sd, _ROW_BUCKET_MIN, clip, plan, mode, sid_keys,
                        sid_set))
                futs[i] = None               # free the slice as we go
                if res is None:
                    prof.bump("empty_slices")
                    continue
                kind, payload, info = res
                prof.rows += info.get("rows", 0)
                for k in ("lean_slices", "merged_slices",
                          "dedup_skip_slices"):
                    if info.get(k):
                        prof.bump(k, info[k])
                if kind == "frames":
                    frames.extend(payload)
                    continue
                if kind == "frame":
                    if payload is not None and len(payload):
                        frames.append(payload)
                    continue
                prof.bump("device_slices")
                ln = scan_full._launch_scan_kernel(payload, schema,
                                                    plan)
                if ln is not None:
                    launched.append(ln)
                del payload, res
        finally:
            # a raise (KILL, failed slice) must not leave prefetched
            # slices occupying the pool: unstarted futures cancel now,
            # the `with` shutdown then only waits for the ≤depth running
            for f in futs:
                if f is not None:
                    f.cancel()
    prof.mark("decode_reduce", _time.perf_counter() - _t_stream)
    _publish_stream_stats(prof)
    if sid_keys and frames:
        _t_fold = _time.perf_counter()
        frames = _fold_sid_frames(frames, plan, sd)
        prof.mark("fold", _time.perf_counter() - _t_fold)
        exec_stats.record("fold", elapsed_s=prof.stages["fold"])
    if not launched:
        prof.total_s = _time.perf_counter() - _t_start
        region.last_scan_profile = prof
        return frames
    # overlap the D2H copies: fetch every per-slice array concurrently —
    # a sequential device_get pays the device-link round-trip latency
    # once per array, which dominates for these small partials
    _t_fetch = _time.perf_counter()
    flat: List = []
    for ln in launched:
        flat.append(ln.counts)
        flat.extend(ln.results)
    for arr in flat:
        if hasattr(arr, "copy_to_host_async"):
            try:
                arr.copy_to_host_async()
            except Exception:  # noqa: BLE001 — async staging is optional;
                # the blocking np.asarray below fetches regardless
                increment_counter("stream_async_fetch_errors")
                break
    flat_np = parallel_map(np.asarray, flat,
                           max_workers=min(8, len(flat)))
    fetched = []
    pos = 0
    for ln in launched:
        k = len(ln.results)
        fetched.append((flat_np[pos], flat_np[pos + 1:pos + 1 + k]))
        pos += 1 + k
    for ln, (counts, res_np) in zip(launched, fetched):
        part = moment_fold._collect_moment_frame(ln, plan, counts,
                                                 res_np)
        if part is not None and len(part):
            frames.append(part)
    prof.mark("device_fetch", _time.perf_counter() - _t_fetch)
    exec_stats.record("device_fetch", elapsed_s=prof.stages["device_fetch"])
    prof.total_s = _time.perf_counter() - _t_start
    region.last_scan_profile = prof
    return frames


def _publish_stream_stats(prof) -> None:
    """Mirror a streamed region's profile into the ExecStats collector
    (stream_scan row) and prometheus counters, so EXPLAIN ANALYZE,
    /metrics and Region.last_scan_profile tell one story."""
    exec_stats.record(
        "stream_scan", rows=prof.rows,
        elapsed_s=prof.stages.get("decode_reduce", 0.0),
        **{k: v for k, v in prof.counters.items() if v})
    for k in ("lean_slices", "merged_slices", "dedup_skip_slices"):
        n = prof.counters.get(k, 0)
        if n:
            increment_counter(f"stream_{k}", n)
