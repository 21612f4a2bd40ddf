"""The region executor: which road a region's rows take (device or CPU by
the table's size, `_dispatch_min_rows`; resident, streamed-cold or
indexed-point a region, `local_dispatch_decision`), and the resident road
itself (`_execute_region`). The resident read path, bottom up; a module
imports only modules above it here, at module level:

    storage/scan_cache.py  MergedScan and its mirrors, the tail, the seam
                           (`SCAN_CACHE`; nothing of query/)
    query/agg_plan.py      TpuPlan, its moments, SQL's `plan_for`
    query/scan_launch.py   what every launch shares (reads, segments,
                           window, `_run_program`, `_Launched`)
    query/scan_narrow.py   the launch over the selected series' ranges
    query/scan_full.py     the launch over every row (layouts, row mask)
    query/moment_fold.py   results -> partial frames -> `_finalize`
    query/tpu_exec.py      dispatch, the roads, orchestration

`query/stream_exec.py` (regions too large to be resident) stands beside
this module; `query/ir.py:execute_agg_plan` folds the regions' partials.
`SET` and tests rebind `TPU_DISPATCH_MIN_ROWS`, `_observed_min_dt` and
functions here: readers outside read them through this module."""

from __future__ import annotations

import json
import threading
import time as _time
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np
import pandas as pd

from ..common import exec_stats, process_list
from ..common.locks import TrackedLock
from ..common.telemetry import increment_counter, span
from ..common.time import TimestampRange
from ..common.tracking import tracked_state
from ..errors import StaleRouteError, UnsupportedError
from ..ops.kernels import sum_form
from ..sql.ast import Query
from ..storage import scan_cache
from ..storage.index import sst_index_enabled
from ..storage.region import ScanProfile
from ..storage.scan_cache import (MergedScan, _base_lasts, _make_tail,
                                  _Rows, _series_firsts)
from . import agg_plan, moment_fold, scan_full, scan_narrow, stream_exec
from .agg_plan import (RUN_DIFF_MOMENT_OPS, TpuPlan, plan_needs_host,
                       plan_scan_columns)
from .moment_fold import _fold_runs, _partial_frame, _RunPartial
from .plan_codec import plan_to_dict
from .planner import Analysis
from .scan_launch import (_bucket_phase, _last_ts, _LaunchShape, _outside,
                          _reduce_part, _statement_shape, _untimed_part)

#: Below this many estimated rows the CPU columnar path wins: a device
#: query has a fixed cost (dispatch chain + transfers + result fetch)
#: that a small scan cannot amortize, and the host path keeps float64
#: precision for DOUBLE columns, which the f32 device mirrors cannot.
#: Cost-based dispatch playing the role of DataFusion's physical-plan
#: costing in the reference (src/query/src/datafusion.rs).
TPU_DISPATCH_MIN_ROWS = 131072

#: assumed CPU columnar throughput for break-even estimation (pandas
#: groupby sustains ~8-25 Mrows/s on simple aggregates; be conservative)
_CPU_ROWS_PER_SEC = 15e6
#: fastest observed steady-state device launch (seconds, kernel launch
#: to result fetch) — an upper bound on the per-query fixed cost
_observed_min_dt = [None]


def _dispatch_min_rows() -> int:
    """Latency-adaptive dispatch floor: the static floor, raised to the
    row count the CPU path would get through in the time the fastest
    steady-state device launch of this process took."""
    dt = _observed_min_dt[0]
    if dt is None:
        return TPU_DISPATCH_MIN_ROWS
    return max(TPU_DISPATCH_MIN_ROWS, int(dt * _CPU_ROWS_PER_SEC))


def _note_device_query_time(dt: float) -> None:
    """Feed the adaptive floor one launch-to-fetch time. Callers pass
    steady-state launches only (_Launched.warm): a first launch also
    pays XLA compile, column uploads and the run-boundary sweep, and one
    such reading would raise the floor over every mid-size table — which
    then never reaches the device again to correct it."""
    cur = _observed_min_dt[0]
    if cur is None or dt < cur:
        _observed_min_dt[0] = dt


def _estimated_table_rows(table) -> Optional[int]:
    """Cheap upper-bound row estimate from memtable counters + SST metas —
    no SST reads, no merged-scan build."""
    regions = getattr(table, "regions", None)
    if not regions:
        return None
    total = 0
    for region in regions.values():
        vc = getattr(region, "version_control", None)
        if vc is None:
            return None
        v = vc.current
        for mt in v.memtables.all_memtables():
            total += mt.num_rows
        for meta in v.ssts.all_files():
            total += meta.num_rows
    return total


def cached_table_frame(table) -> Optional[pd.DataFrame]:
    """Columnar pandas frame for the CPU fallback, memoized per region
    version on the merged-scan cache — the fallback otherwise re-reads
    and re-converts the whole table on every query (the role of
    DataFusion's MemTable caching for hot tables). Nulls follow the
    fallback's frame conventions: NaN for numerics, None for objects."""
    regions = getattr(table, "regions", None)
    if not regions:
        return None
    schema = table.schema
    ts_name = schema.timestamp_column.name \
        if schema.timestamp_column is not None else None
    frames = []
    for region in regions.values():
        scan = scan_cache.SCAN_CACHE.get(region)
        df = scan.device.get("__host_df")
        if df is None:
            cols = {}
            sd = scan.series_dict
            for i, tag in enumerate(sd.tag_names):
                cols[tag] = sd.decode_tag_column(scan.series_ids, i)
            if ts_name is not None:
                cols[ts_name] = scan.ts
            for name, (vals, valid) in scan.fields.items():
                if valid is None:
                    cols[name] = vals
                elif vals.dtype == object:
                    arr = vals.copy()
                    arr[~valid] = None
                    cols[name] = arr
                else:
                    arr = vals.astype(np.float64)
                    arr[~valid] = np.nan
                    cols[name] = arr
            # schema column order
            df = pd.DataFrame({n: cols[n] for n in schema.names()
                               if n in cols})
            scan.device["__host_df"] = df
        frames.append(df)
    if not frames:
        return pd.DataFrame()
    return frames[0] if len(frames) == 1 else \
        pd.concat(frames, ignore_index=True)


def try_execute(table, a: Analysis, query: Query) -> Optional[pd.DataFrame]:
    with exec_stats.stage("plan"):
        plan = agg_plan.plan_for(table, a, query)
        if plan is None:
            return None
        if not hasattr(table, "execute_tpu_plan"):
            # Distributed tables always push down (the fallback would
            # pull raw rows over the wire); local tables route small
            # scans to the CPU columnar path, which is faster and
            # float64-exact.
            est = _estimated_table_rows(table)
            if est is not None and est < _dispatch_min_rows():
                exec_stats.set_dispatch(
                    f"cpu-small-scan (est_rows={est} < "
                    f"dispatch_floor={_dispatch_min_rows()})")
                return None
    # the ONE aggregate-node executor all three front ends share
    # (query/ir.py): scatter or local dispatch, then the moment fold
    from .ir import execute_agg_plan
    try:
        return execute_agg_plan(table, plan)
    except UnsupportedError:
        return None


def dispatch_decision_for_pushdown(table, plan) -> str:
    """The ONE aggregate-pushdown dispatch string EXPLAIN (query/engine)
    and execution (try_execute) both print. DistTable exposes
    scatter_describe (regions pruned a/b, fan-out=k); other pushdown
    tables get the generic line."""
    describe = getattr(table, "scatter_describe", None)
    if describe is not None:
        try:
            return describe(plan)
        except Exception:  # noqa: BLE001 — describing must never fail a
            # query; fall through to the generic dispatch line
            increment_counter("explain_describe_errors")
    return "aggregate-pushdown (datanodes reduce, frontend folds)"


def local_dispatch_decision(table, cold=None, regions=None, plan=None,
                            point_sids=None) -> str:
    """The resident / streamed / indexed-point / mixed decision string
    for a local region-backed table — the ONE source both EXPLAIN
    (query/engine.py) and execution (region_moment_frames → ExecStats)
    print, so the two views cannot drift. `cold` lets a caller that
    already evaluated region_streams_cold per region pass the answers
    in; `regions` the (possibly pruned) region list those answers
    correspond to; `plan` (or a pre-computed `point_sids` vector) routes
    point/IN tag queries through the SST secondary index."""
    if regions is None:
        regions = list(table.regions.values())
    if point_sids is None:
        point_sids = [region_point_sids(r, plan) for r in regions] \
            if plan is not None else [None] * len(regions)
    # sketch / expression moments reduce on the host wherever the rows
    # come from — the suffix keeps EXPLAIN honest about the kernel
    suffix = "; host-partial moments (sketch/expr)" \
        if plan is not None and plan_needs_host(plan) else ""
    n_idx = sum(1 for s in point_sids if s is not None)
    if regions and n_idx == len(regions):
        k = max((len(s) for s in point_sids if s is not None), default=0)
        return (f"indexed-point (sst index, {k} candidate series; "
                f"bloom/sid-summary file pruning{suffix})")
    if cold is None:
        cold = [region_streams_cold(r) for r in regions]
    n_stream = sum(1 for c, s in zip(cold, point_sids)
                   if c and s is None)
    if n_idx:
        return (f"mixed ({n_idx}/{len(regions)} regions indexed-point, "
                f"{n_stream} streamed-cold{suffix})")
    if n_stream == 0:
        return f"device-resident (scan cache{suffix})"
    if n_stream == len(regions):
        return (f"streamed-cold (est_rows={_estimated_table_rows(table)}, "
                f"stream_threshold_rows="
                f"{stream_exec.stream_threshold_rows()}{suffix})")
    return (f"mixed ({n_stream}/{len(regions)} regions "
            f"streamed-cold{suffix})")


def region_point_sids(region, plan) -> Optional[np.ndarray]:
    """Sorted candidate series ids for an indexed point/IN scan of this
    region, or None when the standard resident/streamed paths win.

    Eligible when the plan carries at least one point (`tag = lit`) or
    `IN` tag conjunct (resolved per region through its series dict —
    ROADMAP item 4's 'point and IN predicates prune files'), the sid
    set is selective, the index tier is enabled, and the region is not
    already resident in the scan cache (a warm cache beats any IO).
    The set is a SUPERSET: the host reduction re-applies every tag
    predicate exactly, so `!=`/range conjuncts riding along cannot
    drift answers."""
    if plan is None or not plan.tag_predicates or not sst_index_enabled():
        return None
    sd = getattr(region, "series_dict", None)
    if sd is None or not sd.tag_names:
        return None
    from ..mito.engine import sid_candidates_for_filters
    sids = sid_candidates_for_filters(sd, sd.tag_names,
                                      plan.tag_predicates)
    if sids is None:
        return None
    S = sd.num_series
    if S and len(sids) > max(64, S // 16):
        return None                       # not selective: scan normally
    if scan_cache.SCAN_CACHE.cached(region):
        return None
    return sids


def _indexed_point_frames(region, table, plan: TpuPlan,
                          sids: np.ndarray) -> List[pd.DataFrame]:
    """Partial moment frames for one region via the SST secondary
    index: scan only the files/row groups that may hold the candidate
    series (RegionSnapshot.scan's sid_set tier), merge-dedup the
    surviving rows (exact MVCC), and reduce on the host with the same
    segment arithmetic the streamed path uses — so _finalize folds
    these partials like any others. Never touches the scan cache: a
    point query on a cold many-SST region must not pay (or pin) full
    residency for a handful of series."""
    prof = ScanProfile(path="indexed-point")
    _t0 = _time.perf_counter()
    snap = region.snapshot()
    schema = snap.schema
    tc = schema.timestamp_column
    trange = None
    if tc is not None and (plan.time_lo is not None or
                           plan.time_hi is not None):
        trange = TimestampRange(plan.time_lo, plan.time_hi,
                                tc.dtype.time_unit)
    needed = plan_scan_columns(plan, schema)
    data = snap.scan(projection=needed, time_range=trange, sid_set=sids)
    prof.rows = data.num_rows
    prof.bump("candidate_sids", len(sids))
    prof.mark("scan", _time.perf_counter() - _t0)
    frames: List[pd.DataFrame] = []
    if data.num_rows:
        _t1 = _time.perf_counter()
        kept = stream_exec._slice_dedup(data)
        frame = stream_exec._host_partial_frame(data, kept, plan,
                                                region.series_dict)
        prof.mark("reduce", _time.perf_counter() - _t1)
        exec_stats.record("reduce", rows=data.num_rows,
                          elapsed_s=prof.stages["reduce"])
        if frame is not None and len(frame):
            frames.append(frame)
    prof.total_s = _time.perf_counter() - _t0
    region.last_scan_profile = prof
    return frames


def region_streams_cold(region) -> bool:
    """Whether a region takes the streamed-cold path instead of the
    device-resident scan cache. Streams on either bound: row count, or
    estimated decoded bytes vs the scan-cache budget — a wide-schema
    region can bust residency long before the row threshold (the budget
    never evicts the newest entry, so admission is the only guard).
    Shared by execution (region_moment_frames) and EXPLAIN so the
    printed dispatch decision cannot drift from the real one."""
    return stream_exec.region_estimated_rows(region) > \
        stream_exec.stream_threshold_rows() or \
        (scan_cache.SCAN_CACHE.budget_bytes > 0 and
         stream_exec.region_estimated_bytes(region) >
         scan_cache.SCAN_CACHE.budget_bytes // 2)


def region_moment_frames(table, plan: TpuPlan,
                         regions: Optional[Sequence[int]] = None
                         ) -> List[pd.DataFrame]:
    """Per-region moment frames for a table's local regions (shared by the
    single-node fast path and the datanode side of aggregate pushdown).
    `regions` restricts to a subset of hosted region numbers — the
    frontend's surviving-region list after partition pruning, so a
    datanode does not scan its un-pruned siblings.

    Regions above the streaming threshold never enter the scan cache:
    their time domain is sliced and streamed through the device instead
    (query/stream_exec.py), bounding host+HBM residency by the slice
    budget rather than the region size."""
    if regions is None:
        regions = list(table.regions.values())
    else:
        want = set(regions)
        missing = want - set(table.regions)
        if missing:
            # a pruned aggregate naming regions this node no longer hosts
            # must not silently reduce a partial set — typed so the
            # DistTable refreshes its route and retries
            raise StaleRouteError(
                f"region(s) {sorted(missing)} of table "
                f"{table.info.name} are not hosted here")
        regions = [r for rn, r in table.regions.items() if rn in want]
    if not regions:
        return []
    with exec_stats.stage("plan"):      # its last part: the dispatch
        # indexed point/IN queries bypass both the cache and the slicer:
        # the SST index resolves the predicate to candidate series and
        # the scan opens only the files that may hold them
        point_sids = [region_point_sids(r, plan) for r in regions]
        cold = [False if s is not None else region_streams_cold(r)
                for r, s in zip(regions, point_sids)]
        exec_stats.set_dispatch(local_dispatch_decision(
            table, cold, regions, plan=plan, point_sids=point_sids))
    frames = []
    for region, streams, sids in zip(regions, cold, point_sids):
        process_list.check_cancelled()     # per-region batch boundary
        if sids is not None:
            frames.extend(_indexed_point_frames(region, table, plan,
                                                sids))
            continue
        if streams:
            frames.extend(stream_exec.stream_region_moment_frames(
                region, table, plan))
            continue
        # single-flight: identical concurrent scans of this region fuse
        # into one shared pass (followers adopt the leader's frame)
        part = SCAN_FLIGHTS.execute(region, table, plan)
        if part is not None and len(part):
            frames.append(part)
    return frames


#: bounded park for a follower on the leader's pass — a dead leader
#: degrades to a solo scan, never a hang
_FUSION_WAIT_TIMEOUT_S = 30.0


class _FlightEntry:
    """One in-flight region reduction shared by its cohort."""

    __slots__ = ("done", "frame", "failed")

    def __init__(self) -> None:
        self.done = threading.Event()
        self.frame: Optional[pd.DataFrame] = None
        self.failed = False


class _ScanFlightMap:
    """Single-flight map keyed on (region identity, visible data state,
    plan fingerprint): concurrent identical-shape small scans of the
    same region fuse into ONE shared pass — the leader decodes, the
    cohort adopts its moment frame. The data-state component of the key
    (committed sequence + retraction epoch, sampled at request start)
    keeps read-your-writes intact: a scan that begins after a write is
    acked can never fuse onto a pass that predates the write."""

    def __init__(self) -> None:
        self._lock = TrackedLock("query.scan_fusion")
        self._inflight: Dict[tuple, _FlightEntry] = tracked_state(
            {}, "query.scan_fusion.inflight")

    def execute(self, region, table, plan: TpuPlan):
        key = self._key(region, plan)
        if key is None:
            return _execute_region(region, table, plan)
        with self._lock:
            entry = self._inflight.get(key)
            leader = entry is None
            if leader:
                entry = _FlightEntry()
                self._inflight[key] = entry
        if leader:
            try:
                entry.frame = _execute_region(region, table, plan)
            except BaseException:
                # cohort members fall back to their own solo scans: the
                # leader's failure may be leader-specific (a KILL on its
                # statement must not kill nine bystanders)
                entry.failed = True
                raise
            finally:
                entry.done.set()
                with self._lock:
                    self._inflight.pop(key, None)
            increment_counter("scan_fusion_leader")
            return entry.frame
        # follower: bounded park on the leader's shared pass
        t0 = _time.perf_counter()
        deadline = _time.monotonic() + _FUSION_WAIT_TIMEOUT_S
        while not entry.done.wait(timeout=0.05):
            process_list.check_cancelled()    # killed mid-wait: bail out
            if _time.monotonic() > deadline:
                break
        if not entry.done.is_set() or entry.failed:
            return _execute_region(region, table, plan)
        increment_counter("scan_fusion_follower")
        # EXPLAIN ANALYZE surfaces the fusion: this statement's region
        # pass was adopted from a concurrent leader, not re-decoded
        exec_stats.record(
            "fused-follower",
            rows=0 if entry.frame is None else len(entry.frame),
            elapsed_s=_time.perf_counter() - t0, region=region.name)
        # hand back a copy: cohort members' downstream folds must never
        # share mutable frames (small scans — the copy is cheap)
        return None if entry.frame is None else entry.frame.copy()

    @staticmethod
    def _key(region, plan: TpuPlan) -> Optional[tuple]:
        vc = getattr(region, "version_control", None)
        if vc is None:
            return None
        # fingerprint once per PLAN object, not once per region: a
        # multi-region scan serializes the identical plan only once
        fp = getattr(plan, "_fusion_fp", None)
        if fp is None:
            try:
                fp = json.dumps(plan_to_dict(plan), sort_keys=True,
                                default=str)
            except Exception:  # noqa: BLE001 — unshippable: no fusion
                increment_counter("scan_fusion_unfingerprintable")
                fp = False
            plan._fusion_fp = fp
        if fp is False:
            return None
        return (region.uid, vc.committed_sequence,
                getattr(region, "retraction_epoch", 0), fp)


SCAN_FLIGHTS = _ScanFlightMap()


def _execute_region(region, table, plan: TpuPlan) -> Optional[pd.DataFrame]:
    prof = ScanProfile(path="resident")
    _t0 = _time.perf_counter()
    with span("region_scan", region=region.name, path="resident"):
        with exec_stats.stage("scan_prep"):
            if plan_needs_host(plan):
                scan, tail = scan_cache.SCAN_CACHE.get(region), None
            else:
                scan, tail = scan_cache.SCAN_CACHE.get_parts(
                    region, plan.time_hi)
                if tail is not None and _grows(plan) \
                        and not _outside(plan, tail) \
                        and not _seam_fits(scan, tail, plan):
                    # a late row under a window's growth: one scan
                    # (counted: `scan_cache_merges`)
                    exec_stats.record("scan_prep", seam="merged")
                    scan, tail = scan_cache.SCAN_CACHE.get(region), None
        prof.mark("scan_prep", _time.perf_counter() - _t0)
        outcome = scan_cache.SCAN_CACHE.last_outcome() or "full"
        # same outcome vocabulary as ExecStats (cache=...) and the
        # scan_cache_* prometheus counters: hit / incremental / full
        prof.bump(f"cache_{outcome}")
        rows = scan.num_rows + (tail.valid_rows if tail is not None else 0)
        prof.rows = rows
        exec_stats.record("scan_prep", rows=rows, cache=outcome)
        if rows == 0:
            prof.total_s = _time.perf_counter() - _t0
            region.last_scan_profile = prof
            return None
        _t1 = _time.perf_counter()
        with exec_stats.stage("reduce"):
            reads_tail = tail is not None and not _outside(plan, tail)
            out = None
            if scan.num_rows:
                out = _moment_frame_for_scan(scan, table.schema, plan,
                                             runs=reads_tail)
            if tail is None:
                if scan.num_rows >= TPU_DISPATCH_MIN_ROWS \
                        and not plan_needs_host(plan):
                    _warm_tail_programs(scan, table.schema, plan)
            elif not reads_tail:
                exec_stats.record("reduce", tail="skipped")
            else:
                if scan.num_rows and tail.ts_min <= _last_ts(scan):
                    # late rows: the tail reaches into the base's span
                    exec_stats.record("reduce", tail_span="history")
                out = _base_and_tail_frame(out, _moment_frame_for_scan(
                    tail, table.schema, plan, tail=True, runs=True), plan)
            if out is not None and not len(out):
                out = None
        prof.mark("reduce", _time.perf_counter() - _t1)
        prof.total_s = _time.perf_counter() - _t0
        region.last_scan_profile = prof
        exec_stats.record("reduce", rows=rows)
    return out


def _base_and_tail_frame(base: Optional[_RunPartial],
                         tail: Optional[_RunPartial],
                         plan: TpuPlan) -> Optional[pd.DataFrame]:
    """One partial frame of the two launches. The partials of one group
    are disjoint in keys (a tail holds no (series, time) its base holds),
    not in time: a tail also holds rows that arrived late into the base's
    span. They fold by run before the frame is made (`first` / `last` by
    their companion times), or, where that cannot be, in `_finalize` like
    any two partials."""
    with exec_stats.stage("reduce.collect"):
        parts = [p for p in (base, tail) if p is not None]
        if len(parts) == 2:
            folded = _fold_runs(base, tail, plan)
            parts = [folded] if folded is not None else parts
        frames = [_partial_frame(p, plan) for p in parts]
        return None if not frames else frames[0] if len(frames) == 1 \
            else pd.concat(frames, ignore_index=True)


def _grows(plan: TpuPlan) -> bool:
    """The plan holds a window's growth (`RUN_DIFF_MOMENT_OPS`): over a
    base and its tail it is the sum of the two launches' and the seam's
    (`MergedScan.device_run_diffs`, `_fold_runs`), where `_seam_fits`."""
    return any(m.op in RUN_DIFF_MOMENT_OPS for m in plan.moments)


def _seam_fits(base: MergedScan, tail: MergedScan, plan: TpuPlan) -> bool:
    """Whether a window's growth over `base` and `tail` is the sum of two
    launches and a seam: every row of the tail comes after its series'
    last row in the base (a row that arrived late into the base's history
    lies between two of its samples, whose difference the base's mirror
    already holds), and the fields the plan differences hold no NULL on
    either side of the seam. Found once a tail."""
    key = "__after_base"
    if key not in tail.device:
        n = tail.valid_rows
        first = _series_firsts(tail.series_ids[:n])
        at, has = _base_lasts(base, tail.series_ids[first])
        tail.device[key] = (bool(
            (tail.ts[first][has] > base.ts[at[has]]).all()),)
    return tail.device[key][0] and all(
        scan.fields[m.column][1] is None
        for m in plan.moments if m.op in RUN_DIFF_MOMENT_OPS
        for scan in (base, tail))


def _warm_tail_programs(base: MergedScan, schema, plan: TpuPlan) -> None:
    """Compile what this statement will launch over the base's tail once
    rows are written, now, where the statement's own programs are
    compiled (a server's warm statements come before the writes: the
    first statement after one must not be the one that compiles). The
    launch is laid out over a stand-in tail, one row a series of the base
    at a time the statement reads: just after the base's last where its
    range is open there (the shapes a tick of every series gives), else
    the last instant of its range (a range closed inside the base's span
    meets a tail only through rows that arrive late, and those land
    inside it). A tail's group axis is pinned (`_pinned_groups`), so the
    rows that do arrive meet the program compiled here. Lowered and
    compiled for those shapes and kept in `base.tail_programs`; nothing is
    uploaded and nothing runs, so a table nobody writes holds on the
    device what it held before. Once a base and statement shape (with
    what the base's launch chose: path, range bucket, group axis and its
    size, which the tail's launch follows: `_base_launch`); a base under
    the dispatch floor (`TPU_DISPATCH_MIN_ROWS`, as the operator has set
    it) reached the device by another road (a plan the host path cannot
    run) and is left to compile when a tail is met."""
    shape = _statement_shape(plan)
    key = (base.launch_shapes.get(shape), shape, None if plan.bucket is None
           else _bucket_phase(plan.bucket))
    warmed = base.device.setdefault("__tail_warmed", set())
    if key in warmed:
        return
    warmed.add(key)
    at = _last_ts(base) + 1
    if plan.time_hi is not None and plan.time_hi <= at:
        at = plan.time_hi - 1
    if plan.time_lo is not None and plan.time_lo > at:
        # a statement over times the base has no row of: the tail it
        # meets compiles when met
        return
    with _reduce_part("tail_warm"):
        sids = base.series_ids
        first = _series_firsts(sids)
        k = len(first)
        zeros = np.zeros(k, dtype=np.float64)
        fields = {name: ((zeros, None) if vals.dtype != object
                         else (np.full(k, None, dtype=object),
                               np.zeros(k, dtype=bool)))
                  for name, (vals, _) in base.fields.items()}
        stand_in = _make_tail(_Rows(
            sids[first], np.full(k, at, np.int64),
            np.zeros(k, np.int64), fields), base)
        stand_in.count_uploads = False
        stand_in.stand_in = True
        _launch_for_scan(stand_in, schema, plan, _untimed_part)


def _launch_for_scan(scan: MergedScan, schema, plan: TpuPlan, part):
    """-> (launched or None, "narrow" | "full", the selection or None):
    the resident reduce of one scan, launched."""
    with part("mask"):
        sel = scan_narrow.select(scan, schema, plan)
    n_ranges, padded_rows = (None, 0) if sel is None \
        else (sel.n_ranges, sel.padded_rows)
    path = scan_narrow.scan_read_path(scan.num_rows, n_ranges, padded_rows)
    if path == "narrow":
        return scan_narrow.launch(scan, schema, plan, sel, part), path, sel
    return scan_full._launch_scan_kernel(
        scan, schema, plan, part, sel), path, sel


def _moment_frame_for_scan(scan: MergedScan, schema, plan: TpuPlan,
                           tail: bool = False, runs: bool = False):
    """-> the scan's partial moment frame, or with `runs` its
    `_RunPartial` (None: no row). `tail`: the scan is the tail of the one
    just reduced; the `reduce` row's detail says so (`tail_rows=`,
    `tail_path=`) beside the base's, whose launch left what it chose in
    `launch_shapes` for this one to follow."""
    if plan_needs_host(plan):
        # sketch / expression moments: reduce the resident merged scan
        # on the host with the same segment arithmetic the streamed
        # path uses — MergedScan rows are already sorted + MVCC-deduped,
        # so the partial frame folds like any other
        return stream_exec._host_partial_frame(scan, None, plan,
                                               scan.series_dict)

    t0 = _time.perf_counter()
    launched, path, sel = _launch_for_scan(scan, schema, plan, _reduce_part)
    if not tail:
        axis = None if launched is None or path == "narrow" else \
            "table" if launched.table_runs is None else "live"
        if len(scan.launch_shapes) >= 64:
            scan.launch_shapes.clear()
        scan.launch_shapes[_statement_shape(plan)] = _LaunchShape(
            path, None if sel is None else sel.range_bucket, axis,
            launched.num_groups if axis else 0)
    increment_counter("scan_reads", path=path)
    # the rows this launch reads on the device: the table (a tail: the
    # rows it holds), or the ranges
    rows = scan.num_rows if scan.valid_rows is None else scan.valid_rows
    increment_counter("scan_device_rows",
                      sel.rows if path == "narrow" else rows)
    # whether the host built and uploaded a row mask of the scan's length
    # (tag predicates, field filters: a time range alone makes none)
    made = "host" if launched is not None and launched.host_mask else "none"
    increment_counter("scan_row_mask", made=made)
    if tail:
        exec_stats.record("reduce", tail_rows=rows, tail_path=path,
                          tail_mask=made)
    elif path == "narrow":
        exec_stats.record("reduce", path=path, narrow_rows=sel.rows,
                          ranges=sel.n_ranges)
    else:
        exec_stats.record("reduce", path=path)
        if launched is not None and launched.table_runs is not None:
            increment_counter("scan_group_axis", axis="live")
            exec_stats.record("reduce", groups="live",
                              live_runs=launched.nruns,
                              table_runs=launched.table_runs)
        else:
            increment_counter("scan_group_axis", axis="table")
            exec_stats.record("reduce", groups="table")
        exec_stats.record("reduce", mask=made)
    if launched is None:
        return None
    run, shared = launched.passes
    increment_counter("scan_kernel_passes", run, kind="run")
    increment_counter("scan_kernel_passes", shared, kind="shared")
    # the form the program's float sums take at its group count
    sums = sum_form(launched.num_groups)
    increment_counter("scan_sum_form", form=sums)
    if tail:
        exec_stats.record("reduce", tail_passes=run, tail_sums=sums)
    else:
        exec_stats.record("reduce", moments=run + shared, passes=run,
                          sums=sums)
    if launched.extremes is not None:
        increment_counter("scan_extreme_form", form=launched.extremes)
        exec_stats.record("reduce", **{
            "tail_ext" if tail else "ext": launched.extremes})
    with _reduce_part("fetch"):     # blocked on the device, then D2H
        counts, res_np = jax.device_get((launched.counts,
                                         list(launched.results)))
    if launched.warm:
        _note_device_query_time(_time.perf_counter() - t0)
    with _reduce_part("collect"):
        return (moment_fold._collect_runs if runs
                else moment_fold._collect_moment_frame)(
            launched, plan, counts, res_np)
