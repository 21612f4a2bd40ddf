"""The TPU aggregate fast path.

Executes the canonical time-series shape — scan → filter → group by tags
and/or time bucket → aggregate — as one device kernel pass per region:

1. per-region merged scan (sorted by (series, ts), MVCC-deduped) from a
   version-keyed cache; arrays are device-resident across queries until the
   region version changes (the HBM-resident memtable design of SURVEY §7);
2. group ids are contiguous run ids over (series, bucket) — sorted by
   construction, so the scatter-free sorted-segment kernel applies;
3. the kernel computes decomposable *moments* (sum/sum_sq/count/min/max/
   first+ts/last+ts) per run; runs fold into final SQL groups on the host
   (tiny), which also merges partials across regions.

Anything outside this shape returns None and the engine falls back to the
CPU columnar executor — the same division of labor the reference has
between its pushed-down scans and DataFusion.
"""

from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import (Any, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

import numpy as np
import pandas as pd

from ..errors import UnsupportedError
from ..ops.kernels import (_sorted_grouped_aggregate_pre, distinct_arrays,
                           merge_dedup_numpy, moment_results, shape_bucket,
                           sum_form)
from ..sql.ast import (
    Between, BinaryOp, Column, Expr, FunctionCall, InList, Interval, IsNull,
    Literal, Query, UnaryOp,
)
from ..common.failpoint import register as _fp_register
from ..utils import env_flag as _env_flag
from .expr import Evaluator, expr_name
from .functions import SKETCH_AGGREGATES, TPU_AGGREGATES, parse_interval_ms
from .planner import Analysis, _group_slot

_fp_register("scan_cache_incremental")

_CMP_OPS = {"=": "eq", "!=": "ne", "<": "lt", "<=": "le", ">": "gt",
            ">=": "ge"}


# ---------------------------------------------------------------------------
# merged-scan cache (per region version)
# ---------------------------------------------------------------------------

@dataclass
class MergedScan:
    series_ids: np.ndarray            # int32, sorted
    ts: np.ndarray                    # int64 epoch (region units)
    fields: Dict[str, Tuple[np.ndarray, Optional[np.ndarray]]]
    series_dict: object
    ts_base: int                      # device ts = ts - ts_base (int32)
    seq: Optional[np.ndarray] = None  # per-row sequence (incremental merge)
    device: Dict[str, object] = field(default_factory=dict)
    #: rows beyond this index are shape-bucket padding (streamed slices
    #: pad to shared XLA shapes); None = every row is real
    valid_rows: Optional[int] = None
    #: kernel launches this scan has made (run layout + moments): a
    #: repeat of one compiles, uploads and sweeps nothing
    launched: set = field(default_factory=set)
    #: a tail (`_ScanCache`): the row axis is a capacity, not a count, and
    #: every layout a launch derives from the content (the longest run)
    #: is pinned to what the capacity allows, so that rows written later
    #: never meet a program that was not compiled
    pinned: bool = False
    #: the scan cache's: its uploads count (`scan_cache_upload_bytes`)
    count_uploads: bool = False
    #: smallest / largest ts among the valid rows (a tail's: a statement
    #: whose time range lies outside skips it)
    ts_min: int = 0
    ts_max: int = -1
    #: a tail's fields as one float64 [valid rows, fields] (`_Rows.block`)
    block: Optional[np.ndarray] = None
    #: a base's: the executables its tails launch, compiled ahead of the
    #: first write (`_warm_tail_programs`, `_run_program`)
    tail_programs: dict = field(default_factory=dict)
    #: a tail's: its base's `tail_programs`
    programs: Optional[dict] = None
    #: a base's: what its launch of a statement shape chose last
    #: (`_LaunchShape` by `_statement_shape`), for the launch over its
    #: tail to follow (`_base_launch`) and `_warm_tail_programs` to key by
    launch_shapes: dict = field(default_factory=dict)
    #: a tail nobody reads (`_warm_tail_programs`): its mirrors are shapes,
    #: nothing is uploaded, and its launch is compiled, not run
    stand_in: bool = False
    #: a tail's: the base it follows (a series' first difference here
    #: reaches back to its last sample there: `device_run_diffs`)
    base: Optional["MergedScan"] = None

    @property
    def num_rows(self) -> int:
        return len(self.ts)

    def _put(self, key: str, arr: np.ndarray, fill=None):
        """Upload one mirror. A tail keeps its fields at their valid
        length on the host: the padding to the row axis is made here
        (`fill`, or the last value, as padded slices repeat their last
        row)."""
        import jax
        n, k = self.num_rows, len(arr)
        if self.stand_in:
            self.device[key] = jax.ShapeDtypeStruct((n,), arr.dtype)
            return self.device[key]
        if k < n:
            out = np.empty(n, dtype=arr.dtype)
            out[:k] = arr
            out[k:] = (arr[-1] if k else 0) if fill is None else fill
            arr = out
        if self.count_uploads:
            from ..common.telemetry import increment_counter
            increment_counter("scan_cache_upload_bytes", int(arr.nbytes))
        self.device[key] = jax.device_put(np.ascontiguousarray(arr))
        return self.device[key]

    def upload(self, arr: np.ndarray):
        """A statement's own array (a row mask, run ids) on the device; a
        stand-in's stays a shape."""
        import jax
        if self.stand_in:
            return jax.ShapeDtypeStruct(arr.shape, arr.dtype)
        return jax.device_put(arr)

    def device_ts(self):
        if "__ts" not in self.device:
            if self.pinned:     # a tail knows its span: no pass to find it
                lo, hi = self.ts_min, self.ts_max
                rel = self.ts[:self.valid_rows] - self.ts_base
            else:
                rel = self.ts - self.ts_base
                lo, hi = (int(rel.min()) + self.ts_base,
                          int(rel.max()) + self.ts_base) if rel.size \
                    else (self.ts_base, self.ts_base)
            if hi - self.ts_base >= 2**31 or lo < self.ts_base:
                raise UnsupportedError("region time span exceeds int32")
            self._put("__ts", rel.astype(np.int32))
        return self.device["__ts"]

    def device_sids(self):
        """The series id a row: with the times, what a run label is made
        from where no layout holds run ids (`scan_narrow.run_labels`)."""
        if "__sids" not in self.device:
            self._put("__sids", self.series_ids)
        return self.device["__sids"]

    def device_pad_mask(self):
        """True on the valid rows of a padded scan."""
        if "__pad_mask" not in self.device:
            pm = np.zeros(self.num_rows, np.bool_)
            pm[:self.valid_rows] = True
            self._put("__pad_mask", pm)
        return self.device["__pad_mask"]

    def device_field(self, name: str):
        key = f"f:{name}"
        if key not in self.device:
            vals, valid = self.fields[name]
            if vals.dtype == object:
                raise UnsupportedError(f"field {name} is not numeric")
            import jax as _jax
            v = vals
            x64 = _jax.config.jax_enable_x64
            if v.dtype == np.int64 and not x64:
                v = v.astype(np.float64) if abs(v).max(initial=0) >= 2**31 \
                    else v.astype(np.int32)
            if v.dtype == np.float64 and not x64:
                # TPU has no f64: the device mirrors are f32 (documented
                # precision tradeoff); with x64 on (CPU) keep full precision
                v = v.astype(np.float32)
            self._put(key, v)
        return self.device[key]

    def device_run_diffs(self, name: str, counter: bool):
        """The derived mirror a lowered `rate` / `increase` (`counter`) or
        `delta` reads: each valid sample's difference to its series'
        previous valid sample, reset-aware for a counter (`v - prev`, or
        `v` where the counter restarted below `prev`), 0 for a series'
        first. Made in float64 on the host, so the f32 mirror holds a
        scrape's growth to 6e-8 of itself whatever the level: a window's
        raw increase is the sum over its run but the run's first sample
        (`ops/kernels.py` `growth`). last - first of
        the plain f32 mirrors has no digits left once the level is large
        (a counter at 1e12 that grows 6e4 a window came out 31% off).
        Built on a field's first use by such a function, never before.

        A tail's mirror is made across the seam: a series' first sample
        here takes its difference from the series' last sample in the
        base (`_seam`), so the two scans' differences are those of one
        scan and a window that lies across them is the sum of its two
        parts (`_fold_runs`)."""
        import jax
        key = _run_diffs_key(name, counter)
        if key not in self.device:
            vals, valid = self.fields[name]
            if vals.dtype == object:
                raise UnsupportedError(f"field {name} is not numeric")
            v = vals.astype(np.float64, copy=False)
            n = len(v)          # a tail's fields end at its valid rows
            rows = None if valid is None else np.nonzero(valid)[0]
            sids = self.series_ids[:n] if rows is None \
                else self.series_ids[rows]
            if rows is not None:
                v = v[rows]
            d = np.zeros(len(v), dtype=np.float64)
            if len(v) > 1:
                np.subtract(v[1:], v[:-1], out=d[1:])
                if counter:
                    np.copyto(d[1:], v[1:], where=v[1:] < v[:-1])
                d[1:][sids[1:] != sids[:-1]] = 0.0
            if self.base is not None and not self.stand_in and len(v):
                _seam(self.base, name, counter, sids, v, d)
            if rows is not None:
                full = np.zeros(n, dtype=np.float64)
                full[rows] = d
                d = full
            if not jax.config.jax_enable_x64:
                d = d.astype(np.float32)
            self._put(key, d)
        return self.device[key]

    def device_valid(self, name: str):
        """A field's validity mirror; None: the field has no NULL."""
        key = f"v:{name}"
        if key not in self.device:
            _, valid = self.fields[name]
            if valid is None:
                return None
            self._put(key, valid, fill=False)
        return self.device[key]

    def device_valid_all(self):
        if "__all_valid" not in self.device:
            self._put("__all_valid", np.ones(self.num_rows, dtype=bool))
        return self.device["__all_valid"]

    @property
    def nbytes(self) -> int:
        """Host + device residency of this scan (cache accounting)."""
        total = self.series_ids.nbytes + self.ts.nbytes
        if self.seq is not None:
            total += self.seq.nbytes
        for vals, valid in self.fields.values():
            total += getattr(vals, "nbytes", 8 * len(vals))
            if valid is not None:
                total += valid.nbytes
        # snapshot: a launch on another thread adds mirrors meanwhile
        for v in list(self.device.values()):
            if isinstance(v, tuple):     # cached run-boundary context
                total += sum(getattr(x, "nbytes", 0) for x in v)
            else:
                total += getattr(v, "nbytes", 0)
        return total


def _run_diffs_key(name: str, counter: bool) -> str:
    """Where a scan keeps `device_run_diffs(name, counter)`."""
    return f"{'c' if counter else 'g'}:{name}"


@dataclass
class _CacheEntry:
    scan: MergedScan                  # the base: immutable once built
    visible: int                      # sequences <= visible are merged in
    sst_names: frozenset              # SSTs whose content is merged in
    schema_version: int
    retraction_epoch: int
    #: rows written since the base was built (None: none yet)
    tail: Optional[MergedScan] = None

    @property
    def nbytes(self) -> int:
        return self.scan.nbytes + \
            (self.tail.nbytes if self.tail is not None else 0)


#: A base's tail holds up to 1 / this of the base's rows (as a power of
#: two, at least `_TAIL_MIN_ROWS`): its row axis, so one program a
#: statement shape whatever was written. A launch over the tail costs
#: that share of the base's; past it the tail merges into a new base.
_TAIL_SHARE = 16
_TAIL_MIN_ROWS = 4096


def tail_capacity(base_rows: int) -> int:
    return shape_bucket(base_rows // _TAIL_SHARE, minimum=_TAIL_MIN_ROWS)


@dataclass
class _Rows:
    """Sorted, deduplicated rows on the host: a delta, or a tail's valid
    rows. A field's validity is None where every value is valid."""
    sids: np.ndarray
    ts: np.ndarray
    seq: np.ndarray
    fields: Dict[str, Tuple[np.ndarray, Optional[np.ndarray]]]
    #: a delta's tombstones (None: every row is a put)
    deleted: Optional[np.ndarray] = None
    #: float64 [n, fields] where every field is a float64 without a NULL:
    #: `fields` then holds its columns as views, and a merge moves all of
    #: them in one pass (what a pass costs a statement beside six writers
    #: is a wait for the interpreter lock, not its bytes)
    block: Optional[np.ndarray] = None

    def __len__(self) -> int:
        return len(self.ts)


def _block_fields(names, block: np.ndarray) -> dict:
    return {name: (block[:, j], None) for j, name in enumerate(names)}


def _key_positions(sids: np.ndarray, ts: np.ndarray, new: _Rows):
    """-> (pos, collide, behind): where each row of `new` (sorted, unique
    keys) goes among the rows (sids, ts) sorted by (series, ts): before
    row pos[i], or onto it where `collide[i]` (the same key; None: no row
    collides). `behind[i]`: the row lies at or before its series' last
    row here (a late row, or with `collide` an overwrite; None: every row
    comes after its series' last one)."""
    hi = np.searchsorted(sids, new.sids, side="right")
    if not len(ts):
        return hi, None, None
    at = np.maximum(hi - 1, 0)
    # what ticks give: every row comes after its series' last one
    behind = (hi > 0) & (sids[at] == new.sids) & (ts[at] >= new.ts)
    if not behind.any():
        return hi, None, None
    from .scan_narrow import _lower_bound
    lo = np.searchsorted(sids, new.sids, side="left")
    pos = _lower_bound(ts, lo, hi, new.ts)      # every range at once
    collide = (pos < hi) & (ts[np.minimum(pos, len(ts) - 1)] == new.ts)
    return pos, collide if collide.any() else None, behind


def _merge_rows(old: _Rows, new: _Rows, drop_deleted: bool = True,
                at=None) -> _Rows:
    """`new` merged into `old` (both sorted by (series, ts), keys unique
    within each; every row of `new` is newer than any of `old`): a row
    of `new` replaces the row of its key or takes its place in the
    order, wherever in time that is; a tombstone of `new` removes itself
    and the row it shadows, or with `drop_deleted` off stays as a
    tombstone of the result (for a merge into older rows still to come).
    One search over the keys (`at`: its (pos, collide) where the caller
    has made it), one pass a column (one for all fields of a `block`), no
    sort and no loop over series."""
    pos, collide = _key_positions(old.sids, old.ts, new)[:2] \
        if at is None else at
    n_old = len(old)
    if collide is None:
        fresh, hit, dest_hit = slice(None), None, None
        n_fresh = len(new)
    else:
        fresh, hit = ~collide, collide
        n_fresh = int(fresh.sum())
    m = n_old + n_fresh
    dest_fresh = pos[fresh] + np.arange(n_fresh)
    is_fresh = np.zeros(m, dtype=bool)
    is_fresh[dest_fresh] = True
    dest_old = np.flatnonzero(~is_fresh)
    if hit is not None:
        dest_hit = dest_old[pos[hit]]
    keep = None

    def column(a, b, dtype=None):
        if dtype is None:
            dtype = object if object in (a.dtype, b.dtype) \
                else np.result_type(a.dtype, b.dtype)
        out = np.empty((m,) + a.shape[1:], dtype=dtype)
        out[dest_old] = a
        out[dest_fresh] = b[fresh]
        if hit is not None:
            out[dest_hit] = b[hit]
        return out if keep is None else out[keep]

    deleted = None
    if new.deleted is not None and new.deleted.any():
        deleted = column(np.zeros(n_old, dtype=bool), new.deleted)
        if drop_deleted:
            keep, deleted = ~deleted, None
    block = None
    if old.block is not None and new.block is not None:
        block = column(old.block, new.block)
        fields = _block_fields(old.fields, block)
    else:
        fields = {}
        for name, (ad, av) in old.fields.items():
            bd, bv = new.fields[name]
            valid = None
            if av is not None or bv is not None:
                valid = column(
                    av if av is not None else np.ones(n_old, bool),
                    bv if bv is not None else np.ones(len(new), bool))
                if valid.all():
                    valid = None
            fields[name] = (column(ad, bd), valid)
    return _Rows(column(old.sids, new.sids, np.int32),
                 column(old.ts, new.ts), column(old.seq, new.seq), fields,
                 deleted, block)


def _take_rows(rows: _Rows, keep: np.ndarray) -> _Rows:
    """The rows of a put-only `rows` that the bool `keep` names."""
    if rows.block is not None:
        block = rows.block[keep]
        fields = _block_fields(rows.fields, block)
    else:
        block = None
        fields = {name: (d[keep], None if v is None else v[keep])
                  for name, (d, v) in rows.fields.items()}
    return _Rows(rows.sids[keep], rows.ts[keep], rows.seq[keep], fields,
                 None, block)


def _same_values(fields, at: np.ndarray, new: _Rows,
                 rows: np.ndarray) -> np.ndarray:
    """-> bool [len(rows)]: row rows[i] of `new` holds in every field what
    the resident row at[i] of `fields` holds (a NULL equals a NULL; a NaN
    equals nothing, so such a row counts as changed)."""
    same = np.ones(len(rows), dtype=bool)
    for name, (rd, rv) in fields.items():
        nd, nv = new.fields[name]
        a_ok = np.True_ if rv is None else rv[at]
        b_ok = np.True_ if nv is None else nv[rows]
        same &= (a_ok == b_ok) & (~(a_ok & b_ok) | (rd[at] == nd[rows]))
    return same


@dataclass
class _Settled:
    """What `_settle` made of a delta."""
    rows: _Rows                       # what is left to write
    #: its (pos, collide) among the tail's rows (None: there is no tail)
    at_tail: Optional[tuple]
    late: int = 0                     # at or before a series' last row
    equal: int = 0                    # re-sent: dropped
    changed: int = 0                  # overwrites that change a value
    #: a changed row is the base's: only a merge can write it
    changes_base: bool = False


def _settle(base: "MergedScan", tail: Optional[_Rows],
            delta: _Rows) -> _Settled:
    """Where a put-only delta's rows go, by what base and tail hold at
    their keys (one search a row over each, no pass over the base): a row
    whose key neither holds is left for the tail, wherever its time lies
    (`late` counts those at or before their series' last resident row); a
    row whose key one of them holds with the same values is a retry, and
    is dropped here (the resident row keeps the sequence it had: nothing
    that reads the cache sees a difference); one that changes a value
    stays, to replace the tail's row or, where it is the base's, to make
    the caller merge."""
    n = len(delta)
    drop = np.zeros(n, dtype=bool)
    late = np.zeros(n, dtype=bool)
    held = np.zeros(n, dtype=bool)
    out = _Settled(delta, None)

    def look(sids, ts, fields):
        pos, collide, behind = _key_positions(sids, ts, delta)
        changed = 0
        if behind is not None:
            late[:] |= behind
        if collide is not None:
            held[:] |= collide
            rows = np.flatnonzero(collide)
            same = _same_values(fields, pos[rows], delta, rows)
            drop[rows[same]] = True
            changed = int((~same).sum())
        return pos, collide, changed

    _pos, _collide, changed = look(base.series_ids, base.ts, base.fields)
    out.changed, out.changes_base = changed, changed > 0
    if tail is not None and not out.changes_base:
        pos, collide, changed = look(tail.sids, tail.ts, tail.fields)
        out.changed += changed
        out.at_tail = (pos, collide)
    late &= ~held
    out.late, out.equal = int(late.sum()), int(drop.sum())
    if out.equal:
        keep = ~drop
        out.rows = _take_rows(delta, keep)
        if out.at_tail is not None:
            pos, collide = out.at_tail
            collide = None if collide is None or not collide[keep].any() \
                else collide[keep]
            out.at_tail = (pos[keep], collide)
    return out


class _ScanCache:
    """Per-region merged-scan cache: byte-budget LRU, refreshed by what
    was written.

    An entry is a *base* (the region's merged rows as they were when it
    was built: immutable, with its device mirrors, its compiled launches
    and its run layouts) and a *tail* (the rows written since: a second,
    small sorted scan whose row axis is a fixed capacity,
    `tail_capacity`, masked by `valid_rows`). On a version bump the cache
    collects only the *delta* (memtable rows with sequences beyond the
    cached watermark plus SSTs that carry such rows), sorts it, and
    merges it into the tail: the cost follows the delta and the tail,
    never the base, and no array or mirror of the base is touched. A
    statement reduces both and folds the two partial frames
    (`_execute_region`).

    A tail holds the rows whose key (series, time) the base does not
    hold, wherever in time they lie: what came after the base's last row
    of a series, a series the base has not seen, and rows that arrive
    late into history (a relay's queue drained behind the live ticks).
    The two partials of one group are disjoint in keys, which is what
    sums, counts and extremes need; `first` / `last` fold by their
    companion times, a window's growth by the seam (`_fold_runs`).
    `_settle` decides from what base and tail hold at a delta's keys: a
    row that re-sends a resident row's values (a retry) is dropped, one that changes a tail row's replaces
    it there; a row that changes a base row's values, a tombstone, and a
    tail past its capacity *merge* into a new base (`_merge_rows` over
    every column: counted, `scan_cache_merges`; the new base has a new
    length, so its mirrors are uploaded and its programs compiled
    again). `get` hands the callers that want one sorted scan such a
    merged base.
    Flushes and compactions whose files only contain already-covered
    sequences reuse the entry as it is; TTL retraction
    (region.retraction_epoch) and schema changes force a full rebuild.

    Residency is bounded by a byte budget across regions (host arrays +
    device mirrors): whole entries evict LRU-first — never partially —
    so a server hosting many hot regions can't grow HBM without bound
    (VERDICT round-3 weakness 5). The newest entry always stays, even
    when it alone exceeds the budget (regions that large should be
    routed to the streaming path by region_moment_frames anyway)."""

    def __init__(self, capacity: int = 16,
                 budget_bytes: int = 4 << 30):
        self.capacity = capacity
        self.budget_bytes = budget_bytes
        from ..common.locks import TrackedLock
        from ..common.tracking import tracked_state
        self._lock = TrackedLock("query.scan_cache")
        self._entries: Dict[str, _CacheEntry] = tracked_state(
            {}, "query.scan_cache.entries")          # insertion = LRU order
        # per-thread outcome of the most recent get(): "hit" /
        # "incremental" / "full" — read by the resident scan profiler
        self._last = threading.local()

    def last_outcome(self) -> Optional[str]:
        return getattr(self._last, "outcome", None)

    def get(self, region) -> MergedScan:
        """The region's rows as ONE sorted scan: the base, after merging
        a tail into it (the callers that walk a scan themselves: the
        PromQL selector, flow folds, downsampling, the pandas frame)."""
        entry = self._refresh(region)
        if entry.tail is not None:
            entry = self._store(region, _CacheEntry(
                self._merged(entry.scan, _tail_rows(entry.tail)),
                entry.visible, entry.sst_names, entry.schema_version,
                entry.retraction_epoch))
        return entry.scan

    def get_parts(self, region, time_hi: Optional[int] = None
                  ) -> Tuple[MergedScan, Optional[MergedScan]]:
        """-> (base, tail or None), current as of the region's committed
        sequence at the call for every row before `time_hi` (None: for
        every row)."""
        entry = self._refresh(region, time_hi)
        return entry.scan, entry.tail

    def _store(self, region, entry: _CacheEntry) -> _CacheEntry:
        with self._lock:
            self._entries.pop(region.uid, None)
            self._entries[region.uid] = entry
            self._evict_locked()
        return entry

    def _refresh(self, region, time_hi: Optional[int] = None
                 ) -> _CacheEntry:
        from ..common.telemetry import increment_counter
        snap = region.snapshot()
        v = snap._version
        visible = snap.visible_sequence
        sst_names = frozenset(f.file_name for f in v.ssts.all_files())
        epoch = getattr(region, "retraction_epoch", 0)
        with self._lock:
            entry = self._entries.get(region.uid)
            if entry is not None:                    # LRU touch
                self._entries.pop(region.uid)
                self._entries[region.uid] = entry
        if time_hi is not None and entry is not None \
                and entry.schema_version == v.schema.version \
                and entry.retraction_epoch == epoch \
                and entry.visible <= visible \
                and _unmerged_from(v, entry) >= time_hi:
            # closed history: every row the entry has not merged (a put,
            # an overwrite, a tombstone) carries a timestamp at or after
            # the statement's range, so the entry answers it exactly as
            # it stands, and stays as it is for the statement that does
            # read those rows
            self._last.outcome = "hit"
            increment_counter("scan_cache_hit")
            return entry
        # an entry over an empty region has nothing to keep: the rows
        # that arrive (a bulk load) are a build, not a delta
        if entry is not None and entry.schema_version == v.schema.version \
                and entry.retraction_epoch == epoch \
                and entry.visible <= visible \
                and (entry.scan.num_rows or entry.tail is not None
                     or entry.visible == visible):
            if entry.visible == visible and entry.sst_names == sst_names:
                self._last.outcome = "hit"
                increment_counter("scan_cache_hit")
                return entry
            try:
                from ..common.failpoint import fail_point
                fail_point("scan_cache_incremental")
                base, tail = self._incremental(region, v, entry, visible)
                self._last.outcome = "incremental"
                increment_counter("scan_cache_incremental")
            except Exception as e:  # noqa: BLE001 — degrade, don't fail
                # a corrupt/unusable cached scan must never fail the
                # query: drop the entry and rebuild cold from storage —
                # counted as a miss (that is what the reader pays), plus
                # the recovery marker for dashboards
                import logging
                logging.getLogger(__name__).warning(
                    "scan cache entry for region %s unusable (%s); "
                    "rebuilding cold", region.name, e)
                increment_counter("scan_cache_recovered")
                increment_counter("scan_cache_miss")
                with self._lock:
                    self._entries.pop(region.uid, None)
                self._last.outcome = "full"
                base, tail = self._full(region, snap), None
        else:
            self._last.outcome = "full"
            increment_counter("scan_cache_miss")
            base, tail = self._full(region, snap), None
        return self._store(region, _CacheEntry(
            base, visible, sst_names, v.schema.version, epoch, tail))

    def _evict_locked(self) -> None:
        """Drop LRU entries until count and byte budgets hold (whole
        entries only; the most recent entry is never evicted)."""
        while len(self._entries) > max(self.capacity, 1):
            self._entries.pop(next(iter(self._entries)))
        if self.budget_bytes <= 0:
            return
        total = {uid: e.nbytes for uid, e in self._entries.items()}
        used = sum(total.values())
        for uid in list(self._entries):
            if used <= self.budget_bytes or len(self._entries) <= 1:
                break
            self._entries.pop(uid)
            used -= total[uid]

    def cached(self, region) -> bool:
        """Whether this region has a resident entry (any freshness):
        the indexed-point planner prefers a warm cache — incremental
        maintenance beats re-reading even one SST — and only routes
        around the cache when the region would be scanned cold."""
        with self._lock:
            return region.uid in self._entries

    def resident_bytes(self) -> int:
        with self._lock:
            return sum(e.nbytes for e in self._entries.values())

    def configure(self, *, budget_bytes: Optional[int] = None,
                  capacity: Optional[int] = None) -> None:
        with self._lock:
            if budget_bytes is not None:
                self.budget_bytes = int(budget_bytes)
            if capacity is not None:
                self.capacity = int(capacity)
            self._evict_locked()

    def _full(self, region, snap) -> MergedScan:
        data = snap.scan()
        if data.num_rows:
            kept = merge_dedup_numpy(data.series_ids, data.ts, data.seq,
                                     data.op_types)
            sids = data.series_ids[kept]
            ts = data.ts[kept]
            seq = data.seq[kept]
            fields = {}
            for n, (d, vd) in data.fields.items():
                # a memtable hands every column a validity: one that
                # holds no NULL is None here, as a delta's is, and a
                # launch's moments over such columns share the row count
                vd = None if vd is None else vd[kept]
                fields[n] = (d[kept], None if vd is None or vd.all() else vd)
        else:
            sids, ts, seq = data.series_ids, data.ts, data.seq
            fields = data.fields
        base = int(ts.min()) if ts.size else 0
        return MergedScan(sids.astype(np.int32), ts, fields,
                          data.series_dict, base, seq=seq,
                          count_uploads=True)

    def _incremental(self, region, v, entry: _CacheEntry, visible: int):
        """-> (base, tail) with the rows in (entry.visible, visible]
        applied. Parts of the statement's `scan_prep` row: `.delta` (the
        rows collected and sorted), `.apply` (`_settle`: retries dropped,
        the rest merged into the tail wherever in time they lie, or tail
        and delta into a new base; its detail counts `late=`,
        `equal_dropped=`, `changed=`), `.upload` (the tail's pad mask
        and the mirrors its predecessor had in use, whole: a tail is
        sorted by (series, time), so a tick of every series lands in as
        many places as there are series and no suffix of a mirror is
        left as it was)."""
        from ..common import exec_stats
        from ..common.telemetry import increment_counter
        with exec_stats.stage("scan_prep.delta"):
            delta = self._delta(region, v, entry, visible)
        if delta is None:
            return entry.scan, entry.tail
        increment_counter("scan_cache_delta_rows", len(delta))
        exec_stats.record("scan_prep.delta", rows=len(delta))
        base = entry.scan
        with exec_stats.stage("scan_prep.apply"):
            rows = None
            tail_rows = None if entry.tail is None \
                else _tail_rows(entry.tail)
            if delta.deleted is None:
                settled = _settle(base, tail_rows, delta)
                delta = settled.rows
                increment_counter("scan_cache_late_rows", settled.late)
                increment_counter("scan_cache_overwrites", settled.equal,
                                  kind="equal")
                increment_counter("scan_cache_overwrites", settled.changed,
                                  kind="changed")
                exec_stats.record("scan_prep.apply", late=settled.late,
                                  equal_dropped=settled.equal,
                                  changed=settled.changed)
                if not len(delta):      # retries only: nothing to write
                    return base, entry.tail
                if not settled.changes_base:
                    rows = delta if tail_rows is None else _merge_rows(
                        tail_rows, delta, at=settled.at_tail)
                    if len(rows) > tail_capacity(base.num_rows):
                        rows = None
            if rows is None:
                if tail_rows is not None:
                    # tombstones stay: they may shadow rows of the base
                    delta = _merge_rows(tail_rows, delta,
                                        drop_deleted=False)
                merged = self._merged(base, delta)
                exec_stats.record("scan_prep.apply", merged=1)
                return merged, None
            tail = _make_tail(rows, base)
        with exec_stats.stage("scan_prep.upload"):
            # what the statements before this write read on the device:
            # the next one finds its mirrors there
            tail.device_pad_mask()
            for key in (entry.tail.device if entry.tail is not None
                        else ()):
                if key == "__ts":
                    tail.device_ts()
                elif key.startswith("f:"):
                    tail.device_field(key[2:])
                elif key.startswith("v:") and \
                        tail.fields[key[2:]][1] is not None:
                    tail.device_valid(key[2:])
        return base, tail

    def _merged(self, base: MergedScan, rows: _Rows) -> MergedScan:
        """A new base: `rows` merged into the base's. Every column is
        copied once; the result has no mirror and no compiled launch."""
        from ..common.telemetry import increment_counter
        increment_counter("scan_cache_merges")
        out = _merge_rows(_Rows(
            base.series_ids, base.ts,
            base.seq if base.seq is not None
            else np.zeros(base.num_rows, np.int64), base.fields), rows)
        return MergedScan(out.sids, out.ts, out.fields, base.series_dict,
                          int(out.ts.min()) if len(out) else 0,
                          seq=out.seq, count_uploads=True)

    def _delta(self, region, v, entry: _CacheEntry,
               visible: int) -> Optional[_Rows]:
        """The rows with sequences in (entry.visible, visible], from the
        memtables and from SSTs the entry has not seen, sorted by
        (series, ts), the newest version of a key kept."""
        from ..datatypes.vector import null_column
        schema = v.schema
        field_names = [c.name for c in schema.field_columns()]
        lo = entry.visible
        runs = []
        # memtable rows beyond the cached watermark
        for mt in v.memtables.all_memtables():
            ms = mt.snapshot()
            if ms.num_rows == 0 or ms.seq[-1] <= lo:
                continue
            # writes are serialised and replayed in order: a memtable's
            # sequences ascend, so the rows are one slice of it
            sel = slice(int(np.searchsorted(ms.seq, lo, side="right")),
                        int(np.searchsorted(ms.seq, visible,
                                            side="right")))
            if sel.start and ms.seq[sel.start - 1] > lo:
                sel = np.flatnonzero((ms.seq > lo) & (ms.seq <= visible))
            n = len(ms.ts[sel])
            if not n:
                continue
            fields = {}
            for name in field_names:
                if name in ms.fields:
                    d, vd = ms.fields[name]
                    fields[name] = (d[sel],
                                    vd[sel] if vd is not None else None)
                else:
                    fields[name] = null_column(
                        schema.column_schema(name).dtype, n)
            runs.append((ms.series_ids[sel], ms.ts[sel], ms.seq[sel],
                         ms.op_types[sel], fields))
        # SSTs not yet covered that carry rows beyond the watermark
        # (freshly flushed files whose max_sequence <= lo are already in
        # the cache via the memtable — skip reading them entirely)
        for meta in v.ssts.all_files():
            if meta.file_name in entry.sst_names or meta.max_sequence <= lo:
                continue
            sst = region.access_layer.read_sst(meta,
                                               projection=field_names)
            if sst.num_rows == 0:
                continue
            sel = (sst.seq > lo) & (sst.seq <= visible)
            if not sel.any():
                continue
            fields = {n: (d[sel], vd[sel] if vd is not None else None)
                      for n, (d, vd) in sst.fields.items()}
            runs.append((sst.series_ids[sel], sst.ts[sel], sst.seq[sel],
                         sst.op_types[sel], fields))
        if not runs:
            return None

        def cat(parts):
            return parts[0] if len(parts) == 1 else np.concatenate(parts)

        dsid = cat([r[0] for r in runs])
        dts = cat([r[1] for r in runs])
        dseq = cat([r[2] for r in runs])
        dop = cat([r[3] for r in runs])
        order = np.lexsort((dseq, dts, dsid))
        dsid, dts = dsid[order], dts[order]
        # within the delta the newest version of each (sid, ts) stays
        newest = np.ones(len(order), dtype=bool)
        newest[:-1] = (dsid[1:] != dsid[:-1]) | (dts[1:] != dts[:-1])
        if not newest.all():
            order, dsid, dts = order[newest], dsid[newest], dts[newest]
        vals = {name: cat([r[4][name][0] for r in runs])
                for name in field_names}
        valids = {}
        for name in field_names:
            parts = [r[4][name][1] for r in runs]
            valids[name] = None if all(x is None for x in parts) else cat(
                [x if x is not None else np.ones(len(r[0]), dtype=bool)
                 for x, r in zip(parts, runs)])
        block = None
        if field_names and \
                all(a.dtype == np.float64 for a in vals.values()):
            # TSBS's and a metric table's shape: every field a DOUBLE
            given = [a for a in valids.values() if a is not None]
            if not given or np.stack(given, axis=1).all():
                block = np.stack([vals[n] for n in field_names],
                                 axis=1)[order]
        if block is not None:
            fields = _block_fields(field_names, block)
        else:
            fields = {}
            for name in field_names:
                valid = valids[name]
                if valid is not None:
                    valid = valid[order]
                    if valid.all():
                        valid = None
                fields[name] = (vals[name][order], valid)
        deleted = dop[order] != 0
        return _Rows(dsid.astype(np.int32, copy=False), dts, dseq[order],
                     fields, deleted if deleted.any() else None, block)


def _unmerged_from(v, entry: _CacheEntry) -> int:
    """A lower bound of the timestamps of the rows this version holds
    beyond the entry's watermark, from what memtables and file metas
    record (a memtable's span covers its merged rows too: a bound, not
    the minimum); the largest int where there is none."""
    lo = np.iinfo(np.int64).max
    for mt in v.memtables.all_memtables():
        span = mt.time_range()
        if span is not None and mt.num_rows:
            lo = min(lo, span[0])
    for meta in v.ssts.all_files():
        if meta.file_name not in entry.sst_names and \
                meta.max_sequence > entry.visible:
            span = meta.time_range
            lo = min(lo, span[0] if span is not None else -lo)
    return int(lo)


def _tail_rows(tail: MergedScan) -> _Rows:
    n = tail.valid_rows
    return _Rows(tail.series_ids[:n], tail.ts[:n], tail.seq, tail.fields,
                 block=tail.block)


def _make_tail(rows: _Rows, base: MergedScan) -> MergedScan:
    """The tail scan over `rows` for this base: series ids and times
    padded to the base's tail capacity by repeating the last row (the
    padding joins the last run, as a padded slice's does), fields and
    sequences kept at their length (`MergedScan._put` pads a mirror)."""
    n, cap = len(rows), tail_capacity(base.num_rows)

    def padded(a):
        out = np.empty(cap, dtype=a.dtype)
        out[:n] = a
        out[n:] = a[n - 1]
        return out

    lo, hi = int(rows.ts.min()), int(rows.ts.max())
    return MergedScan(padded(rows.sids), padded(rows.ts), rows.fields,
                      base.series_dict, lo, seq=rows.seq, valid_rows=n,
                      pinned=True, count_uploads=True, ts_min=lo, ts_max=hi,
                      block=rows.block, programs=base.tail_programs,
                      base=base)


def _series_firsts(sids: np.ndarray) -> np.ndarray:
    """The first row of every series of a sorted series-id column."""
    return np.flatnonzero(np.concatenate([[True], sids[1:] != sids[:-1]]))


def _base_lasts(base: MergedScan, sids: np.ndarray):
    """-> (at, has): the base's last row of each of these series, and
    whether the base holds the series at all. One search a series."""
    hi = np.searchsorted(base.series_ids, sids, side="right")
    at = np.maximum(hi - 1, 0)
    return at, (hi > 0) & (base.series_ids[at] == sids) \
        if base.num_rows else np.zeros(len(sids), dtype=bool)


def _seam(base: MergedScan, name: str, counter: bool, sids: np.ndarray,
          v: np.ndarray, d: np.ndarray) -> None:
    """A tail's per-sample differences `d` (of its values `v`, sorted by
    `sids` then time) made those of one scan with its base: a series'
    first sample here takes its difference from the series' last sample
    in the base, with the reset rule, in float64 (a counter at 2.6e14
    keeps its scrape's growth; the f32 `first` / `last` of two partials
    would not). One pair a series the base holds; a series the base has
    never seen keeps 0, as a scan's first sample does."""
    from ..common.telemetry import increment_counter
    first = _series_firsts(sids)
    at, has = _base_lasts(base, sids[first])
    first, at = first[has], at[has]
    prev = base.fields[name][0][at].astype(np.float64, copy=False)
    d[first] = run_diffs(v[first], prev, "increase" if counter else "delta")
    increment_counter("scan_seam_pairs", len(first))


def _seam_fits(base: MergedScan, tail: MergedScan, plan: "TpuPlan") -> bool:
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


SCAN_CACHE = _ScanCache()


# ---------------------------------------------------------------------------
# concurrent scan fusion: single-flight over identical resident scans
# ---------------------------------------------------------------------------

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
        from ..common.locks import TrackedLock
        from ..common.tracking import tracked_state
        self._lock = TrackedLock("query.scan_fusion")
        self._inflight: Dict[tuple, _FlightEntry] = tracked_state(
            {}, "query.scan_fusion.inflight")

    def execute(self, region, table, plan: "TpuPlan"):
        from ..common import exec_stats, process_list
        from ..common.telemetry import increment_counter
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
        import time as _time
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
    def _key(region, plan: "TpuPlan") -> Optional[tuple]:
        vc = getattr(region, "version_control", None)
        if vc is None:
            return None
        # fingerprint once per PLAN object, not once per region: a
        # multi-region scan serializes the identical plan only once
        fp = getattr(plan, "_fusion_fp", None)
        if fp is None:
            try:
                from .plan_codec import plan_to_dict
                import json
                fp = json.dumps(plan_to_dict(plan), sort_keys=True,
                                default=str)
            except Exception:  # noqa: BLE001 — unshippable: no fusion
                from ..common.telemetry import increment_counter
                increment_counter("scan_fusion_unfingerprintable")
                fp = False
            plan._fusion_fp = fp
        if fp is False:
            return None
        return (region.uid, vc.committed_sequence,
                getattr(region, "retraction_epoch", 0), fp)


SCAN_FLIGHTS = _ScanFlightMap()


# ---------------------------------------------------------------------------
# plan
# ---------------------------------------------------------------------------

@dataclass
class TagGroup:
    name: str                         # tag column name
    tag_index: int


@dataclass
class BucketGroup:
    stride_ms: int
    origin: int
    expr_key: str                     # expr_name of the bucket expression


@dataclass
class FieldFilter:
    column: str
    op: str                           # eq/ne/lt/le/gt/ge
    value: float


@dataclass
class Moment:
    op: str                           # kernel op
    column: Optional[str]             # field name; None = row count
    slot: str


#: moment ops whose per-run partial is an encoded sketch (bytes), not a
#: number — built on the host, merged by _finalize through the codec
SKETCH_MOMENT_OPS = frozenset({"distinct", "tdigest"})

#: moment ops over adjacent samples of a run, PromQL's raw window growth:
#: `increase` sums the reset-aware differences between a run's adjacent
#: valid samples (`v - prev`, or `v` where a counter restarted below
#: `prev`; what rate / increase extrapolate), `delta` the plain ones
#: (last - first, summed so that f32 keeps its digits). The device
#: reduces them as the kernels' `growth` of `MergedScan.device_run_diffs`
#: (the sum of a run's differences but its first sample's, which reaches
#: back before the run); the host reducers compute them in float64. Partials of one group are
#: time-disjoint slices of one series: they add up, plus the difference
#: across each slice boundary (`_finalize`, which reads the companion
#: first / last / min_ts moments the lowering always asks for)
RUN_DIFF_MOMENT_OPS = frozenset({"increase", "delta"})


def run_diffs(cur, prev, op: str):
    """Adjacent-sample differences for a RUN_DIFF_MOMENT_OPS op."""
    d = cur - prev
    return np.where(cur < prev, cur, d) if op == "increase" else d


@dataclass
class TpuPlan:
    tag_groups: List[TagGroup]
    bucket: Optional[BucketGroup]
    moments: List[Moment]
    finals: List[Tuple[str, str, List[str]]]  # (slot, final op, moment slots)
    time_lo: Optional[int]
    time_hi: Optional[int]
    tag_predicates: List[Expr]
    field_filters: List[FieldFilter]
    #: arithmetic agg-arg expressions keyed by their moment "column"
    #: name (expr_name): `sum(a*b)` moments over a virtual column that
    #: each region evaluates from its stored fields before momenting
    field_exprs: Dict[str, Expr] = field(default_factory=dict)
    #: literal extras per final slot (approx_percentile's p)
    agg_params: Dict[str, tuple] = field(default_factory=dict)

    def describe(self) -> str:
        gs = [t.name for t in self.tag_groups]
        if self.bucket:
            gs.append(f"time_bucket({self.bucket.stride_ms}ms)")
        ops = [f"{op}" for _, op, _ in self.finals]
        return f"groups=[{', '.join(gs)}] aggs=[{', '.join(ops)}]"


def plan_needs_host(plan: "TpuPlan") -> bool:
    """Whether this plan's moments must reduce on the host: sketch
    partials (distinct/t-digest have no device kernel) and virtual
    expression columns both do. The partial-frame ALGEBRA is unchanged —
    host partials fold exactly like device partials."""
    return bool(plan.field_exprs) or \
        any(m.op in SKETCH_MOMENT_OPS for m in plan.moments)


def plan_scan_columns(plan: "TpuPlan", schema) -> List[str]:
    """Base STORED columns a region scan must project for this plan:
    plain moment columns plus every field a virtual expression column
    references (tags ride the series ids, never the projection)."""
    tag_names = set(schema.tag_names())
    cols: set = set()
    for m in plan.moments:
        if m.column is None:
            continue
        if m.column in plan.field_exprs:
            cols |= _refs(plan.field_exprs[m.column])
        elif m.column not in tag_names:
            cols.add(m.column)
    cols |= {ff.column for ff in plan.field_filters}
    return sorted(cols)


def moment_input(m: Moment, plan: TpuPlan, fields: Dict, sids, ts, sd,
                 cache: Optional[dict] = None):
    """(values, validity) for one moment's input: a stored field, the
    time index, a tag column (decoded per row), or a registered
    arithmetic expression evaluated over the stored fields — the ONE
    resolution both host reducers share, so streamed, resident and
    indexed partials cannot disagree about what `sum(a*b)` means."""
    col = m.column
    if cache is not None and col in cache:
        return cache[col]
    if col in plan.field_exprs:
        base = {}
        for name in sorted(_refs(plan.field_exprs[col])):
            d, vd = fields[name]
            if d.dtype == object:
                raise UnsupportedError(
                    f"expression aggregate over non-numeric {name!r}")
            arr = d.astype(np.float64, copy=vd is not None)
            if vd is not None:
                arr[~vd] = np.nan        # pandas null convention, so the
            base[name] = arr             # expr semantics == the fallback
        ev = Evaluator(pd.DataFrame(base))
        v = ev.eval(plan.field_exprs[col])
        vals = v.to_numpy(dtype=np.float64) if isinstance(v, pd.Series) \
            else np.asarray(v, dtype=np.float64)
        if vals.ndim == 0:
            vals = np.full(len(ts), float(vals))
        valid = ~np.isnan(vals)
        out = (vals, None if valid.all() else valid)
    elif col in fields:
        out = fields[col]
    elif sd is not None and col in tuple(getattr(sd, "tag_names", ())):
        idx = tuple(sd.tag_names).index(col)
        out = (sd.decode_tag_column(np.asarray(sids, dtype=np.int32),
                                    idx), None)
    else:
        out = (ts, None)                 # the time index
    if cache is not None:
        cache[col] = out
    return out


def sketch_run_column(op: str, vals: np.ndarray,
                      valid: Optional[np.ndarray],
                      starts: np.ndarray, n: int) -> np.ndarray:
    """Encoded sketch partial per run: object column of codec frames,
    one per (sid [, bucket]) run — the sketch twin of a reduceat."""
    from .sketches import DistinctSketch, TDigest, encode_sketch
    ends = np.append(starts[1:], n)
    out = np.empty(len(starts), dtype=object)
    for i in range(len(starts)):
        seg = slice(int(starts[i]), int(ends[i]))
        v = vals[seg]
        if valid is not None:
            v = v[valid[seg]]
        if op == "distinct":
            sk = DistinctSketch.from_values(v)
        else:
            sk = TDigest.from_values(np.asarray(v, dtype=np.float64)) \
                if v.dtype != object else TDigest.from_values(
                    np.asarray(list(v), dtype=np.float64))
        out[i] = encode_sketch(sk)
    return out


def _conjuncts(e: Optional[Expr]) -> List[Expr]:
    if e is None:
        return []
    if isinstance(e, BinaryOp) and e.op == "and":
        return _conjuncts(e.left) + _conjuncts(e.right)
    return [e]


def _refs(e: Expr) -> set:
    from .planner import _walk_columns
    out: set = set()
    _walk_columns(e, out)
    return out


def _literal_num(e: Expr):
    if isinstance(e, Literal) and isinstance(e.value, (int, float)) and \
            not isinstance(e.value, bool):
        return e.value
    if isinstance(e, UnaryOp) and e.op == "-":
        v = _literal_num(e.operand)
        return -v if v is not None else None
    return None


_ARITH_OPS = frozenset({"+", "-", "*", "/"})


def _is_expr_arg(e: Expr, field_names: set, schema) -> bool:
    """Arithmetic over numeric FIELD columns and numeric literals, with
    at least one operator — the agg-argument shapes each region can
    evaluate into a virtual moment column (`sum(a*b)`, `avg(a/b)`)."""
    if not isinstance(e, (BinaryOp, UnaryOp)):
        return False

    def ok(x: Expr) -> bool:
        if isinstance(x, Column):
            if x.name not in field_names:
                return False
            cs = schema.column_schema(x.name)
            return not (cs.dtype.is_string or cs.dtype.is_binary)
        if isinstance(x, Literal):
            return isinstance(x.value, (int, float)) and \
                not isinstance(x.value, bool)
        if isinstance(x, UnaryOp):
            return x.op == "-" and ok(x.operand)
        if isinstance(x, BinaryOp):
            return x.op in _ARITH_OPS and ok(x.left) and ok(x.right)
        return False

    return ok(e)


def standard_final(op: str, col: Optional[str], moment):
    """(final op, moment slots) for one standard aggregate through the
    `moment(op, column) -> slot` dedupe closure — the ONE op→moment
    mapping SQL planning (plan_for), PromQL lowering (promql/lowering)
    and flow compilation (flow/lowering) share, so no front end can
    teach the fold a private dialect. A count moment rides along with
    sum/min/max so empty groups finalize to NULL, not 0."""
    if op == "count":
        return "count", [moment("count", col)]
    if op in ("sum", "avg"):
        return op, [moment("sum", col), moment("count", col)]
    if op in ("min", "max"):
        return op, [moment(op, col), moment("count", col)]
    if op in ("stddev", "variance"):
        return op, [moment("sum", col), moment("sum_sq", col),
                    moment("count", col)]
    if op in ("first", "last"):
        mts = moment("min_ts" if op == "first" else "max_ts", col)
        return op, [moment(op, col), mts]
    return None


def plan_for(table, a: Analysis, query: Query) -> Optional[TpuPlan]:
    """Return a TpuPlan if (table, query) fits the fast-path shape."""
    if table is None or not a.is_aggregate or query.joins:
        return None
    if a.window_calls:
        # window slots evaluate on the post-aggregate frame in the
        # fallback engine (query/window.py); the device plan has no
        # WindowAggExec analogue yet
        return None
    if not hasattr(table, "regions"):
        return None  # only region-backed (mito) tables have the SoA path
    schema = table.schema
    tc = schema.timestamp_column
    tag_names = schema.tag_names()
    field_names = set(schema.field_names())

    # group exprs: tags and at most one time bucket
    tag_groups: List[TagGroup] = []
    bucket: Optional[BucketGroup] = None
    for g in a.group_exprs:
        if isinstance(g, Column) and g.name in tag_names:
            tag_groups.append(TagGroup(g.name, tag_names.index(g.name)))
            continue
        b = _match_bucket(g, tc.name if tc else None)
        if b is not None and bucket is None:
            bucket = b
            continue
        return None

    # aggregates → moments
    from .sketches import exact_distinct_forced
    is_pushdown = hasattr(table, "execute_tpu_plan")
    if is_pushdown and not _PARTIAL_PUSHDOWN[0]:
        # SET dist_partial_agg = 0: no pushdown PLAN at all, so EXPLAIN
        # (CpuAggregateExec) and execution (raw-row scatter + CPU
        # fallback) render the same decision
        return None
    moments: List[Moment] = []
    finals: List[Tuple[str, str, List[str]]] = []
    field_exprs: Dict[str, Expr] = {}
    agg_params: Dict[str, tuple] = {}
    seen: Dict[tuple, str] = {}

    def moment(op: str, column: Optional[str]) -> str:
        k = (op, column)
        if k in seen:
            return seen[k]
        slot = f"__m{len(moments)}"
        moments.append(Moment(op, column, slot))
        seen[k] = slot
        return slot

    for call in a.agg_calls:
        op = call.op
        if op not in TPU_AGGREGATES and op not in SKETCH_AGGREGATES:
            return None
        if call.distinct and (op != "count" or not is_pushdown or
                              exact_distinct_forced()):
            # distinct rides the sketch partial only where it pays — the
            # distributed pushdown (a standalone table keeps the exact
            # fallback), and never under SET exact_distinct = 1
            return None
        if call.arg is None:
            if op != "count" or call.distinct:
                return None
            finals.append((call.slot, "count", [moment("count", None)]))
            continue
        # distinct sketches take any value type (sets of strings are
        # sets); everything else needs numbers
        sketchy = call.distinct or op == "approx_distinct"
        if isinstance(call.arg, Column):
            col = call.arg.name
            if col == (tc.name if tc else None):
                pass                            # the time index
            elif col in field_names:
                cs = schema.column_schema(col)
                if (cs.dtype.is_string or cs.dtype.is_binary) and \
                        op != "count" and not sketchy:
                    return None
            elif col in tag_names and sketchy:
                pass          # distinct over a tag: decoded per series
            else:
                return None
        else:
            if not _is_expr_arg(call.arg, field_names, schema):
                return None
            col = expr_name(call.arg)
            field_exprs[col] = call.arg
        if call.distinct:                       # count(DISTINCT x)
            finals.append((call.slot, "count_distinct",
                           [moment("distinct", col)]))
            continue
        if op == "approx_distinct":
            finals.append((call.slot, "approx_distinct",
                           [moment("distinct", col)]))
            continue
        if op in ("approx_percentile", "median"):
            if op == "approx_percentile":
                if len(call.params) != 1 or \
                        not isinstance(call.params[0], (int, float)) or \
                        isinstance(call.params[0], bool) or \
                        not 0 <= float(call.params[0]) <= 100:
                    return None     # the fallback raises the typed error
                p = float(call.params[0])
            else:
                p = 50.0
            finals.append((call.slot, "approx_percentile",
                           [moment("tdigest", col)]))
            agg_params[call.slot] = (p,)
            continue
        std = standard_final(op, col, moment)
        if std is None:
            return None
        finals.append((call.slot, std[0], std[1]))

    # WHERE decomposition
    time_lo = time_hi = None
    tag_predicates: List[Expr] = []
    field_filters: List[FieldFilter] = []
    for c in _conjuncts(query.where):
        refs = _refs(c)
        if refs and refs <= set(tag_names):
            tag_predicates.append(c)
            continue
        if tc is not None and refs == {tc.name}:
            rng = _match_time_pred(c, tc.name)
            if rng is None:
                return None
            lo, hi = rng
            if lo is not None:
                time_lo = lo if time_lo is None else max(time_lo, lo)
            if hi is not None:
                time_hi = hi if time_hi is None else min(time_hi, hi)
            continue
        ff = _match_field_pred(c, field_names)
        if ff is None:
            return None
        field_filters.append(ff)

    return TpuPlan(tag_groups, bucket, moments, finals, time_lo, time_hi,
                   tag_predicates, field_filters, field_exprs, agg_params)


def _match_bucket(e: Expr, ts_name: Optional[str]) -> Optional[BucketGroup]:
    """date_bin(INTERVAL, ts [, origin]) / date_trunc('unit', ts)."""
    if ts_name is None or not isinstance(e, FunctionCall):
        return None
    if e.name == "date_bin" and len(e.args) >= 2:
        stride = None
        if isinstance(e.args[0], Interval):
            stride = parse_interval_ms(e.args[0].text)
        elif _literal_num(e.args[0]) is not None:
            stride = int(_literal_num(e.args[0]))
        if stride is None or stride <= 0:
            return None
        if not (isinstance(e.args[1], Column) and e.args[1].name == ts_name):
            return None
        origin = 0
        if len(e.args) >= 3:
            o = _literal_num(e.args[2])
            if o is None:
                return None
            origin = int(o)
        return BucketGroup(stride, origin, expr_name(e))
    if e.name == "date_trunc" and len(e.args) == 2:
        from .functions import _TRUNC_MS
        if not isinstance(e.args[0], Literal):
            return None
        unit = str(e.args[0].value).lower()
        if unit not in _TRUNC_MS:
            return None
        if not (isinstance(e.args[1], Column) and e.args[1].name == ts_name):
            return None
        from .functions import _WEEK_ORIGIN_MS
        origin = _WEEK_ORIGIN_MS if unit == "week" else 0
        return BucketGroup(_TRUNC_MS[unit], origin, expr_name(e))
    return None


def _match_time_pred(e: Expr, ts_name: str):
    import math as _math
    if isinstance(e, Between):
        lo, hi = _literal_num(e.low), _literal_num(e.high)
        if e.negated or lo is None or hi is None:
            return None
        # inclusive range: directional rounding for fractional bounds
        return _math.ceil(lo), _math.floor(hi) + 1
    if not isinstance(e, BinaryOp):
        return None
    op = e.op
    if isinstance(e.left, Column) and e.left.name == ts_name:
        v = _literal_num(e.right)
    elif isinstance(e.right, Column) and e.right.name == ts_name:
        v = _literal_num(e.left)
        op = {"<": ">", "<=": ">=", ">": "<", ">=": "<="}.get(op, op)
    else:
        return None
    if v is None:
        return None
    # timestamps are integral: round fractional bounds toward the predicate
    if op == "<":
        return None, _math.ceil(v)          # ts < 10.5 ≡ ts < 11
    if op == "<=":
        return None, _math.floor(v) + 1
    if op == ">":
        return _math.floor(v) + 1, None     # ts > 10.5 ≡ ts >= 11
    if op == ">=":
        return _math.ceil(v), None
    if op == "=":
        if v != int(v):
            return 0, 0                     # fractional equality: empty
        return int(v), int(v) + 1
    return None


def _match_field_pred(e: Expr, field_names: set) -> Optional[FieldFilter]:
    if not isinstance(e, BinaryOp) or e.op not in _CMP_OPS:
        return None
    if isinstance(e.left, Column) and e.left.name in field_names:
        v = _literal_num(e.right)
        if v is None:
            return None
        return FieldFilter(e.left.name, _CMP_OPS[e.op], float(v))
    if isinstance(e.right, Column) and e.right.name in field_names:
        v = _literal_num(e.left)
        if v is None:
            return None
        op = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le"}.get(
            _CMP_OPS[e.op], _CMP_OPS[e.op])
        return FieldFilter(e.right.name, op, float(v))
    return None


# ---------------------------------------------------------------------------
# execution
# ---------------------------------------------------------------------------

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
        scan = SCAN_CACHE.get(region)
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


#: SET dist_partial_agg — kill switch for the distributed partial
#: pushdown: 0 routes aggregate statements over DistTables through the
#: raw-row scatter instead (tests/test_sketches.py takes its reference
#: answers from it)
_PARTIAL_PUSHDOWN = [_env_flag("GREPTIME_DIST_PARTIAL_AGG", True)]


def configure_partial_pushdown(*, enabled: Optional[bool] = None) -> None:
    if enabled is not None:
        _PARTIAL_PUSHDOWN[0] = bool(enabled)


def try_execute(table, a: Analysis, query: Query) -> Optional[pd.DataFrame]:
    from ..common import exec_stats

    with exec_stats.stage("plan"):
        plan = plan_for(table, a, query)
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
            from ..common.telemetry import increment_counter
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
    from . import stream_exec
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
    from ..storage.index import sst_index_enabled
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
    if SCAN_CACHE.cached(region):
        return None
    return sids


def _indexed_point_frames(region, table, plan: "TpuPlan",
                          sids: np.ndarray) -> List[pd.DataFrame]:
    """Partial moment frames for one region via the SST secondary
    index: scan only the files/row groups that may hold the candidate
    series (RegionSnapshot.scan's sid_set tier), merge-dedup the
    surviving rows (exact MVCC), and reduce on the host with the same
    segment arithmetic the streamed path uses — so _finalize folds
    these partials like any others. Never touches the scan cache: a
    point query on a cold many-SST region must not pay (or pin) full
    residency for a handful of series."""
    import time as _time

    from ..common import exec_stats
    from ..common.time import TimestampRange
    from ..storage.region import ScanProfile
    from . import stream_exec

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
    from . import stream_exec
    return stream_exec.region_estimated_rows(region) > \
        stream_exec.stream_threshold_rows() or \
        (SCAN_CACHE.budget_bytes > 0 and
         stream_exec.region_estimated_bytes(region) >
         SCAN_CACHE.budget_bytes // 2)


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
    from ..common import exec_stats
    from . import stream_exec
    if regions is None:
        regions = list(table.regions.values())
    else:
        want = set(regions)
        missing = want - set(table.regions)
        if missing:
            # a pruned aggregate naming regions this node no longer hosts
            # must not silently reduce a partial set — typed so the
            # DistTable refreshes its route and retries
            from ..errors import StaleRouteError
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
    from ..common import process_list
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


def _execute_region(region, table, plan: TpuPlan) -> Optional[pd.DataFrame]:
    import time as _time

    from ..common import exec_stats
    from ..common.telemetry import span
    from ..storage.region import ScanProfile

    prof = ScanProfile(path="resident")
    _t0 = _time.perf_counter()
    with span("region_scan", region=region.name, path="resident"):
        with exec_stats.stage("scan_prep"):
            if _wants_one_scan(plan):
                scan, tail = SCAN_CACHE.get(region), None
            else:
                scan, tail = SCAN_CACHE.get_parts(region, plan.time_hi)
                if tail is not None and _grows(plan) \
                        and not _outside(plan, tail) \
                        and not _seam_fits(scan, tail, plan):
                    # a late row under a window's growth: one scan
                    # (counted: `scan_cache_merges`)
                    exec_stats.record("scan_prep", seam="merged")
                    scan, tail = SCAN_CACHE.get(region), None
        prof.mark("scan_prep", _time.perf_counter() - _t0)
        outcome = SCAN_CACHE.last_outcome() or "full"
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
                        and not _wants_one_scan(plan):
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


def _base_and_tail_frame(base: Optional["_RunPartial"],
                         tail: Optional["_RunPartial"],
                         plan: "TpuPlan") -> Optional[pd.DataFrame]:
    """One partial frame of the two launches. The partials of one group
    are disjoint in keys (a tail holds no (series, time) its base holds),
    not in time: a tail also holds rows that arrived late into the base's
    span. They fold by run before the frame is made (`first` / `last` by
    their companion times), or, where that cannot be, in `_finalize` like
    any two partials."""
    from ..common import exec_stats
    with exec_stats.stage("reduce.collect"):
        parts = [p for p in (base, tail) if p is not None]
        if len(parts) == 2:
            folded = _fold_runs(base, tail, plan)
            parts = [folded] if folded is not None else parts
        frames = [_partial_frame(p, plan) for p in parts]
        return None if not frames else frames[0] if len(frames) == 1 \
            else pd.concat(frames, ignore_index=True)


def _wants_one_scan(plan: "TpuPlan") -> bool:
    """Plans that reduce the region's rows as one sorted scan, a tail
    merged into the base first: the host reducers (sketch / expression
    moments walk the rows)."""
    return plan_needs_host(plan)


def _grows(plan: "TpuPlan") -> bool:
    """The plan holds a window's growth (`RUN_DIFF_MOMENT_OPS`): over a
    base and its tail it is the sum of the two launches' and the seam's
    (`MergedScan.device_run_diffs`, `_fold_runs`), where `_seam_fits`."""
    return any(m.op in RUN_DIFF_MOMENT_OPS for m in plan.moments)


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


def _warm_tail_programs(base: MergedScan, schema, plan: "TpuPlan") -> None:
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
    import jax
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
    from ..common import exec_stats
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


def _launch_for_scan(scan: MergedScan, schema, plan: TpuPlan, part):
    """-> (launched or None, "narrow" | "full", the selection or None):
    the resident reduce of one scan, launched."""
    from . import scan_narrow
    with part("mask"):
        sel = scan_narrow.select(scan, schema, plan)
    n_ranges, padded_rows = (None, 0) if sel is None \
        else (sel.n_ranges, sel.padded_rows)
    path = scan_narrow.scan_read_path(scan.num_rows, n_ranges, padded_rows)
    if path == "narrow":
        return scan_narrow.launch(scan, schema, plan, sel, part), path, sel
    return _launch_scan_kernel(scan, schema, plan, part, sel), path, sel


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
        from .stream_exec import _host_partial_frame
        return _host_partial_frame(scan, None, plan, scan.series_dict)
    import time as _time

    import jax

    from ..common import exec_stats
    from ..common.telemetry import increment_counter
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
    with _reduce_part("fetch"):     # blocked on the device, then D2H
        counts, res_np = jax.device_get((launched.counts,
                                         list(launched.results)))
    if launched.warm:
        _note_device_query_time(_time.perf_counter() - t0)
    with _reduce_part("collect"):
        return (_collect_runs if runs else _collect_moment_frame)(
            launched, plan, counts, res_np)


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
    from . import scan_narrow

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
            from ..common import exec_stats
            from ..common.telemetry import increment_counter
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
        d_ts = scan.device_ts()
        # a statement that nothing but time filters starts from the scan's
        # resident mask, all true or true on the valid rows of a padded
        # scan or a tail, and uploads none (n bool bytes a statement: 17 MB
        # at 17M rows); its time range is `window`
        window = _device_window(plan, scan)
        if mask is None:
            d_mask = scan.device_pad_mask() \
                if scan.valid_rows is not None \
                else scan.device_valid_all()
        else:
            d_mask = scan.upload(mask)

        values = []
        col_masks = []
        for _op, field_read, masked_by in reads:
            values.append(d_ts if field_read is None
                          else _device_column(scan, field_read))
            col_masks.append(None if masked_by is None
                             else scan.device_valid(masked_by))
        # what the moments share, told to the program statically: each
        # mirror a parameter once, a column without a NULL no validity
        values, value_ix = distinct_arrays(values, d_ts)
        col_masks, mask_ix = distinct_arrays(col_masks, None)

    if lay.grid is not None:
        # run ids nobody laid out: a label a row, made where the rows are
        d_rid = scan_narrow.run_labels(scan.device_sids(), d_ts, lay.grid)
    elif lay.rid is not None:
        with part("upload"):
            d_rid = scan.upload(lay.rid)
    else:
        d_rid = d_ts
    with part("launch"):
        out = _run_program(
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
                     lay.table_runs, mask is not None, lay.num_groups)


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
    from . import scan_narrow
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
        nbucket, run_ends, rid, seg_len_k = _segment_layout(
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
    from . import scan_narrow
    from ..ops.kernels import seg_len_bucket
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
    axis (`_group_bucket`): first / last / growth always, min / max above
    the high-cardinality threshold."""
    from ..ops.kernels import _SEG_HIGH_CARD_THRESHOLD
    return any(op in ("first", "last", "growth") for op in ops) or \
        (num_groups > _SEG_HIGH_CARD_THRESHOLD
         and any(op in ("min", "max") for op in ops))


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
    # with host ends the kernel reads gids for first/last (arg-extreme
    # tie-break) and for high-cardinality min/max (the shift-doubling
    # kernel's same-segment guard); for every other op ts stands in
    # for shape and both the O(n) rid cumsum and its upload are
    # skipped
    from ..ops.kernels import seg_len_bucket
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
    import pyarrow as pa
    return pd.Series(pa.DictionaryArray.from_arrays(
        pa.array(ids, type=pa.int32()),
        pa.array(values, type=pa.string())).dictionary_decode(),
        dtype="str")


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
    from .sketches import decode_sketch, encode_sketch
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
            from .sketches import decode_sketch
            out[slot] = merged[mslots[0]].map(
                lambda b: 0 if b is None
                else decode_sketch(b).result()).astype(np.int64)
        elif op == "approx_percentile":
            from .sketches import decode_sketch
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
