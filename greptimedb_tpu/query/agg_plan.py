"""The aggregate plan: scan, filter, group by tags and / or one time
bucket, aggregate, as data (`TpuPlan`), and SQL's lowering into it
(`plan_for`). What only *names* a plan imports this module, never the
executor (`query/tpu_exec.py` has the map)."""

from __future__ import annotations

import math as _math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np
import pandas as pd

from ..errors import UnsupportedError
from ..sql.ast import (
    Between, BinaryOp, Column, Expr, FunctionCall, Interval, Literal, Query,
    UnaryOp,
)
from ..utils import env_flag as _env_flag
from .expr import Evaluator, expr_name
from .functions import (_TRUNC_MS, _WEEK_ORIGIN_MS, SKETCH_AGGREGATES,
                        TPU_AGGREGATES, parse_interval_ms)
from .planner import Analysis, _walk_columns
from .sketches import (DistinctSketch, TDigest, encode_sketch,
                       exact_distinct_forced)

_CMP_OPS = {"=": "eq", "!=": "ne", "<": "lt", "<=": "le", ">": "gt",
            ">=": "ge"}


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


#: SET dist_partial_agg — kill switch for the distributed partial
#: pushdown: 0 routes aggregate statements over DistTables through the
#: raw-row scatter instead (tests/test_sketches.py takes its reference
#: answers from it)
_PARTIAL_PUSHDOWN = [_env_flag("GREPTIME_DIST_PARTIAL_AGG", True)]


def configure_partial_pushdown(*, enabled: Optional[bool] = None) -> None:
    if enabled is not None:
        _PARTIAL_PUSHDOWN[0] = bool(enabled)


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
        if not isinstance(e.args[0], Literal):
            return None
        unit = str(e.args[0].value).lower()
        if unit not in _TRUNC_MS:
            return None
        if not (isinstance(e.args[1], Column) and e.args[1].name == ts_name):
            return None
        origin = _WEEK_ORIGIN_MS if unit == "week" else 0
        return BucketGroup(_TRUNC_MS[unit], origin, expr_name(e))
    return None


def _match_time_pred(e: Expr, ts_name: str):
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
