"""Plan shipping: serialize TPU aggregate plans (and the expression subset
they carry) for the router→worker boundary.

Reference behavior: src/common/substrait — `DFLogicalSubstraitConvertor`
encodes the pushed-down plan so the datanode can decode and execute it
against its local catalog (df_substrait.rs:31, consumed by
src/datanode/src/instance/grpc.rs:62-83). Here the shipped plan is the
TpuPlan (tag groups + time bucket + moments + predicates) — the unit of
aggregate pushdown — encoded as JSON-safe dicts.

Rolling upgrades: every front end (SQL, PromQL, flows) now ships plans
through this codec, so skew handling is uniform. Decode validates each
moment/final op against KNOWN_*_OPS and fails closed — an old datanode
rejects a plan carrying an op it predates (typed UnsupportedError, the
WIRE_UNSUPPORTED_MARKER survives Flight), and the frontend degrades
that one statement to the raw-row path for a correct (slower) answer.
Upgrade datanodes before frontends: the window where new plan shapes
degrade is exactly the rollout window. Adding an op = add it to the
reducers AND these sets in the same release; never reuse a name with
different semantics.
"""

from __future__ import annotations

from typing import Optional

from ..errors import UnsupportedError
from ..sql.ast import (
    Between, BinaryOp, Column, Expr, FunctionCall, InList, Interval, IsNull,
    Literal, UnaryOp,
)
from .agg_plan import BucketGroup, FieldFilter, Moment, TagGroup, TpuPlan

#: every moment op this build's reducers implement, and every final op
#: _finalize knows how to render. plan_from_dict VALIDATES against these
#: on decode so version skew fails closed: a datanode that predates a
#: new op rejects the plan with a typed UnsupportedError (carrying
#: WIRE_UNSUPPORTED_MARKER across Flight), the frontend degrades the
#: statement to the raw-row path, and no stale reducer ever folds a
#: moment it half-understands into a wrong answer.
KNOWN_MOMENT_OPS = frozenset({
    "sum", "sum_sq", "count", "min", "max", "first", "last",
    "min_ts", "max_ts", "distinct", "tdigest", "increase", "delta"})
KNOWN_FINAL_OPS = frozenset({
    "sum", "avg", "count", "min", "max", "first", "last", "stddev",
    "variance", "count_distinct", "approx_distinct", "approx_percentile",
    "moment"})

#: substring marker that survives Flight's string-flattened errors —
#: client/flight.py rebuilds UnsupportedError from it, the same scheme
#: StaleRouteError / OverloadedError use
WIRE_UNSUPPORTED_MARKER = "unsupported shipped plan"


def expr_to_dict(e: Optional[Expr]) -> Optional[dict]:
    if e is None:
        return None
    if isinstance(e, Literal):
        return {"k": "lit", "v": e.value}
    if isinstance(e, Column):
        return {"k": "col", "name": e.name}
    if isinstance(e, BinaryOp):
        return {"k": "bin", "op": e.op, "l": expr_to_dict(e.left),
                "r": expr_to_dict(e.right)}
    if isinstance(e, UnaryOp):
        return {"k": "un", "op": e.op, "e": expr_to_dict(e.operand)}
    if isinstance(e, InList):
        return {"k": "in", "e": expr_to_dict(e.expr), "neg": e.negated,
                "items": [expr_to_dict(i) for i in e.items]}
    if isinstance(e, Between):
        return {"k": "between", "e": expr_to_dict(e.expr),
                "neg": e.negated, "lo": expr_to_dict(e.low),
                "hi": expr_to_dict(e.high)}
    if isinstance(e, IsNull):
        return {"k": "isnull", "e": expr_to_dict(e.expr), "neg": e.negated}
    if isinstance(e, FunctionCall):
        return {"k": "fn", "name": e.name,
                "args": [expr_to_dict(a) for a in e.args]}
    if isinstance(e, Interval):
        return {"k": "interval", "text": e.text}
    raise UnsupportedError(f"cannot ship expression {type(e).__name__}")


def expr_from_dict(d: Optional[dict]) -> Optional[Expr]:
    if d is None:
        return None
    k = d["k"]
    if k == "lit":
        return Literal(d["v"])
    if k == "col":
        return Column(d["name"])
    if k == "bin":
        return BinaryOp(d["op"], expr_from_dict(d["l"]),
                        expr_from_dict(d["r"]))
    if k == "un":
        return UnaryOp(d["op"], expr_from_dict(d["e"]))
    if k == "in":
        return InList(expr_from_dict(d["e"]),
                      [expr_from_dict(i) for i in d["items"]], d["neg"])
    if k == "between":
        return Between(expr_from_dict(d["e"]), expr_from_dict(d["lo"]),
                       expr_from_dict(d["hi"]), d["neg"])
    if k == "isnull":
        return IsNull(expr_from_dict(d["e"]), d["neg"])
    if k == "fn":
        return FunctionCall(d["name"],
                            [expr_from_dict(a) for a in d["args"]])
    if k == "interval":
        return Interval(d["text"])
    raise UnsupportedError(f"unknown shipped expression kind {k!r}")


def plan_to_dict(plan: TpuPlan) -> dict:
    return {
        "tag_groups": [{"name": t.name, "tag_index": t.tag_index}
                       for t in plan.tag_groups],
        "bucket": None if plan.bucket is None else {
            "stride_ms": plan.bucket.stride_ms,
            "origin": plan.bucket.origin,
            "expr_key": plan.bucket.expr_key},
        "moments": [{"op": m.op, "column": m.column, "slot": m.slot}
                    for m in plan.moments],
        "finals": [[slot, op, list(mslots)]
                   for slot, op, mslots in plan.finals],
        "time_lo": plan.time_lo,
        "time_hi": plan.time_hi,
        "tag_predicates": [expr_to_dict(p) for p in plan.tag_predicates],
        "field_filters": [{"column": f.column, "op": f.op,
                           "value": f.value}
                          for f in plan.field_filters],
        # expression-arg moments + sketch finals (ISSUE 14): virtual
        # moment columns each datanode evaluates from its stored
        # fields, and per-final literal params (approx_percentile's p)
        "field_exprs": {k: expr_to_dict(e)
                        for k, e in plan.field_exprs.items()},
        "agg_params": {k: list(v) for k, v in plan.agg_params.items()},
    }


def plan_from_dict(d: dict) -> TpuPlan:
    for m in d["moments"]:
        if m["op"] not in KNOWN_MOMENT_OPS:
            raise UnsupportedError(
                f"{WIRE_UNSUPPORTED_MARKER}: moment op {m['op']!r} "
                f"(datanode predates it; upgrade datanodes first)")
    for _slot, op, _mslots in d["finals"]:
        if op not in KNOWN_FINAL_OPS:
            raise UnsupportedError(
                f"{WIRE_UNSUPPORTED_MARKER}: final op {op!r} "
                f"(datanode predates it; upgrade datanodes first)")
    return TpuPlan(
        tag_groups=[TagGroup(t["name"], t["tag_index"])
                    for t in d["tag_groups"]],
        bucket=None if d["bucket"] is None else BucketGroup(
            d["bucket"]["stride_ms"], d["bucket"]["origin"],
            d["bucket"]["expr_key"]),
        moments=[Moment(m["op"], m["column"], m["slot"])
                 for m in d["moments"]],
        finals=[(slot, op, list(mslots)) for slot, op, mslots in
                d["finals"]],
        time_lo=d["time_lo"],
        time_hi=d["time_hi"],
        tag_predicates=[expr_from_dict(p) for p in d["tag_predicates"]],
        field_filters=[FieldFilter(f["column"], f["op"], f["value"])
                       for f in d["field_filters"]],
        # .get: a NEW datanode tolerates a pre-sketch frontend's plans.
        # The reverse direction is NOT degradable — a pre-sketch
        # datanode would drop field_exprs and fail the scan — so roll
        # datanodes before frontends when upgrading across this codec
        field_exprs={k: expr_from_dict(e)
                     for k, e in (d.get("field_exprs") or {}).items()},
        agg_params={k: tuple(v)
                    for k, v in (d.get("agg_params") or {}).items()},
    )
