"""The comparison that decides `correct`: answers against the float64
reference (after `chip_smoke.py`'s `compare`, PR 21), and the executed
dispatch from EXPLAIN ANALYZE's stage rows."""

from __future__ import annotations

import numpy as np


def compare(got: dict, want: dict, tol: dict) -> dict:
    """got/want: {key: [floats]}. -> {"ok", "rows", "max_abs_err",
    "max_rel_err", "why"}; never raises on a wrong answer."""
    if set(got) != set(want):
        missing = sorted(set(want) - set(got))[:3]
        extra = sorted(set(got) - set(want))[:3]
        return {"ok": False, "rows": len(got), "max_abs_err": None,
                "max_rel_err": None,
                "why": f"result keys differ: {len(got)} rows vs "
                       f"{len(want)} expected; missing {missing}, "
                       f"unexpected {extra}"}
    keys = sorted(want)
    g = np.array([got[k] for k in keys], dtype=np.float64)
    w = np.array([want[k] for k in keys], dtype=np.float64)
    if g.shape != w.shape:
        return {"ok": False, "rows": len(keys), "max_abs_err": None,
                "max_rel_err": None, "why": f"shape {g.shape} vs {w.shape}"}
    err = np.abs(g - w)
    bound = tol["atol"] + tol["rtol"] * np.abs(w)
    rel = err / np.maximum(np.abs(w), 1e-300)
    out = {"ok": True, "rows": len(keys),
           "max_abs_err": float(err.max(initial=0.0)),
           "max_rel_err": float(rel.max(initial=0.0)), "why": ""}
    if not np.isfinite(g).all() or (err > bound).any():
        i = int(np.argmax(err - bound)) // max(g.shape[1], 1)
        out["ok"] = False
        out["why"] = (f"off beyond {tol} at {keys[i]}: got {g[i]}, "
                      f"want {w[i]}")
    return out


def compared_number(result: dict, tol: dict):
    """The one number a family is held to, beside its limit: the largest
    absolute error where the tolerance is absolute, else the largest
    relative error."""
    if tol["rtol"] == 0.0:
        return "max_abs_err", result["max_abs_err"], tol["atol"]
    return "max_rel_err", result["max_rel_err"], tol["rtol"]


def stages_of(rows) -> dict:
    """EXPLAIN ANALYZE rows -> {stage: {"rows", "elapsed_ms", "detail"}}."""
    out = {}
    for stage, nrows, _files, ms, detail in rows:
        out[str(stage)] = {"rows": int(nrows), "elapsed_ms": float(ms),
                           "detail": detail or ""}
    return out


def executed_dispatch(stages: dict):
    """The `dispatch` stage row's detail: what actually answered."""
    return stages.get("dispatch", {}).get("detail")


def bf16_round(values):
    """Round float64 values to the nearest bfloat16 (8 significant bits),
    ties to even: what a bf16 mirror of the column would hold."""
    v = np.asarray(values, dtype=np.float32)
    bits = v.view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32).astype(np.float64)
