"""The numbers that decide ``correct``: gaps between what the timed path
produced and the plain reference, each against a limit of its own.

Every gap is relative and taken at its worst: over agents, entries and
iterations.
"""

from __future__ import annotations

import numpy as np

# name -> what it compares (printed beside the number)
NUMBERS = {
    "G_gap": "max_t max|G_t - G_t^ref| / max|G_t^ref|",
    "R_gap": "max_t max|R_t - R_t^ref| / max|R_t^ref|",
    "trG_gap": "max_t |tr G_t - tr G_t^ref| / tr G_t^ref",
    "obj_gap": "max_k |obj_k - obj_k^ref| / |obj_k^ref|",
    "UA_gap": "max_t max|U_t A_t - (U_t A_t)^ref| / max|(U_t A_t)^ref|",
    "pred_gap": "max_t ||H_t (U_t A_t - (U_t A_t)^ref)|| / ||H_t (U_t A_t)^ref||",
}


def _worst_rel(a, ref) -> float:
    a, ref = np.asarray(a, np.float64), np.asarray(ref, np.float64)
    a, ref = a.reshape(a.shape[0], -1), ref.reshape(ref.shape[0], -1)
    den = np.maximum(np.max(np.abs(ref), axis=1), 1e-30)
    return float(np.max(np.max(np.abs(a - ref), axis=1) / den))


def stats_gaps(G, R, G_ref, R_ref) -> dict:
    trG = np.trace(np.asarray(G, np.float64), axis1=1, axis2=2)
    trG_ref = np.trace(np.asarray(G_ref, np.float64), axis1=1, axis2=2)
    return {
        "G_gap": _worst_rel(G, G_ref),
        "R_gap": _worst_rel(R, R_ref),
        "trG_gap": float(np.max(np.abs(trG - trG_ref) / np.abs(trG_ref))),
    }


def pred_gap(UA, UA_ref, G_ref) -> float:
    """The heads' predictions on each agent's own data, H_t U_t A_t, against
    the reference's, in the norm G_t = H_t^T H_t gives: directions of U
    that the data barely see (small eigenvalues of G, which float32
    resolves poorly) weigh little."""
    d = np.asarray(UA, np.float64) - UA_ref
    num = np.einsum("mld,mlk,mkd->m", d, G_ref, d)
    den = np.einsum("mld,mlk,mkd->m", UA_ref, G_ref, UA_ref)
    return float(np.max(np.sqrt(np.maximum(num, 0.0) / den)))


def admm_gaps(objective, UA, objective_ref, UA_ref, G_ref) -> dict:
    obj = np.asarray(objective, np.float64)
    ref = np.asarray(objective_ref, np.float64)
    if obj.shape != ref.shape:
        return {k: float("inf") for k in ("obj_gap", "UA_gap", "pred_gap")}
    return {
        "obj_gap": float(np.max(np.abs(obj - ref) / np.abs(ref))),
        "UA_gap": _worst_rel(UA, UA_ref),
        "pred_gap": pred_gap(UA, UA_ref, G_ref),
    }


def worst(a: dict, b: dict) -> dict:
    """Entry-wise worst of two readings of the same numbers."""
    return {k: max(a[k], b[k]) for k in a}


def judge(gaps: dict, limits: dict) -> tuple[bool, dict]:
    """(every number the cell compares is within its limit, {name: {"value",
    "limit"}}) over the names in ``limits``; a number that is not finite
    fails."""
    out, ok = {}, True
    for name, limit in limits.items():
        value = gaps[name]
        ok &= bool(np.isfinite(value)) and value <= limit
        out[name] = {"value": value, "limit": limit}
    return ok, out
