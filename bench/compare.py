"""The numbers that decide `correct`, and their judgement against limits.

Training (per leaf of the parameters, the worst leaf counts):

    loss_gap         largest |L_prog - L_ref| / |L_ref| over the steps
    grad_norm_gap    | |g_prog| - |g_ref| |, first step's gradient
    change_norm_gap  | |P3_prog - P0| - |P3_ref - P0| |, after the steps
    grad_diff        |g_prog - g_ref|
    change_diff      |P3_prog - P3_ref|

each of the last four over the larger of the reference's norm of that
leaf and of the median leaf.  A leaf whose reference gradient is under
a thousandth of the median leaf's moves by round-off alone and is left
out.  Decode (over the sampled queries, every rank):

    topk_gap         the larger of |value_prog - value_ref| and the
                     amount by which the reference's score of the
                     returned class lies below the reference's score at
                     that rank (inf for an id out of range or repeated):
                     a wrong value and a wrong id both show
"""

from __future__ import annotations

import math
import statistics

import jax
import jax.numpy as jnp
import numpy as np

IGNORE_BELOW = 1e-3


@jax.jit
def _sq_rows(a):
    a = a.reshape(a.shape[0], -1) if a.ndim > 1 else a.reshape(1, -1)
    return jnp.sum(a * a, axis=1)


@jax.jit
def _sq_rows_diff(a, b):
    return _sq_rows(a - b)


def norm(a, b=None) -> float:
    """|a| or |a - b|: per-row sums on the device, the rest in float64."""
    rows = _sq_rows(a) if b is None else _sq_rows_diff(a, b)
    return float(np.sqrt(np.sum(np.asarray(rows, np.float64))))


def _worst(gaps: dict, ref: dict) -> float:
    med = statistics.median(ref.values())
    return max(gaps[k] / max(ref[k], med) for k in gaps)


def train_numbers(loss_p, loss_r, grad_p, grad_r, p0, p3_p, p3_r) -> dict:
    g_ref = {k: norm(grad_r[k]) for k in grad_r}
    med = statistics.median(g_ref.values())
    keep = [k for k in g_ref if g_ref[k] >= IGNORE_BELOW * med]
    g_ref = {k: g_ref[k] for k in keep}
    c_ref = {k: norm(p3_r[k], p0[k]) for k in keep}
    return {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in zip(loss_p, loss_r)),
        "grad_norm_gap": _worst(
            {k: abs(norm(grad_p[k]) - g_ref[k]) for k in keep}, g_ref),
        "change_norm_gap": _worst(
            {k: abs(norm(p3_p[k], p0[k]) - c_ref[k]) for k in keep}, c_ref),
        "grad_diff": _worst({k: norm(grad_p[k], grad_r[k]) for k in keep},
                            g_ref),
        "change_diff": _worst({k: norm(p3_p[k], p3_r[k]) for k in keep},
                              c_ref),
    }


@jax.jit
def _decode_gaps(vals_p, ids_p, vals_r, scores_r):
    k_all = scores_r.shape[-1]
    valid = (ids_p >= 0) & (ids_p < k_all)
    s = jnp.sort(ids_p, axis=-1)
    repeated = jnp.any(s[:, 1:] == s[:, :-1], axis=-1, keepdims=True)
    got = jnp.take_along_axis(scores_r, jnp.clip(ids_p, 0, k_all - 1), -1)
    id_gap = jnp.where(valid & ~repeated, vals_r - got, jnp.inf)
    return jnp.max(jnp.maximum(jnp.abs(vals_p - vals_r), id_gap))


def decode_numbers(vals_p, ids_p, vals_r, scores_r) -> dict:
    return {"topk_gap": float(_decode_gaps(vals_p, ids_p, vals_r,
                                           scores_r))}


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """Each limited number beside its limit; correct only where every one
    is within it (NaN is not).  No limits, no correct run."""
    checks = {}
    ok = bool(limits)
    for name, lim in limits.items():
        v = values.get(name, math.nan)
        checks[name] = {"value": v, "limit": lim["limit"]}
        ok = ok and v <= lim["limit"]
    return ok, checks
