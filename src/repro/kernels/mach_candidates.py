"""Candidate-filtered MACH top-k decode (count-min filtering).

The streaming top-k kernel (``mach_topk.py``) is O(K log k): every one
of K classes is scored.  At retrieval scale (K = 10M+) the K-axis sweep
dominates decode even though almost no class can make the top-k.  This
module implements the sub-O(K log K) path used by the logarithmic-time
one-against-some line of work and Amazon's count-min-sketch deployment:

  1. per repetition, take the top-m buckets of the (n, R, B) meta
     probabilities (``bucket_topm`` / ``bucket_topm_pallas``);
  2. a class is a *candidate* iff it hashes into a top-m bucket in
     >= t of the R repetitions (count-min filtering);
  3. only candidates are gathered and merged through the estimator math
     (unbiased Eq. 2 / min Eq. 7 / median Eq. 8).

Candidates are enumerated through an inverted bucket -> class table
(``hashing.inverted_table``): row j·B + b lists the classes hashing to
bucket b under repetition j, padded to L (the max bucket occupancy,
lane-aligned) with the sentinel K.  The candidate pool is the
concatenation of the R·m top-bucket rows — P = R·m·L entries.  Each
class can appear up to R times in the pool; it is *claimed* exactly
once, by the first repetition whose top-m contains it, so the top-k
never returns duplicates.

Cost: O(R·B log m) for the bucket top-m + O(P·R) for the filtered
gather+score, with P = R·m·L independent of K — vs the streaming
path's O(K·R/B · ...) sweep.  No (n, K) tensor exists anywhere on this
path (tested by a jaxpr gate).

Exactness: with m = B and t = R every class is claimed by repetition 0
and has count R, so the pool scores are exactly the streaming scores —
the mode is provably identical to the streaming path (up to tie
order).  Looser (m, t) trade recall for speed; the benchmark gate
measures recall@k.

Rows with zero count->=t candidates fall back to the best count>=1
candidate (the "t=1 backfill") so serving never samples from an empty
set.  The backfill rides in the same top-k via a penalty-offset score
encoding — OFFSET is larger than the estimator score range, so
penalized entries sort strictly below every valid one and are decoded
(or discarded) after the top-k on the small (n, k) result.

Two implementations with identical semantics:
  * ``mach_candidate_topk`` — pure jnp (CPU fallback + table mode);
  * ``mach_candidate_topk_pallas`` — fused Pallas filter->gather->score
    pipeline (inline multiply-shift mode): the inverted-table rows are
    DMA-gathered per chunk via scalar-prefetched bucket ids, hashes are
    recomputed in-register, and scores merge into a running top-k in
    VMEM scratch — candidates never round-trip through HBM.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.estimators import ESTIMATORS
from repro.kernels import phase
from repro.kernels.mach_decode import LANE as _LANE
from repro.kernels.mach_decode import (NEG_INF, gather_rep, median_of,
                                       merge_topk, round_up)

# Penalty subtracted from backfill (count < t) candidate scores.  Every
# estimator score lies in (-1/(B-1), 1], so subtracting OFFSET maps the
# backfill band to (-OFFSET - eps, -OFFSET + 1] — disjoint from and
# strictly below the valid band, and far above NEG_INF/2 (the
# "unclaimed" sentinel), letting one top-k rank valid > backfill > dead.
OFFSET = 4.0


def _affine_unbiased(mean_g: jnp.ndarray, b: int) -> jnp.ndarray:
    """Eq. 2: B/(B-1) · (mean_j g_j − 1/B)."""
    return (b / (b - 1.0)) * (mean_g - 1.0 / b)


def _median_sorted(g_sorted: jnp.ndarray, axis: int) -> jnp.ndarray:
    """jnp.median semantics given an already-sorted axis."""
    r = g_sorted.shape[axis]
    lo = jax.lax.index_in_dim(g_sorted, (r - 1) // 2, axis, keepdims=False)
    hi = jax.lax.index_in_dim(g_sorted, r // 2, axis, keepdims=False)
    return (lo + hi) * 0.5


def validate_candidate_args(num_classes: int, k: int, m: int, t: int,
                            r: int, b: int, estimator: str) -> None:
    if estimator not in ESTIMATORS:
        raise ValueError(f"estimator must be one of {ESTIMATORS}, "
                         f"got {estimator!r}")
    if not 1 <= k <= num_classes:
        raise ValueError(f"need 1 <= k <= num_classes, got k={k}, "
                         f"num_classes={num_classes}")
    if not 1 <= m <= b:
        raise ValueError(f"need 1 <= m <= B, got m={m}, B={b}")
    if not 1 <= t <= r:
        raise ValueError(f"need 1 <= t <= R, got t={t}, R={r}")


# ---------------------------------------------------------------------------
# Stage 1: per-repetition bucket top-m.
# ---------------------------------------------------------------------------

def bucket_topm(meta_probs: jnp.ndarray, m: int
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(n, R, B) -> (tau (n, R) f32, ids (n, R, m) int32).

    tau is the m-th largest bucket value per (row, repetition) — the
    membership threshold g >= tau used by the count-min filter.  Note
    ``jnp.min`` over the top-m values, not ``tv[..., -1]``: identical
    value, but the slice forces XLA:CPU into a pathological fusion with
    the downstream pool gather (~14x decode slowdown).
    """
    tv, ti = jax.lax.top_k(meta_probs, m)
    return jnp.min(tv, axis=-1).astype(jnp.float32), ti.astype(jnp.int32)


def _topm_body(m: int, probs_ref, ids_out, tau_out):
    """Iterative max-extract: m rounds of (max, first argmax, mask) per
    repetition.  Reproduces lax.top_k's lowest-index tie order (the
    first maximum is taken; masking removes exactly that column)."""
    p = probs_ref[0].astype(jnp.float32)                       # (R, B)
    lane = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
    lane_m = jax.lax.broadcasted_iota(jnp.int32, ids_out.shape[1:], 1)

    def extract(j, carry):
        p, ids, _ = carry
        v = jnp.max(p, axis=1, keepdims=True)                  # (R, 1)
        i = jnp.min(jnp.where(p == v, lane, p.shape[1]), axis=1,
                    keepdims=True)
        return (jnp.where(lane == i, -jnp.inf, p),
                jnp.where(lane_m == j, i, ids), v)

    _, ids, tau = jax.lax.fori_loop(
        0, m, extract, (p, jnp.zeros(ids_out.shape[1:], jnp.int32),
                        jnp.zeros(tau_out.shape[1:], jnp.float32)))
    ids_out[0] = ids
    tau_out[0] = tau


def bucket_topm_pallas(meta_probs: jnp.ndarray, m: int,
                       interpret: bool = False
                       ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pallas bucket top-m: (n, R, B) -> (tau (n, R), ids (n, R, m)).

    One grid step per row; the (R, B) tile stays in VMEM across the m
    extraction rounds.  The ids output is lane-padded internally and
    sliced back to m on the host.
    """
    n, r, b = meta_probs.shape
    if not 1 <= m <= b:
        raise ValueError(f"need 1 <= m <= B, got m={m}, B={b}")
    mpad = round_up(m, _LANE)
    ids, tau = pl.pallas_call(
        functools.partial(_topm_body, m),
        grid=(n,),
        in_specs=[pl.BlockSpec((1, r, b), lambda i: (i, 0, 0))],
        out_specs=(pl.BlockSpec((1, r, mpad), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1, r, 1), lambda i: (i, 0, 0))),
        out_shape=(jax.ShapeDtypeStruct((n, r, mpad), jnp.int32),
                   jax.ShapeDtypeStruct((n, r, 1), jnp.float32)),
        interpret=interpret,
        name="mach_bucket_topm",
        metadata=phase.kernel_metadata(phase.DECODE_TOPK),
    )(meta_probs)
    return tau[:, :, 0], ids[:, :, :m]


# ---------------------------------------------------------------------------
# Shared host-side pieces: chunk ids and penalty-offset decode.
# ---------------------------------------------------------------------------

def candidate_chunks(ids: jnp.ndarray, b: int) -> jnp.ndarray:
    """Top-m bucket ids (n, R, m) -> inverted-table row ids (n, R·m)."""
    n, r, m = ids.shape
    return (jnp.arange(r, dtype=jnp.int32)[None, :, None] * b
            + ids).reshape(n, r * m)


def decode_penalty_topk(val: jnp.ndarray, idx: jnp.ndarray, t: int
                        ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Decode the penalty-offset encoding after the top-k.

    val/idx: (n, k) from a top-k over encoded scores.  Valid entries
    (count >= t) pass through; dead entries become (-inf, -1); backfill
    entries (claimed, count < t) are dropped — except that a row with
    *no* valid candidate keeps its best backfill in slot 0 (score
    restored by +OFFSET) so serving never sees an empty row.
    """
    if t <= 1:
        # valid == claimed: no backfill band was encoded
        dead = val <= NEG_INF / 2
        return (jnp.where(dead, -jnp.inf, val),
                jnp.where(dead, -1, idx))
    is_valid = val > -OFFSET / 2
    is_claimed = val > NEG_INF / 2
    keep0 = (~is_valid[:, :1]) & is_claimed[:, :1]   # row empty, has backfill
    out_val = jnp.where(is_valid, val, -jnp.inf)
    out_idx = jnp.where(is_valid, idx, -1)
    out_val = out_val.at[:, :1].set(
        jnp.where(keep0, val[:, :1] + OFFSET, out_val[:, :1]))
    out_idx = out_idx.at[:, :1].set(
        jnp.where(keep0, idx[:, :1], out_idx[:, :1]))
    return out_val, out_idx


# ---------------------------------------------------------------------------
# Pure-jnp candidate path (CPU fallback + table mode).
# ---------------------------------------------------------------------------

def mach_candidate_topk(meta_probs: jnp.ndarray,
                        inverted: jnp.ndarray,
                        table: Optional[jnp.ndarray] = None,
                        *,
                        num_classes: int,
                        k: int,
                        m: int,
                        t: int = 1,
                        estimator: str = "unbiased",
                        inline_coeffs: Optional[jnp.ndarray] = None,
                        inline_shift: Optional[int] = None,
                        compact_cap: int = 2048
                        ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Candidate-filtered top-k, pure jnp.  meta_probs (N, R, B) ->
    (val, idx) (N, k); unclaimed/filtered slots are (-inf, -1).

    ``inverted`` is the (R·B, L) table from ``hashing.inverted_table``.
    Bucket ids for the gathered pool come from ``inline_coeffs`` /
    ``inline_shift`` (multiply-shift recompute, no table in memory) or
    from gathering ``table`` ((R, K), any hash family).

    min/median compute their order statistic on a count-prioritized
    compaction of the pool (``compact_cap`` entries — a full-pool
    median is pathological on XLA:CPU); the result is exact whenever
    the number of claimed candidates is <= compact_cap.
    """
    n, r, b = meta_probs.shape
    validate_candidate_args(num_classes, k, m, t, r, b, estimator)
    ell = inverted.shape[1]
    p_pool = r * m * ell

    tau, ids = bucket_topm(meta_probs, m)                 # (n, R), (n, R, m)
    chunk = candidate_chunks(ids, b)                      # (n, R·m)
    pool = jnp.take(inverted, chunk, axis=0).reshape(n, p_pool)

    # bucket of every pool entry under every repetition
    if table is not None:
        h = jnp.moveaxis(jnp.take(table, jnp.clip(pool, 0, num_classes - 1),
                                  axis=1), 0, -1)         # (n, P, R)
    else:
        if inline_coeffs is None or inline_shift is None:
            raise ValueError("need table or (inline_coeffs, inline_shift)")
        h = jax.lax.shift_right_logical(
            pool[..., None].astype(jnp.uint32) * inline_coeffs[None, None, :],
            jnp.uint32(inline_shift)).astype(jnp.int32)   # (n, P, R)

    flat = meta_probs.reshape(n, r * b)
    gidx = (h + (jnp.arange(r, dtype=jnp.int32) * b)[None, None, :])
    g = jnp.take_along_axis(flat, gidx.reshape(n, p_pool * r),
                            axis=-1).reshape(n, p_pool, r)

    member = g >= tau[:, None, :]                         # (n, P, R)
    count = member.sum(-1)
    first = jnp.argmax(member, -1)
    chunk_r = (jnp.arange(p_pool, dtype=jnp.int32) // (m * ell))[None]
    claimed = (first == chunk_r) & (pool < num_classes)
    valid = claimed if t <= 1 else claimed & (count >= t)

    if estimator == "unbiased":
        # one consumer of the mean score — a second ``where`` over it
        # re-triggers the XLA:CPU scalar-regather pathology
        sall = _affine_unbiased(jnp.mean(g, -1), b)
        s_enc = jnp.where(claimed,
                          sall - OFFSET * (1.0 - valid.astype(jnp.float32)),
                          NEG_INF)
        val, pos = jax.lax.top_k(s_enc, k)
        idx = jnp.take_along_axis(pool, pos, axis=-1)
        return decode_penalty_topk(val, idx, t)

    # min/median: compact to the highest-count claimed entries first so
    # the order statistic runs on (n, cap, R), never (n, P, R)
    cap = min(p_pool, max(compact_cap, k))
    sel = jnp.where(claimed, count.astype(jnp.float32), 0.0)
    _, cpos = jax.lax.top_k(sel, cap)
    cg = jnp.take_along_axis(g, cpos[..., None], axis=1)  # (n, cap, R)
    cpool = jnp.take_along_axis(pool, cpos, axis=-1)
    cclaimed = jnp.take_along_axis(claimed, cpos, axis=-1)
    cvalid = jnp.take_along_axis(valid, cpos, axis=-1)
    if estimator == "min":
        score = jnp.min(cg, axis=-1)
    else:
        score = _median_sorted(jnp.sort(cg, axis=-1), axis=-1)
    s_enc = jnp.where(cclaimed,
                      score - OFFSET * (1.0 - cvalid.astype(jnp.float32)),
                      NEG_INF)
    val, pos = jax.lax.top_k(s_enc, k)
    idx = jnp.take_along_axis(cpool, pos, axis=-1)
    return decode_penalty_topk(val, idx, t)


# ---------------------------------------------------------------------------
# Fused Pallas pipeline (inline multiply-shift mode).
# ---------------------------------------------------------------------------

def _cand_body(num_classes, r, b, m, k, t, shift, estimator,
               chunks_ref, coeffs_ref, meta_ref, tau_ref, inv_ref,
               val_out, idx_out, run_val, run_idx):
    """Grid (n, R·m), chunk minor.  inv_ref is the (1, 1, L)
    inverted-table row for this chunk, DMA-selected by the
    scalar-prefetched chunk id; meta_ref (1, R, B) and tau_ref (1, R, 1)
    are row-resident in VMEM."""
    c = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(c == 0)
    def _init():
        run_val[...] = jnp.full(run_val.shape, NEG_INF, jnp.float32)
        run_idx[...] = jnp.full(run_idx.shape, -1, jnp.int32)

    pool = inv_ref[0]                                      # (1, L) int32
    ell = pool.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (r, ell), 0)

    def gather(rr, g):
        # a loop, not an unrolled sweep: one repetition's one-hot slices
        # are live at a time
        h = jax.lax.shift_right_logical(pool * coeffs_ref[rr],
                                        jnp.int32(shift))  # (1, L)
        meta_r = meta_ref[0, pl.ds(rr, 1), :].astype(jnp.float32)
        return jnp.where(row == rr, gather_rep(meta_r, h, b, ell), g)

    g = jax.lax.fori_loop(0, r, gather, jnp.zeros((r, ell), jnp.float32))

    member = g >= tau_ref[0]                               # (R, L)
    count = jnp.sum(member.astype(jnp.int32), axis=0, keepdims=True)
    first = jnp.min(jnp.where(member, row, r), axis=0, keepdims=True)
    claimed = (first == c // m) & (pool < num_classes)
    valid = claimed if t <= 1 else claimed & (count >= t)

    if estimator == "unbiased":
        score = _affine_unbiased(jnp.mean(g, axis=0, keepdims=True), b)
    elif estimator == "min":
        score = jnp.min(g, axis=0, keepdims=True)
    else:
        score = median_of([g[rr:rr + 1, :] for rr in range(r)])
    s_enc = jnp.where(claimed,
                      score - OFFSET * (1.0 - valid.astype(jnp.float32)),
                      NEG_INF)

    # skip the merge when no chunk entry can displace a kept one
    lane = jax.lax.broadcasted_iota(jnp.int32, run_val.shape, 1)
    kth = jnp.min(jnp.where(lane < k, run_val[...], jnp.inf))

    @pl.when(jnp.max(s_enc) > kth)
    def _merge():
        new_val, new_idx = merge_topk(run_val[...], run_idx[...], s_enc,
                                      pool, k)
        run_val[...] = new_val
        run_idx[...] = new_idx

    @pl.when(c == nc - 1)
    def _flush():
        val_out[0] = run_val[...]
        idx_out[0] = run_idx[...]


def mach_candidate_topk_pallas(meta_probs: jnp.ndarray,
                               inverted: jnp.ndarray,
                               *,
                               num_classes: int,
                               k: int,
                               m: int,
                               t: int = 1,
                               estimator: str = "unbiased",
                               inline_coeffs: jnp.ndarray,
                               inline_shift: int,
                               interpret: bool = False
                               ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused candidate filter->gather->score.  (N, R, B) -> (val, idx)
    (N, k), same semantics as ``mach_candidate_topk``.

    Inline multiply-shift mode only: the chunk's class list is the only
    HBM gather (BlockSpec-selected by the scalar-prefetched chunk id);
    buckets are recomputed in-register, the per-repetition scores come
    from in-VMEM one-hot matmuls, and the running top-k lives in VMEM
    scratch — no (n, K) or (n, P) tensor exists anywhere.
    """
    n, r, b = meta_probs.shape
    validate_candidate_args(num_classes, k, m, t, r, b, estimator)
    if b & (b - 1):
        raise ValueError("inline mode requires power-of-two B")
    ell = inverted.shape[1]
    kcap = round_up(k, _LANE)

    tau, ids = bucket_topm_pallas(meta_probs, m, interpret=interpret)
    chunks = candidate_chunks(ids, b)                      # (n, R·m)

    # 3-D operands: every block's last two dims are whole array dims
    row_spec = lambda shape: pl.BlockSpec(
        shape, lambda i, c, chunks, coeffs: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n, r * m),
        in_specs=[
            row_spec((1, r, b)),
            row_spec((1, r, 1)),
            pl.BlockSpec((1, 1, ell),
                         lambda i, c, chunks, coeffs: (chunks[i, c], 0, 0)),
        ],
        out_specs=(row_spec((1, 1, kcap)), row_spec((1, 1, kcap))),
        scratch_shapes=[pltpu.VMEM((1, kcap), jnp.float32),
                        pltpu.VMEM((1, kcap), jnp.int32)],
    )
    coeffs = jax.lax.bitcast_convert_type(
        inline_coeffs.astype(jnp.uint32), jnp.int32)
    val, idx = pl.pallas_call(
        functools.partial(_cand_body, num_classes, r, b, m, k, t,
                          inline_shift, estimator),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((n, 1, kcap), jnp.float32),
                   jax.ShapeDtypeStruct((n, 1, kcap), jnp.int32)),
        interpret=interpret,
        name="mach_candidate_topk",
        metadata=phase.kernel_metadata(phase.DECODE_TOPK),
    )(chunks, coeffs, meta_probs, tau[:, :, None], inverted[:, None, :])

    return decode_penalty_topk(val[:, 0, :k], idx[:, 0, :k], t)
