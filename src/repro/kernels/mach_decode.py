"""Fused streaming MACH decode (Algorithm 2 on the MXU): top-1 and top-k.

The paper computes the global score matrix ``G[n, k] = Σ_r P_r[n, h_r(k)]``
with an OpenCL gather kernel, materializes G (N×K), then argmaxes.  On
TPU random gathers are VPU-bound, so decode is recast as blocked
matmuls against one-hot bucket matrices that are *built on the fly in
VMEM*, one repetition at a time:

    g_r = P_r (bn, B)  @  M_r (B, bk),    M_r[b, k] = 1[h_r(k) = b]

and the per-class estimator combines the R gathered values: their sum
(unbiased, Eq. 2 — the affine map is applied after selection, it is
monotone), their min (Eq. 7) or their median (Eq. 8, an odd-even
transposition sort over the R values).  A running top-k (values, class
ids) lives in VMEM scratch across K blocks — the N×K score matrix never
exists in HBM.  HBM traffic is O(N·R·B + K·R [table mode] + N·k).

Grid (N/bn, K/bk, R/nh): the repetition-chunk axis is minor, so each
(row block, K block) cell sweeps nh repetitions per step.  Where the
whole (R, bn, B) probability block fits the VMEM budget nh = R and the
block stays resident across the K sweep; at retrieval widths
(R·B = 131,072) the chooser tiles R instead, and B itself is swept in
2048-bucket slices, so no tiling it returns overflows VMEM.

Selection uses only ops that Mosaic lowers (no ``top_k``/``sort``): the
running set and the block's scores are merged by k rounds of
max-extract.  Each round takes the largest value and, among equal
values, the lowest class id — ``lax.top_k``'s tie order on the full
score matrix — then retires that id.

Two hash sources:
  * table mode   — the (R, K) int32 bucket table is tiled in (works for
                   any 2-universal family),
  * inline mode  — multiply-shift hashes are computed in-register from
                   the class index (paper §2.1's trick), removing the
                   table load from HBM entirely.  Requires B = 2^k.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import phase

NEG_INF = float(jnp.finfo(jnp.float32).min)
LANE = 128                # running-top-k capacity granularity
_B_SLICE = 2048           # buckets per one-hot slice (VMEM bound at big B)
_ID_SENTINEL = jnp.iinfo(jnp.int32).max
DECODE_VMEM_BUDGET = 6 * 2**20


def round_up(x: int, m: int) -> int:
    """Smallest multiple of m >= x (shared by the MACH kernels' block
    and padding arithmetic)."""
    return -(-x // m) * m


def decode_tile_bytes(bn: int, bk: int, nh: int, b: int, *,
                      estimator: str = "unbiased", kcap: int = LANE,
                      r: int = 0) -> int:
    """Accounted VMEM bytes of one decode grid step (f32/i32 words):

    double-buffered probability block 2·(nh, bn, B) and hash block
    2·(nh, 8, bk) (a (1, bk) row pads to a sublane tile); one one-hot
    slice (min(B, 2048), bk) plus its mask; the gathered (bn, bk) value
    and the (bn, bk) accumulator; min/median's padded (R, bn, bk) value
    cube for the order statistic (median only) ; the running top-k
    (bn, kcap) pair, its outputs, and the merge temporaries over
    (bn, kcap + bk)."""
    bs = min(b, _B_SLICE)
    words = 2 * nh * bn * b + 2 * nh * 8 * bk + 2 * bs * bk + 2 * bn * bk
    if estimator == "median":
        words += round_up(r, nh) * bn * bk
    words += 4 * bn * kcap + 4 * bn * (kcap + bk)
    return 4 * words


def _rep_chunks(r: int) -> list[int]:
    """Candidate repetitions per step, descending: every distinct
    ceil(R / c)."""
    return sorted({-(-r // c) for c in range(1, r + 1)}, reverse=True)


def choose_decode_blocks(n: int, r: int, b: int,
                         block_n: Optional[int] = None,
                         block_k: Optional[int] = None,
                         vmem_budget: int = DECODE_VMEM_BUDGET,
                         *, estimator: str = "unbiased",
                         kcap: int = LANE) -> tuple[int, int, int]:
    """Pick (bn, bk, nh): row block, K block (a lane multiple) and
    repetitions per step — the first candidate whose
    ``decode_tile_bytes`` fit ``vmem_budget``, keeping bk large first
    (fewer K steps) and then nh (fewer repetition steps).

    bn is rounded up to a multiple of 8 (the fp32 sublane tile) whatever
    the caller passes; the kernel pads N up to it.  An explicit
    ``block_k`` skips the accounting (the caller takes responsibility)
    and keeps every repetition in one step.  Raises ValueError when even
    (bn, 128, one repetition) overflows the budget."""
    bn = block_n or min(128, max(8, n))
    bn = max(8, round_up(bn, 8))
    if block_k is not None:
        return bn, block_k, r
    for bk in (2048, 1024, 512, 256, 128):
        for nh in _rep_chunks(r):
            if decode_tile_bytes(bn, bk, nh, b, estimator=estimator,
                                 kcap=kcap, r=r) <= vmem_budget:
                return bn, bk, nh
    need = decode_tile_bytes(bn, 128, 1, b, estimator=estimator, kcap=kcap,
                             r=r)
    raise ValueError(
        f"decode tile does not fit: bn={bn} bk=128 nh=1 r={r} b={b} "
        f"estimator={estimator!r} kcap={kcap} needs {need} bytes > "
        f"vmem_budget={vmem_budget}; pass block_k to override")


def _bucket_row(hash_ref, t, inline_shift, kbase, bk):
    """(1, bk) bucket ids of repetition slot t for classes kbase + [0,
    bk): a tiled table row, or multiply-shift hashing in int32 (the
    wrapping product has the same bits as the uint32 one)."""
    if inline_shift is None:
        return hash_ref[t]                                   # (1, bk)
    kk = kbase + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    return jax.lax.shift_right_logical(hash_ref[t] * kk,
                                       jnp.int32(inline_shift))


def gather_rep(probs, buckets, b, bk):
    """g[n, k] = probs[n, buckets[k]] as one-hot matmuls over B slices
    (a bucket id of B or more — table padding — gathers 0)."""
    g = None
    for b0 in range(0, b, _B_SLICE):
        bs = min(_B_SLICE, b - b0)
        onehot = (b0 + jax.lax.broadcasted_iota(jnp.int32, (bs, bk), 0)
                  == buckets).astype(jnp.float32)
        # f32 precision: the default would round probs to bf16
        part = jnp.dot(probs if bs == b else probs[:, b0:b0 + bs], onehot,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
        g = part if g is None else g + part
    return g


def median_of(slabs):
    """Elementwise median of a list of equal-shape arrays (jnp.median
    semantics: mean of the two middle values for an even count), by an
    odd-even transposition sort — compare-exchanges that Mosaic
    lowers."""
    v = list(slabs)
    r = len(v)
    for phase in range(r):
        for i in range(phase % 2, r - 1, 2):
            v[i], v[i + 1] = jnp.minimum(v[i], v[i + 1]), \
                jnp.maximum(v[i], v[i + 1])
    return (v[(r - 1) // 2] + v[r // 2]) * 0.5


def merge_topk(run_val, run_idx, blk_val, blk_idx, k):
    """Merge a running top-k (bn, kcap) with candidate scores (bn, w) by
    k rounds of max-extract -> new (bn, kcap) running set.  Every id is
    unique; among equal values the lowest id wins, so the result is
    ``lax.top_k``'s order on the union.  Lanes k.. of the result hold
    (NEG_INF, sentinel)."""
    kcap = run_val.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, run_val.shape, 1)

    def extract(j, carry):
        rv, bv, out_v, out_i = carry
        m = jnp.maximum(jnp.max(rv, axis=1, keepdims=True),
                        jnp.max(bv, axis=1, keepdims=True))
        pick = jnp.minimum(
            jnp.min(jnp.where(rv == m, run_idx, _ID_SENTINEL), axis=1,
                    keepdims=True),
            jnp.min(jnp.where(bv == m, blk_idx, _ID_SENTINEL), axis=1,
                    keepdims=True))
        out_v = jnp.where(lane == j, m, out_v)
        out_i = jnp.where(lane == j, pick, out_i)
        rv = jnp.where(run_idx == pick, -jnp.inf, rv)
        bv = jnp.where(blk_idx == pick, -jnp.inf, bv)
        return rv, bv, out_v, out_i

    init = (run_val, blk_val, jnp.full(run_val.shape, NEG_INF, jnp.float32),
            jnp.full(run_idx.shape, _ID_SENTINEL, jnp.int32))
    _, _, out_v, out_i = jax.lax.fori_loop(0, min(k, kcap), extract, init)
    return out_v, out_i


def _topk_body(num_classes, r, b, bn, bk, nh, k, estimator, inline_shift,
               probs_ref, hash_ref, val_out, idx_out, acc_scr, run_val,
               run_idx, *cube):
    """Grid (N/bn, K/bk, R/nh).  probs_ref (nh, bn, B); hash_ref (nh, 1,
    bk) table slice or (nh, 1, 1) int32 multiply-shift coefficients;
    cube = the (rp, bn, bk) median scratch."""
    kblk = pl.program_id(1)
    c = pl.program_id(2)
    nc = pl.num_programs(2)
    kbase = kblk * bk

    @pl.when((kblk == 0) & (c == 0))
    def _init_run():
        run_val[...] = jnp.full(run_val.shape, NEG_INF, jnp.float32)
        # distinct negative ids, so retiring one never retires another
        run_idx[...] = -1 - jax.lax.broadcasted_iota(jnp.int32,
                                                     run_idx.shape, 1)

    @pl.when(c == 0)
    def _init_acc():
        fill = jnp.inf if estimator == "min" else 0.0
        acc_scr[...] = jnp.full((bn, bk), fill, jnp.float32)

    for t in range(nh):
        rep = c * nh + t
        g = gather_rep(probs_ref[t].astype(jnp.float32),
                        _bucket_row(hash_ref, t, inline_shift, kbase, bk),
                        b, bk)
        if estimator == "unbiased":
            acc_scr[...] += g          # padded repetitions gather 0
        elif estimator == "min":
            acc_scr[...] = jnp.minimum(acc_scr[...],
                                       jnp.where(rep < r, g, jnp.inf))
        else:
            cube[0][rep] = g

    @pl.when(c == nc - 1)
    def _select():
        if estimator == "median":
            scores = median_of([cube[0][j] for j in range(r)])
        else:
            scores = acc_scr[...]
        ids = kbase + jax.lax.broadcasted_iota(jnp.int32, (bn, bk), 1)
        scores = jnp.where(ids < num_classes, scores, NEG_INF)
        lane = jax.lax.broadcasted_iota(jnp.int32, run_val.shape, 1)
        kth = jnp.min(jnp.where(lane < k, run_val[...], jnp.inf))

        # Most K blocks of a selective decode hold no score above the
        # running k-th; ties go to the running set (lower ids), so
        # skipping on <= is exact.
        @pl.when(jnp.max(scores) > kth)
        def _merge():
            v, i = merge_topk(run_val[...], run_idx[...], scores, ids, k)
            run_val[...] = v
            run_idx[...] = i

        @pl.when(kblk == pl.num_programs(1) - 1)
        def _flush():
            val_out[...] = run_val[...]
            idx_out[...] = run_idx[...]


def streaming_topk(meta_probs: jnp.ndarray,
                   table: Optional[jnp.ndarray] = None,
                   *,
                   num_classes: int,
                   k: int,
                   estimator: str = "unbiased",
                   inline_coeffs: Optional[jnp.ndarray] = None,
                   inline_shift: Optional[int] = None,
                   block_n: Optional[int] = None,
                   block_k: Optional[int] = None,
                   interpret: bool = False
                   ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """meta_probs (N, R, B) -> top-k (raw scores, class ids), each
    (N, k).  Raw scores: the sum over repetitions (unbiased), the min
    or the median.  Exactly one of ``table`` ((R, K) int32) or
    (``inline_coeffs`` ((R,) uint32), ``inline_shift``) must be given."""
    n, r, b = meta_probs.shape
    kcap = round_up(k, LANE)
    bn, bk, nh = choose_decode_blocks(n, r, b, block_n, block_k,
                                      estimator=estimator, kcap=kcap)
    rp = round_up(r, nh)
    npad = round_up(n, bn)
    k_grid = pl.cdiv(num_classes, bk)
    probs = jnp.pad(jnp.moveaxis(meta_probs, 1, 0),
                    ((0, rp - r), (0, npad - n), (0, 0)))   # (rp, npad, B)
    if table is not None:
        # pad bucket = B: an all-zero one-hot column
        hash_arg = jnp.pad(table.astype(jnp.int32),
                           ((0, rp - r), (0, k_grid * bk - num_classes)),
                           constant_values=b)[:, None, :]
        hash_spec = pl.BlockSpec((nh, 1, bk), lambda i, j, c: (c, 0, j))
        inline_shift = None
    else:
        if inline_coeffs is None or inline_shift is None:
            raise ValueError("need table or (inline_coeffs, inline_shift)")
        if b & (b - 1):
            raise ValueError("inline mode requires power-of-two B")
        coeffs = jax.lax.bitcast_convert_type(
            inline_coeffs.astype(jnp.uint32), jnp.int32)
        hash_arg = jnp.pad(coeffs, (0, rp - r)).reshape(rp, 1, 1)
        hash_spec = pl.BlockSpec((nh, 1, 1), lambda i, j, c: (c, 0, 0))

    scratch = [pltpu.VMEM((bn, bk), jnp.float32),
               pltpu.VMEM((bn, kcap), jnp.float32),
               pltpu.VMEM((bn, kcap), jnp.int32)]
    if estimator == "median":
        scratch.append(pltpu.VMEM((rp, bn, bk), jnp.float32))
    out_spec = pl.BlockSpec((bn, kcap), lambda i, j, c: (i, 0))
    val, idx = pl.pallas_call(
        functools.partial(_topk_body, num_classes, r, b, bn, bk, nh, k,
                          estimator, inline_shift),
        grid=(npad // bn, k_grid, rp // nh),
        in_specs=[pl.BlockSpec((nh, bn, b), lambda i, j, c: (c, i, 0)),
                  hash_spec],
        out_specs=(out_spec, out_spec),
        out_shape=(jax.ShapeDtypeStruct((npad, kcap), jnp.float32),
                   jax.ShapeDtypeStruct((npad, kcap), jnp.int32)),
        scratch_shapes=scratch,
        interpret=interpret,
        name="mach_topk",
        metadata=phase.kernel_metadata(phase.DECODE_TOPK),
    )(probs, hash_arg)
    return val[:n, :k], idx[:n, :k]


def mach_decode_pallas(meta_probs: jnp.ndarray,
                       table: Optional[jnp.ndarray] = None,
                       *,
                       num_classes: int,
                       inline_coeffs: Optional[jnp.ndarray] = None,
                       inline_shift: Optional[int] = None,
                       block_n: Optional[int] = None,
                       block_k: Optional[int] = None,
                       interpret: bool = False
                       ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused top-1 decode under the summed-score rule (≡ unbiased
    argmax).  meta_probs (N, R, B) -> (summed score (N,), class (N,))."""
    val, idx = streaming_topk(
        meta_probs, table, num_classes=num_classes, k=1,
        inline_coeffs=inline_coeffs, inline_shift=inline_shift,
        block_n=block_n, block_k=block_k, interpret=interpret)
    return val[:, 0], idx[:, 0]
