"""Public jit'd wrappers around the Pallas kernels.

Dispatch policy: on TPU the Pallas kernels run natively
(``tests/test_tpu_compile.py`` compiles each one for a described v5e).
Elsewhere they run in interpret mode only when a caller forces
``use_pallas=True`` (tests and CPU gates), and otherwise fall back to
the pure-jnp reference, which is semantically identical.  A kernel that
does not run on TPU raises there, naming itself — never a silent jnp
route.  Call sites never branch on platform themselves.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.mach_candidates import (mach_candidate_topk,
                                           mach_candidate_topk_pallas)
from repro.kernels.mach_decode import mach_decode_pallas
from repro.kernels.mach_fused_xent import (GATHER_NNZ_THRESHOLD,
                                           choose_sparse_blocks,
                                           mach_fused_xent_gather_pallas,
                                           mach_fused_xent_pallas,
                                           mach_fused_xent_sparse_pallas)
from repro.kernels.mach_topk import mach_topk_pallas
from repro.kernels.mach_xent import mach_xent_pallas
from repro.kernels.lru_scan import lru_scan_pallas

# candidate_mode values accepted by mach_topk: None (streaming), the
# string "exact" (streaming, spelled as a knob setting), or an (m, t)
# tuple routing through the count-min candidate filter.
CANDIDATE_EXACT = "exact"


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _not_on_tpu(kernel: str, why: str) -> None:
    """Raise for a kernel that the TPU dispatch cannot run natively."""
    raise NotImplementedError(
        f"{kernel} does not run on TPU: {why} (ROADMAP Queue A)")


def _table_from_inline(inline_coeffs: jnp.ndarray, inline_shift: int,
                       num_classes: int) -> jnp.ndarray:
    """Rebuild the (R, K) bucket table from multiply-shift coefficients
    (reference paths only — the kernels hash in-register)."""
    k = jnp.arange(num_classes, dtype=jnp.uint32)
    prod = inline_coeffs[:, None] * k[None, :]       # wraps mod 2^32
    return jax.lax.shift_right_logical(
        prod, jnp.uint32(inline_shift)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# MACH decode
# ---------------------------------------------------------------------------

def mach_top1(meta_probs: jnp.ndarray,
              table: Optional[jnp.ndarray] = None,
              *,
              num_classes: int,
              inline_coeffs: Optional[jnp.ndarray] = None,
              inline_shift: Optional[int] = None,
              use_pallas: Optional[bool] = None,
              interpret: Optional[bool] = None
              ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-1 class under the summed-score rule (≡ unbiased-estimator argmax).

    meta_probs: (..., R, B) — leading dims flattened internally.
    Returns (values (...,) f32, indices (...,) int32).
    """
    lead = meta_probs.shape[:-2]
    r, b = meta_probs.shape[-2:]
    flat = meta_probs.reshape((-1, r, b))
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        interp = (not _on_tpu()) if interpret is None else interpret
        val, idx = mach_decode_pallas(
            flat, table, num_classes=num_classes,
            inline_coeffs=inline_coeffs, inline_shift=inline_shift,
            interpret=interp)
    else:
        if table is None:
            table = _table_from_inline(inline_coeffs, inline_shift,
                                       num_classes)
        # gather-based scores (O(N·K·R) bytes) — the right CPU algorithm;
        # the one-hot-matmul form (ref.mach_decode_ref, the TPU kernel's
        # oracle) builds an O(K·R·B) one-hot regardless of N
        meta = jnp.moveaxis(flat.astype(jnp.float32), 1, 0)   # (R, N, B)
        g = jnp.take_along_axis(
            meta, table[:, None, :].astype(jnp.int32), axis=-1)  # (R, N, K)
        scores = jnp.sum(g, axis=0)
        idx = jnp.argmax(scores, axis=-1).astype(jnp.int32)
        val = jnp.max(scores, axis=-1)
    return val.reshape(lead), idx.reshape(lead)


def _blocked_topk_fallback(flat: jnp.ndarray, table: jnp.ndarray, k: int,
                           estimator: str, block_k: int = 8192
                           ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Streaming CPU top-k: scan K in blocks, gather (R, N, bk) per
    block, reduce, merge into a running top-k with a stable run-first
    sort (ties keep the lowest class id, matching lax.top_k on the full
    matrix — and the kernel's merge).  Replaces the full-matrix
    reference fallback whose one (R, N, K) gather + (N, K) top_k was
    the K=50k benchmark cliff; memory stays O(N·(R·bk + k)).
    """
    n, r, b = flat.shape
    num_classes = table.shape[1]
    bk = max(block_k, k)
    nb = -(-num_classes // bk)
    tpad = jnp.pad(table, ((0, 0), (0, nb * bk - num_classes)))
    meta = jnp.moveaxis(flat.astype(jnp.float32), 1, 0)        # (R, N, B)
    blocks = tpad.reshape(r, nb, bk).transpose(1, 0, 2)        # (nb, R, bk)

    def body(carry, blk):
        rv, ri, base = carry
        tb, kbase = blk
        g = jnp.take_along_axis(meta, tb[:, None, :].astype(jnp.int32),
                                axis=-1)                       # (R, N, bk)
        if estimator == "unbiased":
            s = jnp.mean(g, axis=0)      # affine Eq. 2 map applied at the end
        elif estimator == "min":
            s = jnp.min(g, axis=0)
        else:
            s = jnp.median(g, axis=0)
        gidx = kbase + jnp.arange(bk, dtype=jnp.int32)
        s = jnp.where(gidx[None, :] < num_classes, s, -jnp.inf)
        bv, bp = jax.lax.top_k(s, k)
        cv = jnp.concatenate([rv, bv], axis=-1)
        ci = jnp.concatenate([ri, kbase + bp.astype(jnp.int32)], axis=-1)
        nv, ni = jax.lax.sort((-cv, ci), dimension=-1, is_stable=True,
                              num_keys=1)
        return (-nv[:, :k], ni[:, :k], base), None

    init = (jnp.full((n, k), -jnp.inf, jnp.float32),
            jnp.zeros((n, k), jnp.int32), 0)
    kbases = jnp.arange(nb, dtype=jnp.int32) * bk
    (val, idx, _), _ = jax.lax.scan(body, init, (blocks, kbases))
    if estimator == "unbiased":
        val = (b / (b - 1.0)) * (val - 1.0 / b)
    return val, idx


def mach_topk(meta_probs: jnp.ndarray,
              table: Optional[jnp.ndarray] = None,
              *,
              num_classes: int,
              k: int,
              estimator: str = "unbiased",
              inline_coeffs: Optional[jnp.ndarray] = None,
              inline_shift: Optional[int] = None,
              candidate_mode=None,
              inverted: Optional[jnp.ndarray] = None,
              use_pallas: Optional[bool] = None,
              interpret: Optional[bool] = None
              ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k classes under any paper estimator (unbiased | min | median).

    meta_probs: (..., R, B) — leading dims flattened internally.
    Returns (values (..., k) f32, indices (..., k) int32) on the
    estimator's scale, matching ``estimate_class_probs`` + ``lax.top_k``
    up to tie order.  The Pallas path streams a running top-k across K
    blocks in VMEM and never materializes the (batch, K) score matrix;
    the fallback streams K in blocked gathers under a lax.scan (same
    semantics, bounded memory).

    ``candidate_mode`` selects the decode algorithm: ``None`` or
    ``"exact"`` stream all K classes; an ``(m, t)`` tuple routes
    through the count-min candidate filter (``mach_topk_candidates`` —
    requires ``inverted``, the table from ``hashing.inverted_table``),
    whose cost is independent of K but whose top-k is approximate
    (filtered slots come back as (-inf, -1); recall is measured by
    ``benchmarks/bench_decode_topk.py``).
    """
    if candidate_mode is not None and candidate_mode != CANDIDATE_EXACT:
        m, t = candidate_mode
        return mach_topk_candidates(
            meta_probs, table, inverted=inverted, num_classes=num_classes,
            k=k, m=m, t=t, estimator=estimator, inline_coeffs=inline_coeffs,
            inline_shift=inline_shift, use_pallas=use_pallas,
            interpret=interpret)
    if not 1 <= k <= num_classes:
        raise ValueError(f"need 1 <= k <= num_classes, got k={k}, "
                         f"num_classes={num_classes}")
    lead = meta_probs.shape[:-2]
    r, b = meta_probs.shape[-2:]
    flat = meta_probs.reshape((-1, r, b))
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        interp = (not _on_tpu()) if interpret is None else interpret
        val, idx = mach_topk_pallas(
            flat, table, num_classes=num_classes, k=k, estimator=estimator,
            inline_coeffs=inline_coeffs, inline_shift=inline_shift,
            interpret=interp)
    else:
        if table is None:
            table = _table_from_inline(inline_coeffs, inline_shift,
                                       num_classes)
        # Small problems: one fused (R, N, K) gather + full top_k beats
        # the scan's per-block dispatch overhead (measured: n=8, K=50k
        # runs 1.4x slower blocked).  Large ones: blocking is what
        # removed the K=50k n=32 cliff and bounds memory at K >= 1M.
        if flat.shape[0] * num_classes * r <= 2**24:
            val, idx = ref.mach_topk_ref(flat, table, k, estimator)
        else:
            val, idx = _blocked_topk_fallback(flat, table, k, estimator)
    return val.reshape(lead + (k,)), idx.reshape(lead + (k,))


def mach_topk_candidates(meta_probs: jnp.ndarray,
                         table: Optional[jnp.ndarray] = None,
                         *,
                         inverted: jnp.ndarray,
                         num_classes: int,
                         k: int,
                         m: int,
                         t: int = 1,
                         estimator: str = "unbiased",
                         inline_coeffs: Optional[jnp.ndarray] = None,
                         inline_shift: Optional[int] = None,
                         compact_cap: int = 2048,
                         use_pallas: Optional[bool] = None,
                         interpret: Optional[bool] = None
                         ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Candidate-filtered top-k: count-min filter over the per-repetition
    bucket top-m, then gather+score only the candidates.

    meta_probs: (..., R, B) — leading dims flattened internally;
    ``inverted`` is the (R·B, L) bucket->class table from
    ``hashing.inverted_table`` (built once per model).  Returns
    (values, indices) shaped (..., k); slots beyond the surviving
    candidates are (-inf, -1), and a row with no count>=t candidate
    backfills slot 0 with its best count>=1 candidate so serving never
    sees an empty row.  With m = B, t = R the result is exact (equal to
    the streaming path up to tie order).  Cost is O(R·B·log m +
    R·m·L·R) — independent of K.

    The fused Pallas pipeline needs inline multiply-shift hashing (it
    recomputes buckets in-register).  Table mode runs the pure-jnp path
    off TPU; on TPU it raises rather than leave the chip for jnp.
    """
    lead = meta_probs.shape[:-2]
    r, b = meta_probs.shape[-2:]
    flat = meta_probs.reshape((-1, r, b))
    use = _on_tpu() if use_pallas is None else use_pallas
    inline = inline_coeffs is not None and inline_shift is not None
    if use and not inline and _on_tpu():
        _not_on_tpu("mach_candidate_topk_pallas (table mode)",
                    "the fused filter hashes in-register and needs inline "
                    "multiply-shift coefficients")
    if use and inline:
        interp = (not _on_tpu()) if interpret is None else interpret
        val, idx = mach_candidate_topk_pallas(
            flat, inverted, num_classes=num_classes, k=k, m=m, t=t,
            estimator=estimator, inline_coeffs=inline_coeffs,
            inline_shift=inline_shift, interpret=interp)
    else:
        val, idx = mach_candidate_topk(
            flat, inverted, table, num_classes=num_classes, k=k, m=m, t=t,
            estimator=estimator, inline_coeffs=inline_coeffs,
            inline_shift=inline_shift, compact_cap=compact_cap)
    return val.reshape(lead + (k,)), idx.reshape(lead + (k,))


def mach_scores(meta_probs: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Full (…, K) score matrix — reference path (used by sampling/top-k)."""
    lead = meta_probs.shape[:-2]
    r, b = meta_probs.shape[-2:]
    g = ref.mach_scores_ref(meta_probs.reshape((-1, r, b)), table)
    return g.reshape(lead + (table.shape[1],))


# ---------------------------------------------------------------------------
# MACH fused cross entropy
# ---------------------------------------------------------------------------

def mach_xent(logits: jnp.ndarray, hashed_labels: jnp.ndarray,
              *, use_pallas: Optional[bool] = None,
              interpret: Optional[bool] = None) -> jnp.ndarray:
    """Per-example summed R-head CE with fused fwd/bwd.

    logits: (..., R, B); hashed_labels: (..., R) -> (...,) f32.
    """
    lead = logits.shape[:-2]
    r, b = logits.shape[-2:]
    lg = logits.reshape((-1, r, b))
    lbl = hashed_labels.reshape((-1, r))
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        interp = (not _on_tpu()) if interpret is None else interpret
        out = mach_xent_pallas(lg, lbl, None, interp)
    else:
        out = ref.mach_xent_ref(lg, lbl)
    return out.reshape(lead)


def csr_to_ell(indptr: jnp.ndarray, indices: jnp.ndarray,
               values: jnp.ndarray, nnz_max: int, num_features: int
               ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """CSR -> padded ELL (cols (N, nnz_max) int32, vals (N, nnz_max)).

    Row n's entries land in slots [0, len_n); padded slots carry col id
    ``num_features`` (an always-out-of-range sentinel) and val 0, so
    they contribute nothing however the kernel tiles the feature dim.
    ``nnz_max`` must be static and >= the longest row — it sets the
    kernel's J extent, and rows longer than it would be silently
    truncated (diverging from the densifying reference), so an
    undersized ``nnz_max`` is rejected whenever ``indptr`` is concrete
    (traced indptr — e.g. inside a jitted train step — relies on the
    producer honoring the contract, as ``SparseExtremeDataset`` does).
    Differentiable wrt ``values`` (a pure gather)."""
    n = indptr.shape[0] - 1
    nnz = indices.shape[0]
    if n and not isinstance(indptr, jax.core.Tracer):
        longest = int(np.max(np.diff(np.asarray(indptr))))
        if longest > nnz_max:
            raise ValueError(
                f"nnz_max={nnz_max} < longest CSR row ({longest}): the "
                f"kernel would silently truncate it")
    if nnz == 0:
        return (jnp.full((n, nnz_max), num_features, jnp.int32),
                jnp.zeros((n, nnz_max), values.dtype))
    slot = jnp.arange(nnz_max, dtype=indptr.dtype)
    pos = indptr[:-1, None] + slot[None, :]               # (N, nnz_max)
    valid = pos < indptr[1:, None]
    posc = jnp.minimum(pos, nnz - 1)
    cols = jnp.where(valid, indices[posc].astype(jnp.int32), num_features)
    vals = jnp.where(valid, values[posc], 0)
    return cols, vals


def mach_fused_xent_csr(indptr: jnp.ndarray, indices: jnp.ndarray,
                        values: jnp.ndarray, w: jnp.ndarray,
                        hashed_labels: jnp.ndarray,
                        *, num_buckets: int, nnz_max: int,
                        bias: Optional[jnp.ndarray] = None,
                        block_n: Optional[int] = None,
                        block_c: Optional[int] = None,
                        block_d: Optional[int] = None,
                        sparse_impl: Optional[str] = None,
                        bucket_select: Optional[tuple] = None,
                        bucket_proxy: Optional[jnp.ndarray] = None,
                        use_pallas: Optional[bool] = None,
                        interpret: Optional[bool] = None) -> jnp.ndarray:
    """Sparse-feature fused projection + R-head CE (the ODP d=422k
    training path).

    indptr (N+1,), indices (nnz,), values (nnz,) — a CSR batch over d
    features; w (d, R·B) head kernel; hashed_labels (N, R) bucket ids;
    optional bias (R·B,) — a native kernel operand, broadcast-added to
    the logits tile at the last d block, so the ELL width stays exactly
    nnz_max (no unit-feature column) -> (N,) f32 per-example loss.
    ``block_n/block_c/block_d`` pin the kernel tiling (benchmarks and
    tests); None lets ``choose_sparse_blocks`` fit the VMEM budget.

    On the Pallas path neither the (N, R·B) logits tensor nor a dense
    (N, d) activation ever exists in HBM in either pass — the batch is
    re-laid-out as padded ELL (O(N·nnz_max)), and the VJP scatter-adds
    dW (and reduces dbias) without a logits round-trip.  ``sparse_impl``
    picks the kernel family: ``"densify"`` (per-tile one-hot
    densification — the low-nnz fast path), ``"gather"`` (scalar-
    prefetch DMA of the active W rows — per-step VMEM independent of
    nnz, the only viable family at bag-of-words nnz), or ``None``
    (auto: gather at nnz_max >= GATHER_NNZ_THRESHOLD or whenever the
    densify chooser cannot fit the VMEM budget).  The fallback is the
    densifying reference — the right CPU algorithm, and the parity
    oracle for both families.  Differentiable wrt w and bias;
    ``values`` gets a ZERO cotangent on the kernel path (features are
    data — use the reference if you need feature grads).

    ``bucket_select=(c_sel, refresh_every)`` routes through dynamic
    bucket selection (see ``mach_fused_xent``): the loss runs over the
    top-``c_sel`` proxy-scored bucket columns per repetition with the
    batch's label buckets force-included.  ``bucket_proxy`` optionally
    supplies cached (R, B) proxy scores (the trainer recomputes them
    every ``refresh_every`` steps); otherwise they are computed in-graph
    from the batch mean activation (a scatter-add — never a densified
    batch).
    """
    d = w.shape[0]
    r = hashed_labels.shape[-1]
    if w.shape != (d, r * num_buckets):
        raise ValueError(f"w {w.shape} != ({d}, {r}*{num_buckets})")
    if bucket_select is not None:
        c_sel = bucket_select[0]
        if c_sel < num_buckets:
            proxy = bucket_proxy if bucket_proxy is not None else \
                mach_bucket_proxy(w=w, num_buckets=num_buckets, bias=bias,
                                  csr=(indptr, indices, values))
            selected = mach_select_buckets(
                proxy, hashed_labels, num_buckets=num_buckets, c_sel=c_sel)
            return mach_fused_xent_csr_selected(
                indptr, indices, values, w, hashed_labels, selected,
                num_buckets=num_buckets, nnz_max=nnz_max, bias=bias,
                block_n=block_n, block_c=block_c, block_d=block_d,
                sparse_impl=sparse_impl, use_pallas=use_pallas,
                interpret=interpret)
    use = _on_tpu() if use_pallas is None else use_pallas
    if not use:
        # stop_gradient matches the kernel path's zero cotangent for
        # values (features are data, not parameters) — without it the
        # two backends would silently disagree on d/d(values)
        return ref.mach_fused_xent_csr_ref(
            indptr, indices, jax.lax.stop_gradient(values), w,
            hashed_labels.astype(jnp.int32), num_buckets, bias=bias)
    cols, vals = csr_to_ell(indptr, indices, values, nnz_max, d)
    interp = (not _on_tpu()) if interpret is None else interpret
    impl = sparse_impl
    if impl is None:
        if nnz_max >= GATHER_NNZ_THRESHOLD:
            impl = "gather"
        else:
            try:
                choose_sparse_blocks(indptr.shape[0] - 1, d, r,
                                     num_buckets, nnz_max, block_n,
                                     block_c, block_d)
                impl = "densify"
            except ValueError:
                impl = "gather"
    if impl == "gather":
        if not interp:
            _not_on_tpu(
                "mach_fused_xent_gather_pallas",
                "its (1, bc) W-row blocks break Mosaic's (8, 128) tiling, "
                "and its dW rows accumulate through revisited output "
                "blocks, which a TPU never re-reads from HBM")
        return mach_fused_xent_gather_pallas(
            cols, vals, w, bias, hashed_labels.astype(jnp.int32),
            num_buckets, block_c, interp)
    if impl != "densify":
        raise ValueError(f"sparse_impl must be 'densify', 'gather' or "
                         f"None, got {sparse_impl!r}")
    return mach_fused_xent_sparse_pallas(
        cols, vals, w, bias, hashed_labels.astype(jnp.int32),
        num_buckets, block_n, block_c, block_d, interp)


def mach_fused_xent(h: jnp.ndarray, w: jnp.ndarray,
                    hashed_labels: jnp.ndarray,
                    *, num_buckets: int,
                    bias: Optional[jnp.ndarray] = None,
                    block_n: Optional[int] = None,
                    block_c: Optional[int] = None,
                    block_d: Optional[int] = None,
                    bucket_select: Optional[tuple] = None,
                    bucket_proxy: Optional[jnp.ndarray] = None,
                    use_pallas: Optional[bool] = None,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Logit-free fused projection + R-head CE (training fast path).

    h: (..., d) hidden states; w: (d, R·B) head kernel;
    hashed_labels: (..., R) bucket ids; optional bias (R·B,) — a native
    kernel operand (no (d+1, R·B) W-concat) -> (...,) f32 per-example
    loss.  ``block_n/block_c/block_d`` pin the kernel tiling
    (benchmarks and tests); None lets ``choose_fused_blocks`` fit the
    VMEM budget.

    On the Pallas path the (…, R·B) logits tensor never exists in HBM
    in either the forward or the backward pass, and W/h stream through
    d-blocked VMEM tiles (activation memory is O(N·d + N·R), per-step
    VMEM independent of d); the fallback is the materializing reference
    — the right CPU algorithm, and the parity oracle.  Differentiable
    wrt h, w and bias (custom VJP with recomputing backward kernels).

    ``bucket_select=(c_sel, refresh_every)`` enables dynamic bucket
    selection (arxiv 1801.01687's dynamic class selection, hashed to
    MACH buckets): a cheap proxy scores all R·B bucket columns, the
    top-``c_sel`` per repetition are kept — the batch's label buckets
    force-included, so the positive CE term is exact and the bias is
    one-sided and bounded (``ref.mach_selected_bias_bound_ref``) — and
    the fused loss runs over the selected C-subset, cutting the
    kernel's C-axis ``num_buckets/c_sel``-fold.  ``bucket_proxy``
    optionally supplies cached (R, B) proxy scores; ``refresh_every``
    is the producer-side cadence for that cache (``train.Trainer``
    honors it) — selection itself is recomputed every call, so label
    force-inclusion always reflects the current batch.  With
    ``bucket_select=None`` this is bit-identical to the unselected
    path.
    """
    lead = h.shape[:-1]
    d = h.shape[-1]
    r = hashed_labels.shape[-1]
    if bucket_select is not None:
        c_sel = bucket_select[0]
        if c_sel < num_buckets:
            proxy = bucket_proxy if bucket_proxy is not None else \
                mach_bucket_proxy(h, w, num_buckets=num_buckets, bias=bias)
            selected = mach_select_buckets(
                proxy, hashed_labels, num_buckets=num_buckets, c_sel=c_sel)
            return mach_fused_xent_selected(
                h, w, hashed_labels, selected, num_buckets=num_buckets,
                bias=bias, block_n=block_n, block_c=block_c,
                block_d=block_d, use_pallas=use_pallas,
                interpret=interpret)
    h2 = h.reshape((-1, d))
    lbl = hashed_labels.reshape((-1, r)).astype(jnp.int32)
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        interp = (not _on_tpu()) if interpret is None else interpret
        out = mach_fused_xent_pallas(h2, w, bias, lbl, num_buckets,
                                     block_n, block_c, block_d, interp)
    else:
        out = ref.mach_fused_xent_ref(h2, w, lbl, num_buckets, bias=bias)
    return out.reshape(lead)


# ---------------------------------------------------------------------------
# Dynamic bucket selection (training-time C-axis cut)
# ---------------------------------------------------------------------------

def mach_bucket_proxy(h: Optional[jnp.ndarray] = None,
                      w: Optional[jnp.ndarray] = None,
                      *, num_buckets: int,
                      bias: Optional[jnp.ndarray] = None,
                      csr: Optional[tuple] = None) -> jnp.ndarray:
    """Cheap (R, B) bucket proxy scores: the logits of the batch-mean
    activation.  Dense: ``h (..., d)``; sparse: pass
    ``csr=(indptr, indices, values)`` instead of ``h`` (the mean is a
    scatter-add — no densified batch).  One d·R·B matvec, 1/N of the
    full projection, and cacheable across steps — ``ref.py`` holds the
    math (pure jnp on every backend); gradients are stopped (the proxy
    only *ranks* buckets; it must not add a loss term)."""
    if csr is not None:
        out = ref.mach_bucket_proxy_csr_ref(*csr, w, num_buckets,
                                            bias=bias)
    else:
        out = ref.mach_bucket_proxy_ref(h.reshape((-1, h.shape[-1])), w,
                                        num_buckets, bias=bias)
    return jax.lax.stop_gradient(out)


def mach_select_buckets(proxy_scores: jnp.ndarray,
                        hashed_labels: jnp.ndarray,
                        *, num_buckets: int, c_sel: int) -> jnp.ndarray:
    """Top-``c_sel`` bucket columns per repetition by proxy score with
    the batch's label buckets force-included -> (R, c_sel) int32,
    sorted ascending.  Pure jnp on every backend (a (R, B) top_k —
    negligible next to the loss); ``ref.py`` holds the math."""
    lbl = hashed_labels.reshape((-1, hashed_labels.shape[-1]))
    return ref.mach_select_buckets_ref(proxy_scores,
                                       lbl.astype(jnp.int32),
                                       num_buckets, c_sel)


def _apply_bucket_selection(w, bias, lbl, selected, num_buckets):
    """Gather the selected W/bias columns and remap labels to their
    position inside the selection.  The gather is indexing (an axis-1
    gather of whole (d,) column slices — one gather op, not a
    per-repetition ``take_along_axis`` over the minor axis), so the
    VJP scatter-adds dW back into the selected columns and every
    unselected column receives exactly zero gradient.  Gather and
    scatter are O(d·R·c_sel) *per step*, independent of the batch,
    while the fused-loss saving is per example — selection pays off
    once N amortizes the column traffic (any realistic batch)."""
    r, c_sel = selected.shape
    d = w.shape[0]
    flat = (jnp.arange(r, dtype=selected.dtype)[:, None] * num_buckets
            + selected).reshape(-1)                      # (R·c_sel,)
    wsel = w[:, flat]
    bsel = None if bias is None else bias[flat]
    pos = jnp.argmax(selected[None, :, :] == lbl[:, :, None],
                     axis=-1).astype(jnp.int32)
    return wsel, bsel, pos


def mach_fused_xent_selected(h: jnp.ndarray, w: jnp.ndarray,
                             hashed_labels: jnp.ndarray,
                             selected: jnp.ndarray,
                             *, num_buckets: int,
                             bias: Optional[jnp.ndarray] = None,
                             block_n: Optional[int] = None,
                             block_c: Optional[int] = None,
                             block_d: Optional[int] = None,
                             use_pallas: Optional[bool] = None,
                             interpret: Optional[bool] = None
                             ) -> jnp.ndarray:
    """Fused projection+CE over a selected bucket subset.

    ``selected`` (R, c_sel) int32 — from ``mach_select_buckets``, which
    force-includes every label bucket (required: a label outside its
    head's selection would silently remap to position 0).  The W/bias
    columns are gathered and the ordinary fused op runs at B′ = c_sel,
    so the kernel C-axis shrinks ``num_buckets/c_sel``-fold; unselected
    W columns get exactly zero gradient (take_along_axis VJP).  The
    loss is a lower bound on the full loss: exact positive term,
    logsumexp over a subset — one-sided bias, bounded per example by
    ``ref.mach_selected_bias_bound_ref``."""
    r, c_sel = selected.shape
    lbl = hashed_labels.reshape((-1, r)).astype(jnp.int32)
    wsel, bsel, pos = _apply_bucket_selection(w, bias, lbl, selected,
                                              num_buckets)
    return mach_fused_xent(
        h, wsel, pos.reshape(hashed_labels.shape), num_buckets=c_sel,
        bias=bsel, block_n=block_n, block_c=block_c, block_d=block_d,
        use_pallas=use_pallas, interpret=interpret)


def mach_fused_xent_csr_selected(indptr: jnp.ndarray,
                                 indices: jnp.ndarray,
                                 values: jnp.ndarray, w: jnp.ndarray,
                                 hashed_labels: jnp.ndarray,
                                 selected: jnp.ndarray,
                                 *, num_buckets: int, nnz_max: int,
                                 bias: Optional[jnp.ndarray] = None,
                                 block_n: Optional[int] = None,
                                 block_c: Optional[int] = None,
                                 block_d: Optional[int] = None,
                                 sparse_impl: Optional[str] = None,
                                 use_pallas: Optional[bool] = None,
                                 interpret: Optional[bool] = None
                                 ) -> jnp.ndarray:
    """CSR counterpart of ``mach_fused_xent_selected`` — gathers the
    selected W/bias columns and runs ``mach_fused_xent_csr`` at
    B′ = c_sel (same one-sided, bounded bias; same zero gradient on
    unselected columns)."""
    r, c_sel = selected.shape
    lbl = hashed_labels.reshape((-1, r)).astype(jnp.int32)
    wsel, bsel, pos = _apply_bucket_selection(w, bias, lbl, selected,
                                              num_buckets)
    return mach_fused_xent_csr(
        indptr, indices, values, wsel, pos, num_buckets=c_sel,
        nnz_max=nnz_max, bias=bsel, block_n=block_n, block_c=block_c,
        block_d=block_d, sparse_impl=sparse_impl, use_pallas=use_pallas,
        interpret=interpret)


# ---------------------------------------------------------------------------
# RG-LRU scan
# ---------------------------------------------------------------------------

def lru_scan(a: jnp.ndarray, x: jnp.ndarray, h0: jnp.ndarray,
             *, use_pallas: Optional[bool] = None,
             interpret: Optional[bool] = None) -> jnp.ndarray:
    """Diagonal linear recurrence h_t = a_t·h_{t-1} + x_t;  (B, T, D)."""
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        interp = (not _on_tpu()) if interpret is None else interpret
        return lru_scan_pallas(a, x, h0, interpret=interp)
    return ref.lru_scan_ref(a, x, h0)


# ---------------------------------------------------------------------------
# Flash attention (fused softmax attention — the §Perf memory-term fix)
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    use_pallas=None, interpret=None):
    """q (B,T,H,hd), k/v (B,S,KV,hd) -> (B,T,H,hd).  On TPU: the Pallas
    kernel (scores never leave VMEM); elsewhere: the exact jnp flash."""
    from repro.kernels.flash_attention import flash_attention_pallas
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        interp = (not _on_tpu()) if interpret is None else interpret
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      interpret=interp)
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window)


# ---------------------------------------------------------------------------
# Oracle registry: every public op names its pure-jnp reference in
# kernels/ref.py.  CI lints this table (tools/lint_kernel_oracles.py) so
# the dispatch surface and the oracle set cannot drift — adding an op
# without a reference is a build failure, not a review nit.
# ---------------------------------------------------------------------------

ORACLES: dict = {
    "mach_top1": "mach_decode_ref",
    "mach_topk": "mach_topk_ref",
    "mach_topk_candidates": "mach_candidate_topk_ref",
    "mach_scores": "mach_scores_ref",
    "mach_xent": "mach_xent_ref",
    "mach_fused_xent": "mach_fused_xent_ref",
    "mach_fused_xent_csr": "mach_fused_xent_csr_ref",
    "mach_bucket_proxy": "mach_bucket_proxy_ref",
    "mach_select_buckets": "mach_select_buckets_ref",
    "mach_fused_xent_selected": "mach_fused_xent_selected_ref",
    "mach_fused_xent_csr_selected": "mach_fused_xent_csr_selected_ref",
    "csr_to_ell": "csr_densify_ref",
    "lru_scan": "lru_scan_ref",
    "flash_attention": "flash_attention_ref",
}
