"""Fused projection+CE kernel vs oracles: values, grads, memory shape.

Parity ladder (all interpret=True on CPU):
  kernel  ==  ref.mach_fused_xent_ref        (values + dh/dW/dbias grads)
  ops.mach_fused_xent / head.fused_loss  ==  mach_loss(head.apply(...))
  model.loss(mach_fused_loss=True)  ==  model.loss (materializing path)
plus the structural claims the kernel exists for: no (N, R·B)-sized
tensor in the jaxpr of either pass, no (d+1, R·B) bias-concat on the
dense head path, and the block choosers provably respecting their VMEM
budget (the d=12288 LM-scale case included).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.mach import MACHConfig, MACHOutputHead, mach_loss
from repro.kernels import mach_fused_xent, ops, ref
from repro.kernels.mach_fused_xent import (DEFAULT_VMEM_BUDGET,
                                           GATHER_NNZ_THRESHOLD,
                                           choose_fused_blocks,
                                           choose_gather_blocks,
                                           choose_sorted_bwd_blocks,
                                           choose_sparse_blocks,
                                           dense_tile_bytes,
                                           gather_tile_bytes,
                                           mach_fused_xent_gather_pallas,
                                           mach_fused_xent_pallas,
                                           sorted_bwd_tile_bytes,
                                           sparse_tile_bytes)
from repro.models import LanguageModel, ModelConfig


def _case(n, d, r, b, seed=0, dtype=jnp.float32, with_bias=False):
    """Shared dense fixture (benchmarks/common.py) — the benchmark's
    parity gate and these tests see the same inputs."""
    from benchmarks.common import make_dense_case
    h, w, bias, y, g = make_dense_case(n, d, r, b, seed=seed, dtype=dtype)
    if not with_bias:
        return h, w, y, g
    return h, w, bias, y, g


# ---------------------------------------------------------------------------
# kernel vs reference oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,d,r,b", [
    (16, 32, 4, 8),        # several whole heads per column block
    (13, 32, 6, 24),       # ragged N (padded to the 8-sublane tile)
    (5, 32, 25, 32),       # paper ODP-ish R=25: padded head count
    (2, 16, 20, 512),      # imagenet-ish B=512, tiny N
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_xent_matches_ref(n, d, r, b, dtype):
    h, w, y, g = _case(n, d, r, b, dtype=dtype)
    lr = ref.mach_fused_xent_ref(h, w, y, b)
    lk = mach_fused_xent_pallas(h, w, None, y, b, None, None, None, True)
    np.testing.assert_allclose(np.asarray(lr), np.asarray(lk),
                               rtol=1e-5, atol=1e-5)
    dr = jax.grad(lambda h_, w_: jnp.sum(
        ref.mach_fused_xent_ref(h_, w_, y, b) * g), argnums=(0, 1))(h, w)
    dk = jax.grad(lambda h_, w_: jnp.sum(
        mach_fused_xent_pallas(h_, w_, None, y, b, None, None, None,
                               True) * g),
        argnums=(0, 1))(h, w)
    # Both paths accumulate in f32 but in different orders, then cast
    # the grad to the input dtype.  For bf16 an f32 value that lies near
    # the midpoint of two bf16 neighbours can round either way: one bf16
    # ulp, at most 2^-7·|x| (8 significand bits).  f32 keeps its bound.
    rtol = 2.0**-7 if dtype == jnp.bfloat16 else 1e-4
    for a, k in zip(dr, dk):
        assert a.dtype == k.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(k, np.float32),
                                   rtol=rtol, atol=1e-5)


@pytest.mark.parametrize("n,d,r,b", [
    (16, 32, 4, 8),
    (13, 32, 6, 24),       # ragged N
    (2, 16, 20, 512),      # B=512, tiny N
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_xent_bias_matches_ref(n, d, r, b, dtype):
    """The in-kernel bias operand: values and (dh, dW, dbias) against
    the materializing reference."""
    h, w, bias, y, g = _case(n, d, r, b, dtype=dtype, with_bias=True)
    lr = ref.mach_fused_xent_ref(h, w, y, b, bias=bias)
    lk = mach_fused_xent_pallas(h, w, bias, y, b, None, None, None, True)
    np.testing.assert_allclose(np.asarray(lr), np.asarray(lk),
                               rtol=1e-5, atol=1e-5)
    dr = jax.grad(lambda h_, w_, b_: jnp.sum(
        ref.mach_fused_xent_ref(h_, w_, y, b, bias=b_) * g),
        argnums=(0, 1, 2))(h, w, bias)
    dk = jax.grad(lambda h_, w_, b_: jnp.sum(
        mach_fused_xent_pallas(h_, w_, b_, y, b, None, None, None,
                               True) * g),
        argnums=(0, 1, 2))(h, w, bias)
    # bf16 grads agree to 1 ulp (the final f32->bf16 cast may round a
    # near-midpoint value differently between the two paths)
    rtol, atol = ((1e-2, 1e-4) if dtype == jnp.bfloat16
                  else (1e-4, 1e-5))
    for a, k in zip(dr, dk):
        assert a.dtype == k.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(k, np.float32),
                                   rtol=rtol, atol=atol)


@pytest.mark.parametrize("sparse", [False, True])
def test_fused_xent_backward_row_chunks(monkeypatch, sparse):
    """A batch larger than one backward call's rows (its dlogits stay
    in VMEM between the two backward phases) runs the backward kernel
    per row chunk; the summed grads must equal the oracle's."""
    import repro.kernels.mach_fused_xent as kmod
    monkeypatch.setattr(kmod, "_BWD_ROWS", 8)
    n, d, r, b, j = 21, 32, 3, 16, 5
    h, w, bias, y, g = _case(n, d, r, b, with_bias=True)
    if sparse:
        k1, k2 = jax.random.split(jax.random.key(9))
        cols = jax.random.randint(k1, (n, j), 0, d)
        vals = jax.random.uniform(k2, (n, j))
        indptr = jnp.arange(n + 1, dtype=jnp.int32) * j

        def kern(w_, b_):
            return kmod.mach_fused_xent_sparse_pallas(
                cols, vals, w_, b_, y, b, None, None, None, True)

        def oracle(w_, b_):
            return ref.mach_fused_xent_csr_ref(
                indptr, cols.reshape(-1), vals.reshape(-1), w_, y, b,
                bias=b_)
    else:
        def kern(w_, b_):
            return mach_fused_xent_pallas(h, w_, b_, y, b, None, None, None,
                                          True)

        def oracle(w_, b_):
            return ref.mach_fused_xent_ref(h, w_, y, b, bias=b_)
    dk = jax.grad(lambda w_, b_: jnp.sum(kern(w_, b_) * g),
                  argnums=(0, 1))(w, bias)
    dr = jax.grad(lambda w_, b_: jnp.sum(oracle(w_, b_) * g),
                  argnums=(0, 1))(w, bias)
    for a, k in zip(dr, dk):
        np.testing.assert_allclose(np.asarray(a), np.asarray(k),
                                   rtol=1e-4, atol=1e-5)


def test_fused_xent_head_split_blocks():
    """B larger than the column block: a head's logsumexp streams across
    blocks through the online rescaling path (bias included)."""
    n, d, r, b = 9, 16, 3, 256
    h, w, bias, y, g = _case(n, d, r, b, with_bias=True)
    bn, bc, bd, rp, bp = choose_fused_blocks(n, d, r, b, None, 64)
    assert bc < b and bp % bc == 0          # the path under test
    lr = ref.mach_fused_xent_ref(h, w, y, b, bias=bias)
    lk = mach_fused_xent_pallas(h, w, bias, y, b, None, 64, None, True)
    np.testing.assert_allclose(np.asarray(lr), np.asarray(lk),
                               rtol=1e-5, atol=1e-6)
    dr = jax.grad(lambda h_, w_, b_: jnp.sum(
        ref.mach_fused_xent_ref(h_, w_, y, b, bias=b_) * g),
        argnums=(0, 1, 2))(h, w, bias)
    dk = jax.grad(lambda h_, w_, b_: jnp.sum(
        mach_fused_xent_pallas(h_, w_, b_, y, b, None, 64, None,
                               True) * g),
        argnums=(0, 1, 2))(h, w, bias)
    for a, k in zip(dr, dk):
        np.testing.assert_allclose(np.asarray(a), np.asarray(k),
                                   rtol=1e-4, atol=1e-6)


def test_fused_xent_d_blocked():
    """d larger than the d block: logits accumulate across d blocks in
    scratch; dh/dW ride the revisited d-blocked output windows."""
    n, d, r, b = 12, 200, 4, 32
    h, w, bias, y, g = _case(n, d, r, b, with_bias=True)
    bn, bc, bd, rp, bp = choose_fused_blocks(n, d, r, b, None, None, 64)
    assert bd < d                            # the path under test
    lr = ref.mach_fused_xent_ref(h, w, y, b, bias=bias)
    lk = mach_fused_xent_pallas(h, w, bias, y, b, None, None, 64, True)
    np.testing.assert_allclose(np.asarray(lr), np.asarray(lk),
                               rtol=1e-5, atol=1e-6)
    dr = jax.grad(lambda h_, w_, b_: jnp.sum(
        ref.mach_fused_xent_ref(h_, w_, y, b, bias=b_) * g),
        argnums=(0, 1, 2))(h, w, bias)
    dk = jax.grad(lambda h_, w_, b_: jnp.sum(
        mach_fused_xent_pallas(h_, w_, b_, y, b, None, None, 64,
                               True) * g),
        argnums=(0, 1, 2))(h, w, bias)
    for a, k in zip(dr, dk):
        np.testing.assert_allclose(np.asarray(a), np.asarray(k),
                                   rtol=1e-4, atol=1e-6)


def test_fused_xent_d_blocked_and_head_split():
    """Both streaming paths at once: d blocked AND a head's logsumexp
    spanning column blocks."""
    n, d, r, b = 9, 200, 3, 256
    h, w, bias, y, g = _case(n, d, r, b, with_bias=True)
    bn, bc, bd, rp, bp = choose_fused_blocks(n, d, r, b, None, 64, 64)
    assert bc < b and bd < d
    lr = ref.mach_fused_xent_ref(h, w, y, b, bias=bias)
    lk = mach_fused_xent_pallas(h, w, bias, y, b, None, 64, 64, True)
    np.testing.assert_allclose(np.asarray(lr), np.asarray(lk),
                               rtol=1e-5, atol=1e-6)
    dr = jax.grad(lambda h_, w_, b_: jnp.sum(
        ref.mach_fused_xent_ref(h_, w_, y, b, bias=b_) * g),
        argnums=(0, 1, 2))(h, w, bias)
    dk = jax.grad(lambda h_, w_, b_: jnp.sum(
        mach_fused_xent_pallas(h_, w_, b_, y, b, None, 64, 64, True) * g),
        argnums=(0, 1, 2))(h, w, bias)
    for a, k in zip(dr, dk):
        np.testing.assert_allclose(np.asarray(a), np.asarray(k),
                                   rtol=1e-4, atol=1e-6)


def test_fused_xent_acceptance_case():
    """The PR-2 acceptance config: (N=256, d=128, R=16, B=512) in
    interpret mode — |Δloss| ≤ 1e-5, grads allclose at rtol 1e-4."""
    n, d, r, b = 256, 128, 16, 512
    h, w, y, g = _case(n, d, r, b, seed=7)
    lr = ref.mach_fused_xent_ref(h, w, y, b)
    lk = mach_fused_xent_pallas(h, w, None, y, b, None, None, None, True)
    assert float(jnp.max(jnp.abs(lr - lk))) <= 1e-5
    dr = jax.grad(lambda h_, w_: jnp.sum(
        ref.mach_fused_xent_ref(h_, w_, y, b) * g), argnums=(0, 1))(h, w)
    dk = jax.grad(lambda h_, w_: jnp.sum(
        mach_fused_xent_pallas(h_, w_, None, y, b, None, None, None,
                               True) * g),
        argnums=(0, 1))(h, w)
    for a, k in zip(dr, dk):
        np.testing.assert_allclose(np.asarray(a), np.asarray(k),
                                   rtol=1e-4, atol=1e-6)


def test_fused_xent_lm_scale_d_acceptance():
    """This PR's acceptance config: d=12288 (mistral-large d_model) at
    (R=32, B=512) — the shape whose old tiling silently blew the VMEM
    budget ~2x.  Two claims: (1) choose_fused_blocks at the confirmed
    N=256 shape yields a tiling whose accounted tile bytes fit the
    default 6 MB budget; (2) values + (dh, dW, dbias) match the
    materializing reference through the d-blocked kernels in interpret
    mode at that d/R/B.  Parity runs at N=16 — the (C/bc, D/bd) grid
    axes under test are N-independent, and interpret-mode cost is per
    grid step — with the chooser's own (budget-checked) tiling, which
    streams both axes exactly like the N=256 one."""
    n, d, r, b = 16, 12288, 32, 512
    bn, bc, bd, rp, bp = choose_fused_blocks(256, d, r, b)
    assert dense_tile_bytes(bn, bc, bd, rp) <= DEFAULT_VMEM_BUDGET
    assert bd < d and bc < r * b            # both axes actually stream
    bn2, bc2, bd2, rp2, _ = choose_fused_blocks(n, d, r, b)
    assert dense_tile_bytes(bn2, bc2, bd2, rp2) <= DEFAULT_VMEM_BUDGET
    assert bd2 < d and bc2 < r * b
    h, w, bias, y, g = _case(n, d, r, b, seed=3, with_bias=True)

    @jax.jit
    def kernel_vag(h_, w_, b_):
        return jax.value_and_grad(lambda hh, ww, bb: jnp.sum(
            mach_fused_xent_pallas(hh, ww, bb, y, b, None, None, None,
                                   True) * g),
            argnums=(0, 1, 2))(h_, w_, b_)

    @jax.jit
    def ref_vag(h_, w_, b_):
        return jax.value_and_grad(lambda hh, ww, bb: jnp.sum(
            ref.mach_fused_xent_ref(hh, ww, y, b, bias=bb) * g),
            argnums=(0, 1, 2))(h_, w_, b_)

    lr, dr = ref_vag(h, w, bias)
    lk, dk = kernel_vag(h, w, bias)
    np.testing.assert_allclose(float(lr), float(lk), rtol=1e-6, atol=1e-4)
    for name, a, k in zip(("dh", "dw", "dbias"), dr, dk):
        np.testing.assert_allclose(np.asarray(a), np.asarray(k),
                                   rtol=1e-4, atol=1e-6, err_msg=name)


# ---------------------------------------------------------------------------
# block choosers: provably within the VMEM budget
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d,r,b", [
    (12288, 32, 512),      # the confirmed blowout case (ISSUE 4)
    (8192, 16, 2048),      # R·B = 32k at LM-scale d
    (4096, 20, 512),       # imagenet-21k head on a 4k trunk
    (1024, 25, 32),        # ODP head
    (128, 16, 512),        # the PR-2 acceptance shape
    (32, 4, 8),            # tiny test shape
])
def test_choose_fused_blocks_respects_budget(d, r, b):
    bn, bc, bd, rp, bp = choose_fused_blocks(256, d, r, b)
    assert dense_tile_bytes(bn, bc, bd, rp) <= DEFAULT_VMEM_BUDGET
    # structural invariants the kernels rely on
    assert bn % 8 == 0 and bd % 8 == 0
    assert (rp * bp) % bc == 0 and rp >= r and bp >= b


@pytest.mark.parametrize("d,r,b,j", [
    (422_713, 25, 32, 128),    # paper ODP: d=422k bag-of-words
    (8192, 8, 64, 1024),       # high-nnz regime (gather-path parity in
    #                            test_gather_high_nnz_acceptance_case)
    (4096, 20, 512, 64),
    (96, 4, 16, 8),
])
def test_choose_sparse_blocks_respects_budget(d, r, b, j):
    bn, bc, bd, rp, bp, jp = choose_sparse_blocks(256, d, r, b, j)
    assert sparse_tile_bytes(bn, bc, bd, rp, jp) <= DEFAULT_VMEM_BUDGET
    assert bn % 8 == 0 and bd % 8 == 0 and jp % 128 == 0
    assert (rp * bp) % bc == 0 and rp >= r and bp >= b


@pytest.mark.parametrize("n,d,r,b,j", [
    (512, 422_713, 25, 32, 120),    # odp.train
    (4096, 1024, 8, 4096, 64),      # the 500k-label selected-bucket job
    (13, 96, 4, 16, 8),
])
def test_choose_sorted_bwd_blocks_respects_budget(n, d, r, b, j):
    _, bc, _, rp, _, _ = choose_sparse_blocks(n, d, r, b, j)
    bd, rows = choose_sorted_bwd_blocks(n, d, bc, rp)
    assert sorted_bwd_tile_bytes(bc, bd, rp, rows) <= DEFAULT_VMEM_BUDGET
    assert bd % 8 == 0 and rows % 8 == 0 and 8 <= rows <= -(-n // 8) * 8


def test_sorted_bwd_chooser_raises_when_budget_impossible(monkeypatch):
    monkeypatch.setattr(mach_fused_xent, "DEFAULT_VMEM_BUDGET", 100_000)
    with pytest.raises(ValueError, match="100000 bytes of VMEM"):
        choose_sorted_bwd_blocks(512, 422_713, 800, 25)


def test_choosers_raise_when_budget_impossible():
    """No silent over-budget clamp: an unaffordable budget raises
    instead of returning a tiling that overflows (the old _LANE-clamp
    bug returned bn=128, bc=128 at ~12.7 MB against 6 MB)."""
    with pytest.raises(ValueError, match="vmem_budget"):
        choose_fused_blocks(256, 12288, 32, 512, vmem_budget=100_000)
    with pytest.raises(ValueError, match="vmem_budget"):
        choose_sparse_blocks(256, 422_713, 25, 32, 1024,
                             vmem_budget=100_000)


def test_ops_threads_block_overrides(monkeypatch):
    """Benchmarks/tests can pin blocks through the public dispatch:
    ops.mach_fused_xent forwards block_n/block_c/block_d to the kernel
    (which hands them to the chooser), and parity holds under pinned
    blocks."""
    from repro.kernels import mach_fused_xent as kmod

    seen = []
    orig = kmod.choose_fused_blocks

    def spy(n, d, r, b, block_n=None, block_c=None, block_d=None, **kw):
        seen.append((block_n, block_c, block_d))
        return orig(n, d, r, b, block_n, block_c, block_d, **kw)

    monkeypatch.setattr(kmod, "choose_fused_blocks", spy)
    n, d, r, b = 10, 96, 4, 64
    h, w, bias, y, g = _case(n, d, r, b, with_bias=True)
    out = ops.mach_fused_xent(h, w, y, num_buckets=b, bias=bias,
                              block_n=8, block_c=64, block_d=32,
                              use_pallas=True, interpret=True)
    assert seen and all(blk == (8, 64, 32) for blk in seen)
    lr = ref.mach_fused_xent_ref(h, w, y, b, bias=bias)
    np.testing.assert_allclose(np.asarray(lr), np.asarray(out),
                               rtol=1e-5, atol=1e-5)


def test_ops_csr_threads_block_overrides(monkeypatch):
    from repro.kernels import mach_fused_xent as kmod

    seen = []
    orig = kmod.choose_sparse_blocks

    def spy(n, d, r, b, j, block_n=None, block_c=None, block_d=None,
            **kw):
        seen.append((block_n, block_c, block_d))
        return orig(n, d, r, b, j, block_n, block_c, block_d, **kw)

    monkeypatch.setattr(kmod, "choose_sparse_blocks", spy)
    from benchmarks.common import make_csr_case
    n, d, r, b, nnz = 9, 96, 4, 32, 6
    indptr, indices, values, w, bias, y, g = make_csr_case(n, d, r, b,
                                                           nnz)
    out = ops.mach_fused_xent_csr(
        indptr, indices, values, w, y, num_buckets=b, nnz_max=nnz,
        bias=bias, block_n=8, block_c=64, block_d=32,
        use_pallas=True, interpret=True)
    assert seen and all(blk == (8, 64, 32) for blk in seen)
    lr = ref.mach_fused_xent_csr_ref(indptr, indices, values, w, y, b,
                                     bias=bias)
    np.testing.assert_allclose(np.asarray(lr), np.asarray(out),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# scalar-prefetch gather family (the high-nnz sparse path)
# ---------------------------------------------------------------------------

def _ell_case(n, d, r, b, nnz, seed=0):
    from benchmarks.common import make_csr_case
    indptr, indices, values, w, bias, y, g = make_csr_case(n, d, r, b,
                                                           nnz, seed=seed)
    cols, vals = ops.csr_to_ell(indptr, indices, values, nnz, d)
    return indptr, indices, values, cols, vals, w, bias, y, g


def _gather_vs_ref(indptr, indices, values, cols, vals, w, bias, y, g, b,
                   block_c=None, rtol=1e-4, atol=1e-5):
    lr = ref.mach_fused_xent_csr_ref(indptr, indices, values, w, y, b,
                                     bias=bias)
    lk = mach_fused_xent_gather_pallas(cols, vals, w, bias, y, b,
                                       block_c, True)
    np.testing.assert_allclose(np.asarray(lr), np.asarray(lk),
                               rtol=1e-5, atol=1e-5)
    sv = jax.lax.stop_gradient(values)     # kernel path: values are data
    argnums = (0,) if bias is None else (0, 1)

    def ref_loss(w_, b_=None):
        return jnp.sum(ref.mach_fused_xent_csr_ref(
            indptr, indices, sv, w_, y, b, bias=b_) * g)

    def ker_loss(w_, b_=None):
        return jnp.sum(mach_fused_xent_gather_pallas(
            cols, vals, w_, b_, y, b, block_c, True) * g)

    args = (w,) if bias is None else (w, bias)
    dr = jax.grad(ref_loss, argnums=argnums)(*args)
    dk = jax.grad(ker_loss, argnums=argnums)(*args)
    for name, a, k in zip(("dw", "dbias"), dr, dk):
        np.testing.assert_allclose(np.asarray(a), np.asarray(k),
                                   rtol=rtol, atol=atol, err_msg=name)


@pytest.mark.parametrize("n,d,r,b,nnz", [
    (9, 96, 4, 32, 6),        # ragged rows, several heads per block
    (5, 64, 3, 24, 8),        # padded head count
    (4, 48, 8, 16, 16),       # nnz rows spanning several grid steps
])
def test_gather_matches_densifying_ref(n, d, r, b, nnz):
    """The scalar-prefetch gather kernels against the densifying
    reference oracle: values + dW + dbias on ragged CSR batches."""
    case = _ell_case(n, d, r, b, nnz)
    _gather_vs_ref(*case, b)


def test_gather_no_bias_and_sub_lane_block():
    """No-bias path and a sub-lane column block (bc = 8 < the 128-lane
    tile) through the gather family."""
    n, d, r, b, nnz = 7, 64, 3, 16, 8
    (indptr, indices, values, cols, vals, w, bias, y, g) = _ell_case(
        n, d, r, b, nnz, seed=5)
    _gather_vs_ref(indptr, indices, values, cols, vals, w, None, y, g, b)
    _gather_vs_ref(indptr, indices, values, cols, vals, w, bias, y, g, b,
                   block_c=8)


def test_gather_high_nnz_acceptance_case():
    """ISSUE 8's promoted high-nnz case: (d=8192, R=8, B=64, nnz=1024)
    — the bag-of-words regime where the densify family's one-hot tile
    made the padded-ELL path non-viable — full parity (values + dW +
    dbias) through the gather kernels.  N=2 because interpret mode
    carries the full dW array through every grid step (cost ~ N·d per
    pass); the gather grid axes under test (C/bc, jp) are N-independent.
    """
    n, d, r, b, nnz = 2, 8192, 8, 64, 1024
    (indptr, indices, values, cols, vals, w, bias, y, g) = _ell_case(
        n, d, r, b, nnz, seed=11)
    sv = jax.lax.stop_gradient(values)

    lr, dr = jax.value_and_grad(lambda w_, b_: jnp.sum(
        ref.mach_fused_xent_csr_ref(indptr, indices, sv, w_, y, b,
                                    bias=b_) * g),
        argnums=(0, 1))(w, bias)
    lk, dk = jax.value_and_grad(lambda w_, b_: jnp.sum(
        mach_fused_xent_gather_pallas(cols, vals, w_, b_, y, b, None,
                                      True) * g),
        argnums=(0, 1))(w, bias)
    np.testing.assert_allclose(float(lr), float(lk), rtol=1e-6, atol=1e-4)
    for name, a, k in zip(("dw", "dbias"), dr, dk):
        np.testing.assert_allclose(np.asarray(a), np.asarray(k),
                                   rtol=1e-4, atol=1e-6, err_msg=name)


def test_choose_gather_blocks_nnz_and_d_independent():
    """The gather accounting's whole point: the budget never depends on
    nnz or d (W streams one gathered row at a time; ELL indices live in
    SMEM) — the paper-ODP d=422k at nnz from 8 to 100k all fit."""
    for j in (8, 1024, 100_000):
        bc, rp, bp, jp = choose_gather_blocks(256, 422_713, 25, 32, j)
        assert gather_tile_bytes(bc, rp) <= DEFAULT_VMEM_BUDGET
        assert jp == max(j, 1)
        assert (rp * bp) % bc == 0 and rp >= 25 and bp >= 32


def test_csr_dispatch_routes_by_nnz(monkeypatch):
    """ops.mach_fused_xent_csr auto-dispatch: nnz_max >=
    GATHER_NNZ_THRESHOLD routes to the gather family, below it to the
    densify family; sparse_impl overrides both ways; parity holds on
    the routed path."""
    calls = []
    orig = ops.mach_fused_xent_gather_pallas
    monkeypatch.setattr(
        ops, "mach_fused_xent_gather_pallas",
        lambda *a, **k: (calls.append("gather"), orig(*a, **k))[1])

    n, d, r, b = 3, 64, 4, 16
    lo = GATHER_NNZ_THRESHOLD // 32
    hi = GATHER_NNZ_THRESHOLD
    for nnz, impl, expect in [(lo, None, []),
                              (lo, "gather", ["gather"]),
                              (hi, None, ["gather"])]:
        calls.clear()
        (indptr, indices, values, _, _, w, bias, y, _) = _ell_case(
            n, d, r, b, nnz)
        out = ops.mach_fused_xent_csr(
            indptr, indices, values, w, y, num_buckets=b, nnz_max=nnz,
            bias=bias, sparse_impl=impl, use_pallas=True, interpret=True)
        assert calls == expect, (nnz, impl, calls)
        lr = ref.mach_fused_xent_csr_ref(indptr, indices, values, w, y,
                                         b, bias=bias)
        np.testing.assert_allclose(np.asarray(lr), np.asarray(out),
                                   rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="sparse_impl"):
        ops.mach_fused_xent_csr(indptr, indices, values, w, y,
                                num_buckets=b, nnz_max=hi,
                                sparse_impl="bogus", use_pallas=True,
                                interpret=True)


def test_no_onehot_tile_in_gather_jaxpr():
    """ISSUE 8 acceptance: scanning INTO the pallas kernel jaxprs
    (skip_primitives=()), the gather path has no (bn, jp, bd)-shaped
    one-hot intermediate — every gather tile is 2D — while the densify
    path provably has one (the detector works)."""
    from benchmarks.common import intermediate_avals

    n, d, r, b, nnz = 4, 96, 4, 32, 16
    (indptr, indices, values, _, _, w, bias, y, g) = _ell_case(
        n, d, r, b, nnz)

    def vag(impl):
        def f(w_, b_):
            return jax.value_and_grad(lambda ww, bb: jnp.sum(
                ops.mach_fused_xent_csr(
                    indptr, indices, values, ww, y, num_buckets=b,
                    nnz_max=nnz, bias=bb, sparse_impl=impl,
                    use_pallas=True, interpret=True) * g),
                argnums=(0, 1))(w_, b_)
        return jax.make_jaxpr(f)(w, bias).jaxpr

    def onehot_tiles(jaxpr):
        # a (bn, jp, bd) one-hot: nnz-sized middle axis crossed with a
        # real feature block (bd >= the 8-sublane tile) — benign 3D
        # reshapes like the (N, jp, 1) ELL widening or the (d, R, B)
        # W view don't match
        return [a.shape for a in intermediate_avals(
            jaxpr, skip_primitives=())
            if getattr(a, "ndim", 0) == 3
            and a.shape[1] >= nnz and a.shape[2] >= 8]

    densify_onehot = onehot_tiles(vag("densify"))
    assert densify_onehot, "detector broken: densify one-hot not seen"
    gather_onehot = onehot_tiles(vag("gather"))
    assert not gather_onehot, gather_onehot


# ---------------------------------------------------------------------------
# integration: head / model parity with the materializing path
# ---------------------------------------------------------------------------

def test_head_fused_loss_matches_loss():
    cfg = MACHConfig(1000, 16, 5)
    head = MACHOutputHead(cfg, 24)
    params = head.init(jax.random.key(0))
    h = jax.random.normal(jax.random.key(1), (7, 3, 24))
    labels = jax.random.randint(jax.random.key(2), (7, 3), 0, 1000)
    weights = (jnp.arange(21).reshape(7, 3) % 4 != 0).astype(jnp.float32)

    def mat(p):
        return head.loss(p, h, labels, weights)

    def fused(p):
        return head.fused_loss(p, h, labels, weights,
                               use_pallas=True, interpret=True)

    l0, g0 = jax.value_and_grad(mat)(params)
    l1, g1 = jax.value_and_grad(fused)(params)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(g0["kernel"]),
                               np.asarray(g1["kernel"]),
                               rtol=1e-4, atol=1e-6)


def test_model_loss_fused_flag_parity():
    cfg = ModelConfig(name="tiny", num_layers=2, d_model=32, num_heads=2,
                      num_kv_heads=1, d_ff=64, vocab_size=64,
                      dtype=jnp.float32, mach=MACHConfig(64, 8, 4))
    cfgf = dataclasses.replace(cfg, mach_fused_loss=True)
    m0, m1 = LanguageModel(cfg), LanguageModel(cfgf)
    params, _ = m0.init(jax.random.key(0))
    batch = {"tokens": jax.random.randint(jax.random.key(1), (4, 17), 0, 64)}
    (l0, _), g0 = jax.value_and_grad(m0.loss, has_aux=True)(params, batch)
    (l1, _), g1 = jax.value_and_grad(m1.loss, has_aux=True)(params, batch)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6, atol=1e-6)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_model_loss_fused_flag_routes_to_kernel(monkeypatch):
    """On CPU the flag's default dispatch falls back to the reference,
    so the plain parity test never proves the *kernel* routing.  Fake a
    TPU backend (with the kernel pinned to interpret mode) and check
    model.loss under the flag actually reaches mach_fused_xent_pallas
    and still matches the materialized path."""
    from repro.kernels import ops as ops_mod

    cfg = ModelConfig(name="tiny", num_layers=1, d_model=32, num_heads=2,
                      num_kv_heads=1, d_ff=64, vocab_size=64,
                      dtype=jnp.float32, mach=MACHConfig(64, 8, 4))
    m0 = LanguageModel(cfg)
    params, _ = m0.init(jax.random.key(0))
    batch = {"tokens": jax.random.randint(jax.random.key(1), (4, 9), 0, 64)}
    (l0, _), g0 = jax.value_and_grad(m0.loss, has_aux=True)(params, batch)

    calls = {"n": 0}
    orig = ops_mod.mach_fused_xent_pallas

    def spy(h2, w, bias, lbl, nb, bn, bc, bd, interpret):
        calls["n"] += 1
        return orig(h2, w, bias, lbl, nb, bn, bc, bd, True)  # interpret
    m1 = LanguageModel(dataclasses.replace(cfg, mach_fused_loss=True))
    with monkeypatch.context() as mp:
        mp.setattr(ops_mod, "_on_tpu", lambda: True)
        mp.setattr(ops_mod, "mach_fused_xent_pallas", spy)
        (l1, _), g1 = jax.value_and_grad(m1.loss, has_aux=True)(params,
                                                                batch)
    assert calls["n"] >= 1                          # kernel path taken
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6, atol=1e-6)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# structural claims: no (N, R·B) tensor, no (d+1, R·B) bias concat
# ---------------------------------------------------------------------------

def test_no_nrb_tensor_in_fused_jaxpr():
    # shared jaxpr walker (tier-1 runs from the repo root, so the
    # benchmarks package is importable alongside src/)
    from benchmarks.common import intermediate_avals

    # N > dp (the padded feature dim) so batch-carrying and
    # parameter-shaped intermediates are distinguishable by leading dim
    n, d, r, b = 256, 32, 8, 128
    h, w, bias, y, g = _case(n, d, r, b, with_bias=True)

    def fused_vag(h_, w_, b_):
        return jax.value_and_grad(lambda hh, ww, bb: jnp.sum(
            mach_fused_xent_pallas(hh, ww, bb, y, b, None, None, None,
                                   True) * g),
            argnums=(0, 1, 2))(h_, w_, b_)

    def mat_vag(h_, w_, b_):
        return jax.value_and_grad(lambda hh, ww, bb: jnp.sum(
            ref.mach_fused_xent_ref(hh, ww, y, b, bias=bb) * g),
            argnums=(0, 1, 2))(h_, w_, b_)

    nrb = n * r * b

    def batch_sizes(fn):
        return [a.size for a in intermediate_avals(
            jax.make_jaxpr(fn)(h, w, bias).jaxpr)
            if getattr(a, "ndim", 0) >= 1 and a.size
            and n <= a.shape[0] < n + 128]

    fused_sizes = batch_sizes(fused_vag)
    mat_sizes = batch_sizes(mat_vag)
    # the materializing path forms (N, R·B) twice (fwd + bwd)...
    assert any(s >= nrb for s in mat_sizes)
    # ...the fused path never does, in either pass
    assert all(s < nrb for s in fused_sizes), \
        sorted(fused_sizes, reverse=True)[:5]


def test_dense_fused_loss_has_no_bias_concat():
    """MACHLinear.fused_loss on dense inputs no longer folds the bias
    by concatenating a row onto W: no (d+1, R·B)-shaped intermediate
    (nor its concat cotangent) in either pass — the bias is an
    in-kernel operand."""
    from benchmarks.common import intermediate_avals
    from repro.core.mach import MACHLinear

    cfg = MACHConfig(300, 8, 5)
    dim = 24
    m = MACHLinear(cfg, dim, fused=True)
    params = m.init(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (10, dim))
    y = jax.random.randint(jax.random.key(2), (10,), 0, 300)

    def vag(p):
        return jax.value_and_grad(
            lambda q: m.fused_loss(q, x, y, use_pallas=True,
                                   interpret=True))(p)

    avals = intermediate_avals(jax.make_jaxpr(vag)(params).jaxpr)
    rb = cfg.num_repetitions * cfg.num_buckets
    concat_shapes = [a.shape for a in avals
                     if getattr(a, "ndim", 0) == 2
                     and a.shape[0] == dim + 1 and a.shape[1] >= rb]
    assert not concat_shapes, concat_shapes
