"""Compile every Pallas kernel that ``ops.py`` dispatches to on TPU, at
the widths ``chip_smoke.py`` runs, for a described (not attached) TPU
v5e.

Nothing executes: each test lowers the kernel against a ``v5e:2x2``
topology description and asks the TPU compiler (Mosaic) to compile it,
which refuses what interpret mode cannot see — unaligned blocks,
lane-splitting reshapes, ops with no TPU lowering, VMEM overflow.  Each
test asserts that the compiled program holds the kernel
(``tpu_custom_call``), so a dispatch that silently fell back to jnp
fails here.

The topology is described inside a module fixture, never at import:
only one process may load the TPU compiler library at a time, and the
test workers all import this file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.lru_scan import lru_scan_pallas
from repro.kernels.mach_candidates import mach_candidate_topk_pallas
from repro.kernels.mach_decode import mach_decode_pallas
from repro.kernels.mach_fused_xent import (mach_fused_xent_gather_pallas,
                                           mach_fused_xent_pallas,
                                           mach_fused_xent_sparse_pallas)
from repro.kernels.mach_topk import mach_topk_pallas
from repro.kernels.mach_xent import mach_xent_pallas

# ODP, the paper's Table 2 run (configs/odp_mach.py)
ODP_K, ODP_D, ODP_B, ODP_R, ODP_NNZ = 105_033, 422_713, 32, 25, 120
# recurrentgemma-2b's MACH head (configs/recurrentgemma_2b.py)
LM_V, LM_D, LM_B, LM_R = 256_000, 2560, 2048, 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip; return the compiled text.
    Matmul precision is the chip's default, not the f32 that
    ``conftest.py`` sets for the CPU oracles (Mosaic refuses f32
    precision on bf16 operands)."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    with jax.default_matmul_precision("default"):
        return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(text):
    assert "tpu_custom_call" in text


# ---------------------------------------------------------------------------
# training: fused projection + MACH cross-entropy
# ---------------------------------------------------------------------------

def test_sparse_fused_xent_odp(one_chip):
    """ODP training step: CSR batch at d=422,713, forward and backward."""
    n = 512

    def loss(cols, vals, w, bias, y):
        return mach_fused_xent_sparse_pallas(cols, vals, w, bias, y,
                                             ODP_B).mean()

    text = _compile(jax.value_and_grad(loss, argnums=(2, 3)), one_chip,
                    ((n, ODP_NNZ), jnp.int32), ((n, ODP_NNZ), jnp.float32),
                    ((ODP_D, ODP_R * ODP_B), jnp.float32),
                    ((ODP_R * ODP_B,), jnp.float32), ((n, ODP_R), jnp.int32))
    _assert_kernel(text)


def test_dense_fused_xent_lm_head(one_chip):
    """LM-head training loss at recurrentgemma-2b's head widths."""
    n = 512

    def loss(h, w, y):
        return mach_fused_xent_pallas(h, w, None, y, LM_B).mean()

    text = _compile(jax.value_and_grad(loss, argnums=(0, 1)), one_chip,
                    ((n, LM_D), jnp.float32),
                    ((LM_D, LM_R * LM_B), jnp.float32),
                    ((n, LM_R), jnp.int32))
    _assert_kernel(text)


@pytest.mark.xfail(strict=True, reason=(
    "mach_fused_xent_gather_pallas: its (1, bc) W-row blocks break "
    "Mosaic's (8, 128) tiling rule, and its dW rows accumulate through "
    "revisited output blocks, which a TPU never re-reads from HBM; "
    "ops.mach_fused_xent_csr raises on TPU instead of dispatching it"))
def test_gather_fused_xent_500k(one_chip):
    """The 500k-label sparse job (B=4096, R=8, d=1024, nnz=64)."""
    n, d, b, r, nnz = 256, 1024, 4096, 8, 64

    def loss(cols, vals, w, bias, y):
        return mach_fused_xent_gather_pallas(cols, vals, w, bias, y,
                                             b).mean()

    text = _compile(jax.value_and_grad(loss, argnums=(2, 3)), one_chip,
                    ((n, nnz), jnp.int32), ((n, nnz), jnp.float32),
                    ((d, r * b), jnp.float32), ((r * b,), jnp.float32),
                    ((n, r), jnp.int32))
    _assert_kernel(text)


def test_mach_xent_lm_head(one_chip):
    """Materialized-logits MACH cross-entropy, forward and backward."""
    n = 512

    def loss(logits, y):
        return mach_xent_pallas(logits, y).mean()

    text = _compile(jax.value_and_grad(loss), one_chip,
                    ((n, LM_R, LM_B), jnp.float32), ((n, LM_R), jnp.int32))
    _assert_kernel(text)


# ---------------------------------------------------------------------------
# decode: streaming top-k, top-1, candidate filter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("estimator", ["unbiased", "min", "median"])
def test_topk_odp_table(one_chip, estimator):
    """ODP decode: top-10 over K=105,033 through the (R, K) table."""
    fn = functools.partial(mach_topk_pallas, num_classes=ODP_K, k=10,
                           estimator=estimator)
    text = _compile(fn, one_chip, ((256, ODP_R, ODP_B), jnp.float32),
                    ((ODP_R, ODP_K), jnp.int32))
    _assert_kernel(text)


@pytest.mark.parametrize("estimator", ["unbiased", "min", "median"])
def test_topk_lm_inline(one_chip, estimator):
    """Serving's sampling candidates: top-50 over a 256k vocabulary with
    in-register multiply-shift hashing."""
    def fn(probs, coeffs):
        return mach_topk_pallas(probs, num_classes=LM_V, k=50,
                                estimator=estimator, inline_coeffs=coeffs,
                                inline_shift=21)
    text = _compile(fn, one_chip, ((8, LM_R, LM_B), jnp.float32),
                    ((LM_R,), jnp.uint32))
    _assert_kernel(text)


def test_topk_retrieval_1m(one_chip):
    """K=1,048,576, R=16, B=8192: the (R·B)-wide row cannot sit in VMEM
    with its multi-hot block, so the kernel tiles R·B."""
    def fn(probs, coeffs):
        return mach_topk_pallas(probs, num_classes=2**20, k=10,
                                inline_coeffs=coeffs, inline_shift=19)
    text = _compile(fn, one_chip, ((32, 16, 8192), jnp.float32),
                    ((16,), jnp.uint32))
    _assert_kernel(text)


def test_top1_lm_inline(one_chip):
    def fn(probs, coeffs):
        return mach_decode_pallas(probs, num_classes=LM_V,
                                  inline_coeffs=coeffs, inline_shift=21)
    text = _compile(fn, one_chip, ((8, LM_R, LM_B), jnp.float32),
                    ((LM_R,), jnp.uint32))
    _assert_kernel(text)


def test_candidate_topk(one_chip):
    """Count-min candidate filter at K=1,048,576, R=16, B=8192."""
    r, b, ell = 16, 8192, 256

    def fn(probs, inverted, coeffs):
        return mach_candidate_topk_pallas(
            probs, inverted, num_classes=2**20, k=10, m=4, t=2,
            inline_coeffs=coeffs, inline_shift=19)
    text = _compile(fn, one_chip, ((64, r, b), jnp.float32),
                    ((r * b, ell), jnp.int32), ((r,), jnp.uint32))
    _assert_kernel(text)


# ---------------------------------------------------------------------------
# the LM substrate of the served model
# ---------------------------------------------------------------------------

def test_lru_scan(one_chip):
    fn = lru_scan_pallas
    text = _compile(fn, one_chip, ((4, 16, LM_D), jnp.float32),
                    ((4, 16, LM_D), jnp.float32), ((4, LM_D), jnp.float32))
    _assert_kernel(text)


def test_flash_attention(one_chip):
    fn = functools.partial(flash_attention_pallas, causal=True, window=2048)
    text = _compile(fn, one_chip, ((1, 1024, 10, 256), jnp.bfloat16),
                    ((1, 1024, 1, 256), jnp.bfloat16),
                    ((1, 1024, 1, 256), jnp.bfloat16))
    _assert_kernel(text)


# ---------------------------------------------------------------------------
# what the TPU dispatch refuses: it raises, naming the kernel
# ---------------------------------------------------------------------------

def test_tpu_dispatch_raises_for_gather(monkeypatch):
    """nnz >= GATHER_NNZ_THRESHOLD routes to the gather family, which
    does not run on TPU: the dispatch must raise, not fall back."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    n, d, r, b, nnz = 4, 64, 2, 16, 512
    with pytest.raises(NotImplementedError,
                       match="mach_fused_xent_gather_pallas"):
        jax.eval_shape(
            lambda ip, ix, v, w, y: ops.mach_fused_xent_csr(
                ip, ix, v, w, y, num_buckets=b, nnz_max=nnz),
            jax.ShapeDtypeStruct((n + 1,), jnp.int32),
            jax.ShapeDtypeStruct((n * nnz,), jnp.int32),
            jax.ShapeDtypeStruct((n * nnz,), jnp.float32),
            jax.ShapeDtypeStruct((d, r * b), jnp.float32),
            jax.ShapeDtypeStruct((n, r), jnp.int32))


def test_tpu_dispatch_raises_for_table_candidates(monkeypatch):
    """The fused candidate filter hashes in-register; a table-mode call
    on TPU raises instead of leaving the chip for jnp."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    r, b, k_cls = 2, 16, 100
    with pytest.raises(NotImplementedError,
                       match="mach_candidate_topk_pallas"):
        jax.eval_shape(
            lambda p, tab, inv: ops.mach_topk_candidates(
                p, tab, inverted=inv, num_classes=k_cls, k=5, m=2),
            jax.ShapeDtypeStruct((3, r, b), jnp.float32),
            jax.ShapeDtypeStruct((r, k_cls), jnp.int32),
            jax.ShapeDtypeStruct((r * b, 128), jnp.int32))
