"""Compile every Pallas kernel that ``ops.py`` dispatches to on TPU, at
the widths ``chip_smoke.py`` runs, for a described (not attached) TPU
v5e.

Nothing executes: each test lowers the kernel against a ``v5e:2x2``
topology description and asks the TPU compiler (Mosaic) to compile it,
which refuses what interpret mode cannot see — unaligned blocks,
lane-splitting reshapes, ops with no TPU lowering, VMEM overflow.  Each
test asserts that the compiled program holds the kernel
(``tpu_custom_call``), so a dispatch that silently fell back to jnp
fails here, and that a MACH kernel carries its program phase
(``kernels/phase.py``) in its frontend attributes, where the device
trace reads it.

The topology is described inside a module fixture, never at import:
only one process may load the TPU compiler library at a time, and the
test workers all import this file.
"""

import collections
import contextlib
import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.lru_scan import lru_scan_pallas
from repro.kernels.mach_candidates import mach_candidate_topk_pallas
from repro.kernels.mach_decode import mach_decode_pallas
from repro.kernels.mach_fused_xent import (choose_sorted_bwd_blocks,
                                           choose_sparse_blocks,
                                           mach_fused_xent_gather_pallas,
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


def _kernels(text):
    """(kernel name, ``mach_phase``) of each Mosaic custom call in a
    compiled text; the name without the prefixes autodiff adds, the
    phase "" where it has none."""
    calls = [ins for ins in re.split(r"\n\s+(?=(?:ROOT )?%)", text)
             if 'custom_call_target="tpu_custom_call"' in ins]
    return [(re.sub(r"^(?:ROOT )?%(?:transpose_)?(?:jvp_)?|_*(?:\.\d+)? = .*",
                    "", ins.split("\n")[0]),
             m.group(1) if (m := re.search(r'"mach_phase":"([^"]+)"', ins))
             else "") for ins in calls]


def _kernel_phases(text):
    """The ``mach_phase`` of each Mosaic custom call in a compiled text
    ("" where it has none)."""
    return [p for _, p in _kernels(text)]


# Entry ops that do no device work of their own, and the copies XLA
# adds to move data between layouts (the trace counts copies as
# untagged time; PERF.md names them).
_FREE_OPS = {"parameter", "constant", "tuple", "get-tuple-element",
             "bitcast", "copy", "copy-start", "copy-done"}


def _untagged_ops(text):
    """The entry computation's ops with no ``mach_phase``, bar
    ``_FREE_OPS``, counted by name without its numeric suffix.  These
    instructions are the op events of the device trace."""
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    out = collections.Counter()
    for ins in re.split(r"\n\s+(?=(?:ROOT )?%)", entry)[1:]:
        name, op = re.match(r"(?:ROOT )?%([\w.\-]+) = .*? ([\w\-]+)\(",
                            ins).groups()
        assert op not in ("while", "conditional", "call"), ins[:120]
        if op not in _FREE_OPS and "mach_phase" not in ins:
            out[re.sub(r"\.\d+$", "", name)] += 1
    return out


def _assert_kernel(text, *phases):
    """The program holds a kernel; with ``phases``, its kernels carry
    exactly these."""
    assert "tpu_custom_call" in text
    if phases:
        assert sorted(set(_kernel_phases(text))) == sorted(phases)


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
    _assert_kernel(text, "loss.fwd", "loss.bwd")
    # the backward over feature-sorted entries, one kernel for both of
    # its phases; the forward's blocks as the d-sweep backward left them
    assert sorted(_kernels(text)) == [
        ("mach_fused_xent_sparse_bwd_sorted", "loss.bwd"),
        ("mach_fused_xent_sparse_fwd", "loss.fwd")]
    assert choose_sparse_blocks(n, ODP_D, ODP_R, ODP_B, ODP_NNZ)[:3] == \
        (8, 800, 256)
    assert choose_sorted_bwd_blocks(n, ODP_D, 800, ODP_R) == (256, n)


def test_sparse_fused_xent_bf16(one_chip):
    """A bf16 head through the CSR loss: the backward's single-row
    loads of W need it widened to f32 first."""
    n, d, r, b, nnz = 64, 4096, 8, 64, 16

    def loss(cols, vals, w, y):
        return mach_fused_xent_sparse_pallas(cols, vals, w, None, y,
                                             b).mean()

    text = _compile(jax.value_and_grad(loss, argnums=2), one_chip,
                    ((n, nnz), jnp.int32), ((n, nnz), jnp.bfloat16),
                    ((d, r * b), jnp.bfloat16), ((n, r), jnp.int32))
    _assert_kernel(text, "loss.fwd", "loss.bwd")


def test_dense_fused_xent_lm_head(one_chip):
    """LM-head training loss at recurrentgemma-2b's head widths."""
    n = 512

    def loss(h, w, y):
        return mach_fused_xent_pallas(h, w, None, y, LM_B).mean()

    text = _compile(jax.value_and_grad(loss, argnums=(0, 1)), one_chip,
                    ((n, LM_D), jnp.float32),
                    ((LM_D, LM_R * LM_B), jnp.float32),
                    ((n, LM_R), jnp.int32))
    _assert_kernel(text, "loss.fwd", "loss.bwd")


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
    _assert_kernel(text, "decode.topk")


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
    _assert_kernel(text, "decode.topk")


def test_topk_retrieval_1m(one_chip):
    """K=1,048,576, R=16, B=8192: the (R·B)-wide row cannot sit in VMEM
    with its multi-hot block, so the kernel tiles R·B."""
    def fn(probs, coeffs):
        return mach_topk_pallas(probs, num_classes=2**20, k=10,
                                inline_coeffs=coeffs, inline_shift=19)
    text = _compile(fn, one_chip, ((32, 16, 8192), jnp.float32),
                    ((16,), jnp.uint32))
    _assert_kernel(text, "decode.topk")


def test_top1_lm_inline(one_chip):
    def fn(probs, coeffs):
        return mach_decode_pallas(probs, num_classes=LM_V,
                                  inline_coeffs=coeffs, inline_shift=21)
    text = _compile(fn, one_chip, ((8, LM_R, LM_B), jnp.float32),
                    ((LM_R,), jnp.uint32))
    _assert_kernel(text, "decode.topk")


def test_candidate_topk(one_chip):
    """Count-min candidate filter at K=1,048,576, R=16, B=8192."""
    r, b, ell = 16, 8192, 256

    def fn(probs, inverted, coeffs):
        return mach_candidate_topk_pallas(
            probs, inverted, num_classes=2**20, k=10, m=4, t=2,
            inline_coeffs=coeffs, inline_shift=19)
    text = _compile(fn, one_chip, ((64, r, b), jnp.float32),
                    ((r * b, ell), jnp.int32), ((r,), jnp.uint32))
    _assert_kernel(text, "decode.topk")


# ---------------------------------------------------------------------------
# the whole MACH head step: phase tags, and a program they leave unchanged
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("features", ["csr", "dense"])
def test_head_step_phases(one_chip, monkeypatch, features):
    """The benchmark's head step (``MACHLinear(fused=True)``, AdamW,
    ``make_head_step``) at a tiny size through the TPU dispatch: its
    compiled ops carry the three training phases, and compiled with the
    tags switched off it is the same program up to names and
    annotations (``tools/compiled_hlo.py``)."""
    from repro.core import MACHConfig, MACHLinear
    from repro.data.extreme import SparseBatch
    from repro.kernels import mach_fused_xent, ops, phase
    from repro.optim import adamw
    from repro.train.trainer import make_head_step
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    from compiled_hlo import normalize

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    n, d, r, b, nnz = 64, 1024, 4, 32, 8
    model = MACHLinear(MACHConfig(num_classes=1000, num_buckets=b,
                                  num_repetitions=r, hash_kind="mult_shift"),
                       d, fused=True)
    opt = adamw(1e-3)

    def shape(s, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    params = {"w": shape((d, r, b)), "b": shape((r, b))}
    state = jax.tree.map(lambda a: shape(a.shape, a.dtype),
                         jax.eval_shape(opt.init, params))
    x = (SparseBatch(shape((n + 1,), jnp.int32), shape((n * nnz,), jnp.int32),
                     shape((n * nnz,)), num_features=d, nnz_max=nnz)
         if features == "csr" else shape((n, d)))
    y = shape((n,), jnp.int32)

    def compiled():
        with jax.default_matmul_precision("default"):
            return make_head_step(model.loss, opt).lower(
                params, state, x, y).compile().as_text()

    tagged = compiled()
    assert set(re.findall(r'mach_phase="([\w.]+)"', tagged)) == {
        "loss.fwd", "loss.bwd", "optim"}
    # every op but four carries its phase: XLA rebuilds the CSR->ELL
    # gather of the column ids, and adds an index operand to each of the
    # backward's two sorts, and carries no tag over
    assert _untagged_ops(tagged) == (
        {"fusion": 1, "pad_clamp_fusion": 1, "iota": 2}
        if features == "csr" else {})
    monkeypatch.setattr(phase, "tag", lambda p: contextlib.nullcontext())
    monkeypatch.setattr(mach_fused_xent, "_kernel_tags", lambda *a: {})
    plain = compiled()
    assert "mach_phase" not in plain
    assert normalize(tagged) == normalize(plain)


# Untagged ops of the decode cells' calls (N = 256, top-10), pinned.
# In ODP's meta_probs XLA rewrites the densify's 2-D scatter-add into a
# 1-D one over the flattened (N * d) buffer, and none of the ops it
# makes carries the tag: the flat zero fill (``broadcast``), the scatter
# fusion (``fusion``), the relayout back to (N, d) for the projection
# (``reshape``), the linear index and its bounds check (the
# ``*_reduce_fusion``, ``broadcast_clamp_fusion`` and
# ``broadcast_select_fusion``); the cumulative sums behind
# ``jnp.repeat``'s row ids come apart into untagged ``reduce-window``,
# ``slice`` and small fusions.  In predict_topk the table's pad becomes
# an untagged reshape (named after the ``broadcast_in_dim`` it was).
DECODE_UNTAGGED = {
    "odp": ({"broadcast": 2, "fusion": 1, "reshape": 1,
             "broadcast_clamp_fusion": 1, "and_reduce_fusion": 1,
             "multiply_reduce_fusion": 1, "broadcast_select_fusion": 1,
             "reduce-window": 5, "slice": 3, "slice_reduce_fusion": 2,
             "add_bitcast_fusion": 1, "pad_bitcast_fusion": 1,
             "pad_slice_fusion": 1, "broadcast_add_fusion": 1},
            {"broadcast_in_dim": 1}),
    "imagenet21k": ({}, {"broadcast_in_dim": 1}),
}


@pytest.mark.parametrize("cell", ["odp", "imagenet21k"])
def test_decode_untagged_ops(one_chip, monkeypatch, cell):
    """The benchmark's decode calls at the decode cells' widths and
    precision, compiled for the chip: which ops lose their phase."""
    from repro.core import MACHConfig, MACHLinear, estimators
    from repro.data.extreme import SparseBatch
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    n, k, (classes, d, b, r) = 256, 10, {
        "odp": (ODP_K, ODP_D, ODP_B, ODP_R),
        "imagenet21k": (21_841, 6144, 512, 20)}[cell]
    model = MACHLinear(MACHConfig(num_classes=classes, num_buckets=b,
                                  num_repetitions=r, hash_kind="mult_shift"),
                       d, fused=True)

    def shape(s, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    x = (SparseBatch(shape((n + 1,), jnp.int32),
                     shape((n * ODP_NNZ,), jnp.int32),
                     shape((n * ODP_NNZ,)), num_features=d, nnz_max=ODP_NNZ)
         if cell == "odp" else shape((n, d)))
    topk = functools.partial(estimators.predict_topk, k=k,
                             estimator="unbiased")
    with jax.default_matmul_precision("highest"):
        meta = jax.jit(model.meta_probs).lower(
            {"w": shape((d, r, b)), "b": shape((r, b))}, x).compile()
        ids = jax.jit(topk).lower(shape((r, n, b)),
                                  shape((r, classes), jnp.int32)).compile()
    assert (_untagged_ops(meta.as_text()), _untagged_ops(ids.as_text())) \
        == DECODE_UNTAGGED[cell]


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
