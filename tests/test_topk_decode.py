"""Streaming top-k decode kernel vs the reference estimator+top_k path.

The fused kernel must match ``estimate_class_probs`` + ``jax.lax.top_k``
(indices and values, up to tie order) for all three paper estimators,
both hash sources, and non-divisible N/K — in interpret mode on CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MACHConfig
from repro.core.estimators import predict_topk
from repro.core.mach import mach_meta_probs
from repro.kernels import ops, ref
from repro.kernels.mach_decode import choose_decode_blocks, mach_decode_pallas
from repro.kernels.mach_topk import mach_topk_pallas

ESTIMATORS = ("unbiased", "min", "median")


def _assert_topk_matches(probs, tab, kv, ki, rv, ri, estimator,
                         rtol=1e-5, atol=1e-6):
    """Values must match; indices must match up to tie order (where they
    differ, the reference score at the kernel's index must equal the
    reference value at that rank)."""
    kv, ki = np.asarray(kv), np.asarray(ki)
    rv, ri = np.asarray(rv), np.asarray(ri)
    np.testing.assert_allclose(kv, rv, rtol=rtol, atol=atol)
    n = kv.shape[0]
    # no duplicate classes within a row
    for i in range(n):
        assert len(set(ki[i].tolist())) == ki.shape[1]
    if np.array_equal(ki, ri):
        return
    scores = np.asarray(ref.mach_estimator_scores_ref(probs, tab, estimator))
    np.testing.assert_allclose(scores[np.arange(n)[:, None], ki], rv,
                               rtol=rtol, atol=atol)


@pytest.mark.parametrize("k,b,r,n,topk", [
    (1000, 32, 8, 16, 10),     # paper-ish ODP block
    (5003, 64, 4, 7, 50),      # non-divisible K, odd N
    (257, 16, 3, 1, 5),        # single row
    (300, 4, 2, 3, 128),       # topk == lane width, tiny B
])
@pytest.mark.parametrize("estimator", ESTIMATORS)
def test_topk_table_mode(k, b, r, n, topk, estimator):
    cfg = MACHConfig(k, b, r)
    tab = cfg.table()
    probs = jax.nn.softmax(
        jax.random.normal(jax.random.key(k + n), (n, r, b)), -1)
    rv, ri = ref.mach_topk_ref(probs, tab, topk, estimator)
    kv, ki = mach_topk_pallas(probs, tab, num_classes=k, k=topk,
                              estimator=estimator, interpret=True)
    _assert_topk_matches(probs, tab, kv, ki, rv, ri, estimator)


@pytest.mark.parametrize("estimator", ESTIMATORS)
@pytest.mark.parametrize("k,b,r", [(1000, 32, 8), (4096, 128, 3)])
def test_topk_inline_mode(k, b, r, estimator):
    cfg = MACHConfig(k, b, r, hash_kind="mult_shift")
    fam = cfg.family
    probs = jax.nn.softmax(jax.random.normal(jax.random.key(1), (9, r, b)), -1)
    rv, ri = ref.mach_topk_ref(probs, cfg.table(), 20, estimator)
    kv, ki = mach_topk_pallas(
        probs, num_classes=k, k=20, estimator=estimator,
        inline_coeffs=jnp.asarray(fam.coeffs()), inline_shift=fam.shift,
        interpret=True)
    _assert_topk_matches(probs, cfg.table(), kv, ki, rv, ri, estimator)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_topk_dtypes(dtype):
    k, b, r, n = 1000, 32, 6, 5
    cfg = MACHConfig(k, b, r)
    tab = cfg.table()
    probs = jax.nn.softmax(
        jax.random.normal(jax.random.key(3), (n, r, b)), -1).astype(dtype)
    rv, ri = ref.mach_topk_ref(probs.astype(jnp.float32), tab, 8)
    kv, ki = mach_topk_pallas(probs, tab, num_classes=k, k=8, interpret=True)
    _assert_topk_matches(probs.astype(jnp.float32), tab, kv, ki, rv, ri,
                         "unbiased")


def test_topk_k1_matches_top1_kernel():
    """k=1 degenerates to the fused top-1 decode (same argmax rule)."""
    k, b, r, n = 511, 16, 5, 6
    cfg = MACHConfig(k, b, r)
    tab = cfg.table()
    probs = jax.nn.softmax(jax.random.normal(jax.random.key(4), (n, r, b)), -1)
    v1, i1 = mach_decode_pallas(probs, tab, num_classes=k, interpret=True)
    vk, ik = mach_topk_pallas(probs, tab, num_classes=k, k=1,
                              estimator="unbiased", interpret=True)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(ik[:, 0]))
    # top-1 kernel reports raw summed scores; top-k reports Eq. 2 values
    np.testing.assert_allclose(
        np.asarray((b / (b - 1.0)) * (v1 / r - 1.0 / b)),
        np.asarray(vk[:, 0]), rtol=1e-5, atol=1e-6)


def test_topk_ties_across_blocks():
    """Uniform probs -> every class ties; the streaming merge must keep
    the lowest class ids, like lax.top_k on the full matrix."""
    k, b, r, n, topk = 300, 4, 2, 3, 8
    cfg = MACHConfig(k, b, r)
    tab = cfg.table()
    probs = jnp.ones((n, r, b)) / b
    _, ki = mach_topk_pallas(probs, tab, num_classes=k, k=topk,
                             interpret=True, block_k=128)
    np.testing.assert_array_equal(
        np.asarray(ki), np.broadcast_to(np.arange(topk), (n, topk)))


@pytest.mark.parametrize("estimator", ["min", "median"])
def test_topk_paper_scale_blocks(estimator):
    """ODP-like (R=25, B=32) min/median config: the bk chooser must
    shrink for the extra (R, bn, bk) VMEM tensor and stay correct."""
    k, b, r, n = 4000, 32, 25, 32
    cfg = MACHConfig(k, b, r)
    tab = cfg.table()
    probs = jax.nn.softmax(
        jax.random.normal(jax.random.key(5), (n, r, b)), -1)
    rv, ri = ref.mach_topk_ref(probs, tab, 16, estimator)
    kv, ki = mach_topk_pallas(probs, tab, num_classes=k, k=16,
                              estimator=estimator, interpret=True)
    _assert_topk_matches(probs, tab, kv, ki, rv, ri, estimator)


def test_topk_validation():
    cfg = MACHConfig(100, 16, 2)
    probs = jnp.ones((2, 2, 16)) / 16
    with pytest.raises(ValueError):
        mach_topk_pallas(probs, cfg.table(), num_classes=100, k=0,
                         interpret=True)
    with pytest.raises(ValueError):
        mach_topk_pallas(probs, cfg.table(), num_classes=100, k=101,
                         interpret=True)
    with pytest.raises(ValueError):
        mach_topk_pallas(probs, cfg.table(), num_classes=100, k=5,
                         estimator="mode", interpret=True)


# ---------------------------------------------------------------------------
# blocking / padding paths
# ---------------------------------------------------------------------------

def test_choose_decode_blocks_rounds_bn():
    """bn is clamped to a multiple of 8 whatever the caller passes."""
    for block_n, want in [(1, 8), (5, 8), (8, 8), (13, 16), (100, 104),
                          (None, 8)]:
        bn, bk, nh = choose_decode_blocks(7, 4, 16, block_n, None)
        if block_n is not None:
            assert bn == want
        assert bn % 8 == 0
        assert bk % 128 == 0 and 1 <= nh <= 4


def test_choose_decode_blocks_budget_sweep():
    """Explicit tile-byte accounting: over a sweep of VMEM budgets and
    estimator / kcap configurations the chosen tile always fits and bk
    is lane-aligned; median never picks a wider tile than unbiased at
    the same budget (its per-repetition score cube is accounted); at
    retrieval widths (R·B = 131,072) the repetitions are tiled instead
    of overflowing; the floor tile overflowing raises instead of
    silently clamping."""
    from repro.kernels.mach_decode import decode_tile_bytes
    r, b, n = 8, 128, 8
    for budget in (4 * 2**20, 6 * 2**20, 16 * 2**20):
        tiles = {}
        for est in ("unbiased", "min", "median"):
            for kcap in (128, 512):
                bn, bk, nh = choose_decode_blocks(
                    n, r, b, vmem_budget=budget, estimator=est, kcap=kcap)
                assert bk % 128 == 0 and bk >= 128 and 1 <= nh <= r
                assert decode_tile_bytes(bn, bk, nh, b, estimator=est,
                                         kcap=kcap, r=r) <= budget
                tiles[est, kcap] = bk * nh
        for kcap in (128, 512):
            assert tiles["median", kcap] <= tiles["unbiased", kcap]
    # K=1M retrieval setting: the whole (R, bn, B) block cannot fit, so
    # the chooser tiles R rather than raise
    bn, bk, nh = choose_decode_blocks(32, 16, 8192, kcap=128)
    assert nh < 16
    assert decode_tile_bytes(bn, bk, nh, 8192, kcap=128) <= 6 * 2**20
    # larger budget -> never smaller tiles
    sizes = [bk * nh for _, bk, nh in (
        choose_decode_blocks(n, r, b, vmem_budget=bud, estimator="min")
        for bud in (4 * 2**20, 6 * 2**20, 32 * 2**20))]
    assert sizes == sorted(sizes)
    # floor overflow is an error, not a silent VMEM blowout ...
    with pytest.raises(ValueError):
        choose_decode_blocks(n, r, b, vmem_budget=2**17,
                             estimator="median")
    # ... unless the caller takes responsibility with an explicit block_k
    assert choose_decode_blocks(32, r, b, None, 256,
                                vmem_budget=2**17) == (32, 256, r)


@pytest.mark.parametrize("block_n", [5, 13])
def test_decode_padding_path_odd_block_n(block_n):
    """N not divisible by (rounded) bn AND K not divisible by bk stays
    correct for both the top-1 and top-k kernels."""
    k, b, r, n = 300, 8, 3, 13
    cfg = MACHConfig(k, b, r)
    tab = cfg.table()
    probs = jax.nn.softmax(
        jax.random.normal(jax.random.key(7), (n, r, b)), -1)
    rv, ri = ref.mach_decode_ref(probs, tab)
    kv, ki = mach_decode_pallas(probs, tab, num_classes=k, interpret=True,
                                block_n=block_n, block_k=128)
    np.testing.assert_array_equal(np.asarray(ri), np.asarray(ki))
    np.testing.assert_allclose(np.asarray(rv), np.asarray(kv), rtol=1e-5)
    tv, ti = ref.mach_topk_ref(probs, tab, 9)
    pv, pi = mach_topk_pallas(probs, tab, num_classes=k, k=9, interpret=True,
                              block_n=block_n, block_k=128)
    _assert_topk_matches(probs, tab, pv, pi, tv, ti, "unbiased")


# ---------------------------------------------------------------------------
# dispatch layers: ops.mach_topk and estimators.predict_topk
# ---------------------------------------------------------------------------

def test_ops_mach_topk_leading_dims_and_fallback_parity():
    cfg = MACHConfig(100, 16, 4)
    tab = cfg.table()
    probs = jax.nn.softmax(
        jax.random.normal(jax.random.key(0), (2, 3, 4, 16)), -1)
    v1, i1 = ops.mach_topk(probs, tab, num_classes=100, k=7,
                           use_pallas=True, interpret=True)
    v2, i2 = ops.mach_topk(probs, tab, num_classes=100, k=7,
                           use_pallas=False)
    assert v1.shape == (2, 3, 7) and i1.shape == (2, 3, 7)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2),
                               rtol=1e-5, atol=1e-6)


def test_ops_mach_topk_inline_fallback_rebuilds_table():
    cfg = MACHConfig(512, 32, 4, hash_kind="mult_shift")
    fam = cfg.family
    probs = jax.nn.softmax(jax.random.normal(jax.random.key(8), (5, 4, 32)), -1)
    v1, i1 = ops.mach_topk(probs, num_classes=512, k=6,
                           inline_coeffs=jnp.asarray(fam.coeffs()),
                           inline_shift=fam.shift, use_pallas=False)
    v2, i2 = ops.mach_topk(probs, cfg.table(), num_classes=512, k=6,
                           use_pallas=False)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-6)


@pytest.mark.parametrize("estimator", ESTIMATORS)
def test_predict_topk_matches_reference_rule(estimator):
    """predict_topk (kernel route) == estimate_class_probs + lax.top_k,
    and its top-1 equals predict_classes."""
    from repro.core.estimators import estimate_class_probs, predict_classes
    cfg = MACHConfig(200, 16, 5)
    tab = cfg.table()
    logits = jax.random.normal(jax.random.key(11), (6, 5, 16))
    meta = mach_meta_probs(logits)                   # (R, N, B)
    scores = estimate_class_probs(meta, tab, estimator)
    rv, ri = jax.lax.top_k(scores, 4)
    kv, ki = predict_topk(meta, tab, 4, estimator,
                          use_pallas=True, interpret=True)
    _assert_topk_matches(jnp.moveaxis(meta, 0, 1), tab, kv, ki, rv, ri,
                         estimator)
    np.testing.assert_array_equal(np.asarray(ki[:, 0]),
                                  np.asarray(predict_classes(meta, tab,
                                                             estimator)))
