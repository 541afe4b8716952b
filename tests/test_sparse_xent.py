"""Sparse (CSR) fused projection+CE: kernel vs densified oracle, the
MACHHead abstraction, and the structural memory claims.

Parity ladder (all interpret=True on CPU):
  sparse kernel  ==  ref.mach_fused_xent_csr_ref   (values + dW/dbias)
  ops.mach_fused_xent_csr / MACHLinear.fused_loss  ==  materializing
  MACHLinear(fused=True).loss on CSR  ==  MACHLinear().loss on dense
plus the structural claims the kernel exists for: no (N, R·B) logits
tensor AND no dense (N, d) activation in the jaxpr of either pass, and
the slice/merge per-repetition API surviving a fused training step.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MACHConfig, MACHHead, MACHLinear, MACHOutputHead
from repro.core.mach import is_sparse_batch
from repro.data import SparseBatch, SparseExtremeDataConfig, \
    SparseExtremeDataset
from repro.kernels import ops, ref
from repro.kernels.mach_fused_xent import (choose_sparse_blocks,
                                           mach_fused_xent_sparse_pallas)
from repro.optim import adamw, apply_updates


def _csr_case(n, d, r, b, nnz_max, seed=0, dtype=jnp.float32):
    """Shared ragged-CSR fixture (benchmarks/common.py) minus the bias —
    the benchmark's parity gate and these tests see the same inputs."""
    from benchmarks.common import make_csr_case
    indptr, indices, values, w, _, y, g = make_csr_case(
        n, d, r, b, nnz_max, seed=seed, dtype=dtype)
    return indptr, indices, values, w, y, g


# ---------------------------------------------------------------------------
# kernel vs densified reference oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,d,r,b,nnz", [
    (16, 96, 4, 16, 8),      # several whole heads per column block
    (13, 100, 6, 24, 5),     # ragged N and d (both padded)
    (5, 64, 25, 32, 7),      # paper ODP-ish R=25: padded head count
    (2, 48, 8, 512, 4),      # imagenet-ish B=512, tiny N
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sparse_xent_matches_ref(n, d, r, b, nnz, dtype):
    indptr, indices, values, w, y, g = _csr_case(n, d, r, b, nnz,
                                                 dtype=dtype)
    cols, vals = ops.csr_to_ell(indptr, indices, values, nnz, d)
    lr = ref.mach_fused_xent_csr_ref(indptr, indices, values, w, y, b)
    lk = mach_fused_xent_sparse_pallas(cols, vals, w, None, y, b,
                                       None, None, None, True)
    np.testing.assert_allclose(np.asarray(lr), np.asarray(lk),
                               rtol=1e-5, atol=1e-5)
    dr = jax.grad(lambda w_: jnp.sum(
        ref.mach_fused_xent_csr_ref(indptr, indices, values, w_, y, b)
        * g))(w)
    dk = jax.grad(lambda w_: jnp.sum(
        mach_fused_xent_sparse_pallas(cols, vals, w_, None, y, b,
                                      None, None, None, True) * g))(w)
    assert dr.dtype == dk.dtype
    # bf16 grads agree to 1 ulp (the final f32->bf16 cast may round a
    # near-midpoint value differently between the two paths)
    rtol, atol = ((1e-2, 1e-4) if dtype == jnp.bfloat16
                  else (1e-4, 1e-5))
    np.testing.assert_allclose(np.asarray(dr, np.float32),
                               np.asarray(dk, np.float32),
                               rtol=rtol, atol=atol)


def test_sparse_xent_d_blocked_and_head_split():
    """Feature dim larger than the d block AND B larger than the column
    block: the d-accumulation and the online logsumexp streaming paths
    run together."""
    n, d, r, b, nnz = 9, 200, 3, 256, 6
    indptr, indices, values, w, y, g = _csr_case(n, d, r, b, nnz)
    bn, bc, bd, rp, bp, jp = choose_sparse_blocks(n, d, r, b, nnz,
                                                  None, 64, 64)
    assert bc < b and bd < d                 # the paths under test
    cols, vals = ops.csr_to_ell(indptr, indices, values, nnz, d)
    lr = ref.mach_fused_xent_csr_ref(indptr, indices, values, w, y, b)
    lk = mach_fused_xent_sparse_pallas(cols, vals, w, None, y, b,
                                       None, 64, 64, True)
    np.testing.assert_allclose(np.asarray(lr), np.asarray(lk),
                               rtol=1e-5, atol=1e-5)
    dr = jax.grad(lambda w_: jnp.sum(
        ref.mach_fused_xent_csr_ref(indptr, indices, values, w_, y, b)
        * g))(w)
    dk = jax.grad(lambda w_: jnp.sum(
        mach_fused_xent_sparse_pallas(cols, vals, w_, None, y, b,
                                      None, 64, 64, True) * g))(w)
    np.testing.assert_allclose(np.asarray(dr), np.asarray(dk),
                               rtol=1e-4, atol=1e-6)


def test_csr_op_with_bias_matches_ref():
    """ops-level dispatch: bias as a native in-kernel operand; dW flows
    through the fused scatter-add, dbias through the (1, bc) scratch
    reduction."""
    from benchmarks.common import make_csr_case
    n, d, r, b, nnz = 11, 96, 5, 32, 8
    indptr, indices, values, w, bias, y, g = make_csr_case(n, d, r, b,
                                                           nnz)

    def fr(w_, b_):
        return jnp.sum(ref.mach_fused_xent_csr_ref(
            indptr, indices, values, w_, y, b, bias=b_) * g)

    def fk(w_, b_):
        return jnp.sum(ops.mach_fused_xent_csr(
            indptr, indices, values, w_, y, num_buckets=b, nnz_max=nnz,
            bias=b_, use_pallas=True, interpret=True) * g)

    np.testing.assert_allclose(float(fr(w, bias)), float(fk(w, bias)),
                               rtol=1e-5, atol=1e-5)
    dr = jax.grad(fr, argnums=(0, 1))(w, bias)
    dk = jax.grad(fk, argnums=(0, 1))(w, bias)
    for a, k in zip(dr, dk):
        np.testing.assert_allclose(np.asarray(a), np.asarray(k),
                                   rtol=1e-4, atol=1e-6)


def test_csr_bias_keeps_ell_width_nnz_max():
    """The bias used to ride an always-on unit feature, widening the
    ELL layout to nnz_max+1 (a full extra lane block whenever nnz_max
    was a multiple of 128).  With the in-kernel bias operand the ELL
    width is exactly nnz_max again: the traced fwd+bwd contains
    (N, nnz_max) intermediates and none of width nnz_max+1."""
    from benchmarks.common import intermediate_avals, make_csr_case

    n, d, r, b, nnz = 16, 96, 4, 32, 128    # nnz on a lane multiple
    indptr, indices, values, w, bias, y, g = make_csr_case(n, d, r, b,
                                                           nnz)

    def vag(w_, bias_):
        return jax.value_and_grad(lambda ww, bb: jnp.sum(
            ops.mach_fused_xent_csr(indptr, indices, values, ww, y,
                                    num_buckets=b, nnz_max=nnz, bias=bb,
                                    use_pallas=True, interpret=True)
            * g), argnums=(0, 1))(w_, bias_)

    widths = {a.shape[1] for a in
              intermediate_avals(jax.make_jaxpr(vag)(w, bias).jaxpr)
              if getattr(a, "ndim", 0) == 2 and a.shape[0] == n}
    assert nnz in widths, sorted(widths)
    assert nnz + 1 not in widths, sorted(widths)


def test_csr_to_ell_roundtrip():
    """ELL layout densifies to exactly the CSR densification (duplicate
    ids scatter-add; padding contributes nothing)."""
    n, d, nnz = 7, 40, 5
    indptr, indices, values, _, _, _ = _csr_case(n, d, 4, 8, nnz)
    cols, vals = ops.csr_to_ell(indptr, indices, values, nnz, d)
    assert cols.shape == (n, nnz) and vals.shape == (n, nnz)
    dense_csr = ref.csr_densify_ref(indptr, indices, values, d)
    rows = jnp.arange(n)[:, None] * jnp.ones((1, nnz), jnp.int32)
    dense_ell = jnp.zeros((n, d + 1)).at[
        rows.reshape(-1), cols.reshape(-1)].add(vals.reshape(-1))[:, :d]
    np.testing.assert_allclose(np.asarray(dense_csr),
                               np.asarray(dense_ell), rtol=0, atol=1e-7)


# ---------------------------------------------------------------------------
# the backward over feature-sorted entries: dW and dbias against the oracle
# ---------------------------------------------------------------------------

def _rows_csr(rows, d, seed):
    """CSR of explicit per-row column lists, with random values."""
    rng = np.random.default_rng(seed)
    indptr = np.concatenate([[0], np.cumsum([len(x) for x in rows])])
    indices = np.concatenate([np.asarray(x, np.int64) for x in rows])
    assert indices.max() < d
    values = rng.normal(size=indices.size) / np.sqrt(max(map(len, rows)))
    return (jnp.asarray(indptr, jnp.int32), jnp.asarray(indices, jnp.int32),
            jnp.asarray(values, jnp.float32))


def _sorted_bwd_case(case):
    """(n, d, r, b, per-row cols, block_c, block_d, with bias, c_sel)."""
    rng = np.random.default_rng(7)
    if case == "duplicates":            # an id twice in a row, and in rows
        rows = [[3, 3, 17, 40], [17, 3], [40, 40, 40], [5], [3, 17, 17]] * 2
        return 10, 48, 4, 16, rows, None, 16, True, None
    if case == "empty_blocks":          # only d blocks 0 and 5 hold entries
        rows = [list(rng.integers(0, 8, 3)) + list(rng.integers(40, 48, 2))
                for _ in range(8)]
        return 8, 64, 3, 16, rows, None, 8, False, None
    if case == "skewed":                # block 0 holds two entry chunks
        rows = [list(rng.integers(0, 8, 30)) + [60 + i % 4, 70]
                for i in range(40)]
        return 40, 96, 2, 16, rows, None, 16, True, None
    if case == "sentinel_ragged":       # padded ELL slots at the sentinel
        rows = [list(rng.integers(0, 72, k)) for k in (1, 6, 2, 9, 4, 3)]
        return 6, 72, 5, 8, rows, None, 16, False, None
    if case == "n_not_8":
        rows = [list(rng.integers(0, 40, 5)) for _ in range(13)]
        return 13, 40, 3, 8, rows, None, None, True, None
    if case == "column_blocks":         # R·B > bc: three column blocks
        rows = [list(rng.integers(0, 56, 6)) for _ in range(9)]
        return 9, 56, 3, 32, rows, 32, 16, True, None
    if case == "bucket_select":
        rows = [list(rng.integers(0, 48, 7)) for _ in range(9)]
        return 9, 48, 4, 32, rows, None, 16, True, 8
    raise ValueError(case)


@pytest.mark.parametrize("case", [
    "duplicates", "empty_blocks", "skewed", "sentinel_ragged", "n_not_8",
    "column_blocks", "bucket_select"])
def test_sorted_backward_matches_ref(case):
    """The sparse backward walks the batch's entries sorted by feature:
    duplicate ids sum, d blocks without entries get zero dW rows, a
    block may span several entry chunks, sentinel slots add nothing.
    dW and dbias match the densifying oracle in interpret mode."""
    n, d, r, b, rows, block_c, block_d, with_bias, c_sel = \
        _sorted_bwd_case(case)
    nnz = max(map(len, rows))
    indptr, indices, values = _rows_csr(rows, d, seed=len(case))
    rng = np.random.default_rng(n + d)
    w = jnp.asarray(rng.normal(size=(d, r * b)) / np.sqrt(nnz), jnp.float32)
    bias = (jnp.asarray(rng.normal(size=r * b) * 0.1, jnp.float32)
            if with_bias else None)
    y = jnp.asarray(rng.integers(0, b, (n, r)), jnp.int32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    if c_sel is None:
        def kernel(w_, b_):
            return ops.mach_fused_xent_csr(
                indptr, indices, values, w_, y, num_buckets=b, nnz_max=nnz,
                bias=b_, block_c=block_c, block_d=block_d, use_pallas=True,
                interpret=True)

        def oracle(w_, b_):
            return ref.mach_fused_xent_csr_ref(indptr, indices, values, w_,
                                               y, b, bias=b_)
    else:
        proxy = ops.mach_bucket_proxy(w=w, num_buckets=b, bias=bias,
                                      csr=(indptr, indices, values))
        sel = ops.mach_select_buckets(proxy, y, num_buckets=b, c_sel=c_sel)

        def kernel(w_, b_):
            return ops.mach_fused_xent_csr(
                indptr, indices, values, w_, y, num_buckets=b, nnz_max=nnz,
                bias=b_, block_d=block_d, bucket_select=(c_sel, 1),
                bucket_proxy=proxy, use_pallas=True, interpret=True)

        def oracle(w_, b_):
            return ref.mach_fused_xent_csr_selected_ref(
                indptr, indices, values, w_, y, sel, b, bias=b_)

    argnums = (0, 1) if with_bias else (0,)
    dk = jax.grad(lambda *a: jnp.sum(kernel(*a) * g), argnums)(w, bias)
    dr = jax.grad(lambda *a: jnp.sum(oracle(*a) * g), argnums)(w, bias)
    for a, k in zip(dr, dk):
        np.testing.assert_allclose(np.asarray(a), np.asarray(k),
                                   rtol=1e-4, atol=1e-6)
    if case == "empty_blocks":
        assert not np.any(np.asarray(dk[0])[8:40])


# ---------------------------------------------------------------------------
# the MACHHead abstraction: one surface for both heads
# ---------------------------------------------------------------------------

def test_mach_head_protocol_conformance():
    cfg = MACHConfig(500, 16, 4)
    lin = MACHLinear(cfg, 32)
    out = MACHOutputHead(cfg, 32)
    assert isinstance(lin, MACHHead) and isinstance(out, MACHHead)
    key = jax.random.key(0)
    h = jax.random.normal(jax.random.key(1), (6, 32))
    y = jax.random.randint(jax.random.key(2), (6,), 0, 500)
    for head in (lin, out):
        params = head.init(key)
        assert float(head.loss(params, h, y)) > 0
        assert float(head.fused_loss(params, h, y)) == pytest.approx(
            float(head.loss(params, h, y)), rel=1e-5)
        pred = head.predict(params, h)          # Algorithm-2 decode
        assert pred.shape == (6,) and head.param_count() > 0


def test_linear_fused_flag_routes_loss_dense():
    """MACHLinear(fused=True).loss == materializing loss, values and
    grads (bias included via the unit-feature augmentation)."""
    cfg = MACHConfig(300, 8, 5)
    m0, m1 = MACHLinear(cfg, 24), MACHLinear(cfg, 24, fused=True)
    params = m0.init(jax.random.key(0))
    params["b"] = jax.random.normal(jax.random.key(3), params["b"].shape) * 0.1
    x = jax.random.normal(jax.random.key(1), (10, 24))
    y = jax.random.randint(jax.random.key(2), (10,), 0, 300)
    l0, g0 = jax.value_and_grad(m0.loss)(params, x, y)
    l1, g1 = jax.value_and_grad(m1.loss)(params, x, y)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6, atol=1e-6)
    for k in ("w", "b"):
        np.testing.assert_allclose(np.asarray(g0[k]), np.asarray(g1[k]),
                                   rtol=1e-4, atol=1e-6)


def test_linear_fused_csr_matches_dense_path():
    """The full vertical slice: SparseBatch -> fused CSR loss ==
    materializing loss on the densified batch (interpret-mode kernel)."""
    ds = SparseExtremeDataset(SparseExtremeDataConfig(
        num_classes=128, num_features=64, nnz=8, sig_features=4))
    cfg = MACHConfig(128, 8, 4)
    m0, m1 = MACHLinear(cfg, 64), MACHLinear(cfg, 64, fused=True)
    params = m0.init(jax.random.key(0))
    sb, y = ds.batch_at(0, 12)
    xd, _ = ds.batch_at(0, 12, format="dense")
    assert is_sparse_batch(sb) and not is_sparse_batch(xd)
    l0, g0 = jax.value_and_grad(m0.loss)(params, xd, y)
    l1, g1 = jax.value_and_grad(
        lambda p: m1.fused_loss(p, sb, y, use_pallas=True,
                                interpret=True))(params)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-5, atol=1e-5)
    for k in ("w", "b"):
        np.testing.assert_allclose(np.asarray(g0[k]), np.asarray(g1[k]),
                                   rtol=1e-4, atol=1e-6)
    # the materializing path accepts the sparse batch too (densifies)
    np.testing.assert_allclose(float(m0.loss(params, sb, y)), float(l0),
                               rtol=1e-6, atol=1e-6)


def test_slice_merge_roundtrip_through_fused_step():
    """Paper §6.1 embarrassing parallelism survives fused training: one
    adamw step through the fused CSR loss, then slice_repetition /
    merge_repetitions round-trips the trained params exactly."""
    ds = SparseExtremeDataset(SparseExtremeDataConfig(
        num_classes=64, num_features=48, nnz=6, sig_features=3))
    cfg = MACHConfig(64, 8, 4)
    m = MACHLinear(cfg, 48, fused=True)
    params = m.init(jax.random.key(0))
    sb, y = ds.batch_at(0, 16)
    opt = adamw(0.05)
    state = opt.init(params)
    loss, g = jax.value_and_grad(m.loss)(params, sb, y)
    upd, state = opt.update(g, state, params)
    params = apply_updates(params, upd)
    assert np.isfinite(float(loss))
    merged = MACHLinear.merge_repetitions(
        [MACHLinear.slice_repetition(params, j)
         for j in range(cfg.num_repetitions)])
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(merged)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ragged_zipf_csr_end_to_end_training():
    """Real ragged rows (Zipf doc lengths, not handmade fixtures) flow
    through the fused CSR path end to end: the dataset emits rows of
    varying nnz, the fused interpret-mode loss/grads match the
    materializing dense path on the same batch, and a full adamw step
    goes through."""
    ds = SparseExtremeDataset(SparseExtremeDataConfig(
        num_classes=64, num_features=48, nnz=8, sig_features=3,
        length_zipf_a=1.0))
    cfg = MACHConfig(64, 8, 4)
    m0, m1 = MACHLinear(cfg, 48), MACHLinear(cfg, 48, fused=True)
    params = m0.init(jax.random.key(0))
    sb, y = ds.batch_at(0, 16)
    lens = np.diff(np.asarray(sb.indptr))
    assert lens.min() >= 3 and lens.max() <= 8   # sig_features..nnz
    assert len(set(lens.tolist())) > 1           # actually ragged
    assert sb.nnz_max == 8
    xd, yd = ds.batch_at(0, 16, format="dense")
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yd))
    l0, g0 = jax.value_and_grad(m0.loss)(params, xd, y)
    l1, g1 = jax.value_and_grad(
        lambda p: m1.fused_loss(p, sb, y, use_pallas=True,
                                interpret=True))(params)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-5, atol=1e-5)
    for k in ("w", "b"):
        np.testing.assert_allclose(np.asarray(g0[k]), np.asarray(g1[k]),
                                   rtol=1e-4, atol=1e-6)
    opt = adamw(0.05)
    state = opt.init(params)
    upd, state = opt.update(g1, state, params)
    params = apply_updates(params, upd)
    loss2 = m1.fused_loss(params, sb, y, use_pallas=True, interpret=True)
    assert np.isfinite(float(loss2))


# ---------------------------------------------------------------------------
# structural claims: no (N, R·B) logits, no dense (N, d) activation
# ---------------------------------------------------------------------------

def test_no_nrb_or_nd_tensor_in_sparse_jaxpr():
    from benchmarks.common import intermediate_avals

    n, d, r, b, nnz = 32, 1024, 8, 64, 8
    indptr, indices, values, w, y, g = _csr_case(n, d, r, b, nnz)

    def fused_vag(w_):
        return jax.value_and_grad(lambda ww: jnp.sum(
            ops.mach_fused_xent_csr(indptr, indices, values, ww, y,
                                    num_buckets=b, nnz_max=nnz,
                                    use_pallas=True, interpret=True)
            * g))(w_)

    def densified_vag(w_):
        return jax.value_and_grad(lambda ww: jnp.sum(
            ref.mach_fused_xent_csr_ref(indptr, indices, values, ww, y,
                                        b) * g))(w_)

    nrb, nd = n * r * b, n * d

    def batch_sizes(fn):
        return [a.size for a in intermediate_avals(
            jax.make_jaxpr(fn)(w).jaxpr)
            if getattr(a, "ndim", 0) >= 1 and a.size
            and n <= a.shape[0] < n + 128]

    fused_sizes = batch_sizes(fused_vag)
    dens_sizes = batch_sizes(densified_vag)
    # the densifying path forms the (N, d) activation (and d > R·B here)
    assert any(s >= nd for s in dens_sizes)
    # the fused path forms neither the logits nor the dense activation
    assert all(s < min(nrb, nd) for s in fused_sizes), \
        sorted(fused_sizes, reverse=True)[:5]


def test_csr_to_ell_rejects_undersized_nnz_max():
    """Rows longer than nnz_max would be silently truncated on the
    kernel path (the densifying reference uses every entry) — concrete
    batches must be rejected instead."""
    indptr = jnp.asarray([0, 3, 4], jnp.int32)   # row 0 has 3 entries
    indices = jnp.asarray([0, 1, 2, 3], jnp.int32)
    values = jnp.ones((4,))
    with pytest.raises(ValueError, match="nnz_max"):
        ops.csr_to_ell(indptr, indices, values, 2, 8)
    w = jnp.ones((8, 4 * 2)) * 0.1
    y = jnp.zeros((2, 2), jnp.int32)
    with pytest.raises(ValueError, match="nnz_max"):
        ops.mach_fused_xent_csr(indptr, indices, values, w, y,
                                num_buckets=4, nnz_max=2,
                                use_pallas=True, interpret=True)
