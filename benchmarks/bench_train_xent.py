"""Training-loss hot path: fused projection+CE vs materialized logits.

Sweeps (N, d, R, B) — N = B·T flattened tokens — and records, per config:

  * ``us_materialized`` — value_and_grad of the materializing path
                  (``head matmul → (N, R·B) logits → mach_xent``), i.e.
                  what ``model.loss`` runs with ``mach_fused_loss=False``.
  * ``us_fused``  — value_and_grad of ``ops.mach_fused_xent`` as
                  dispatched on this backend.  On TPU that is the fused
                  Pallas kernel; on CPU the dispatcher falls back to the
                  same materializing reference math, so the two columns
                  coincide — ``fused_is_kernel`` records which one ran.
  * ``peak_act_bytes_*`` — the largest *activation* in the jaxpr of
                  each path's forward+backward: intermediates carrying
                  the batch dimension (leading dim in [N, N+block)).
                  Parameter-shaped intermediates (the padded W, dW) are
                  parameter/gradient memory — the paper's O(d log K)
                  budget — and Pallas kernel internals are VMEM tiles;
                  both are excluded.  The structural claim: the
                  materialized path peaks at the N·R·B·4-byte logits
                  tensor, the fused path's peak is h/dh-sized —
                  independent of R·B.
  * ``has_nrb_tensor_*`` — whether any batch-carrying intermediate of
                  ≥ N·R·B elements exists in the pass.
  * ``parity_max_abs_err`` / ``grad_allclose`` — interpret-mode kernel
                  vs reference on this config (loss |Δ| and dh/dW/dbias
                  at rtol 1e-4): the PR's acceptance gate, checked on
                  every sweep entry (``--quick`` skips the largest).

The **d-sweep gate** (ISSUE 4): for d ∈ {1k, 4k, 12k} at the
mistral-large-scale head (R=32, B=512), ``choose_fused_blocks`` must
yield a tiling whose accounted VMEM tile bytes (``dense_tile_bytes``)
fit the default 6 MB budget — the old lane-floor clamp silently blew
it ~2x at d=12288 — and interpret-mode parity (values + dh/dW/dbias)
must hold through the d-blocked kernels.  ``--quick`` runs the budget
accounting at every d but parity only at d=1k (interpret-mode grids at
d=12k are minutes-slow on CPU); the full run checks parity at all
three.

The **500k-label gate** (ISSUE 8): the commodity-GPU workload of
arXiv 2306.03725 — K = 500k Zipf classes over sparse bag-of-words
features (``SparseExtremeDataset``) — trained through the fused CSR
loss with and without dynamic bucket selection at ≥ 5× C-axis
reduction.  Quick mode gates the *per-step* wall-clock ratio
(selected/full < 1) and parity within the documented one-sided bias
bound (``ref.mach_selected_bias_bound_ref``); the full run also races
both paths to the full loss's bucket-accuracy target and gates
**wall-clock-to-target** (selected strictly faster).

Writes ``BENCH_xent.json`` (see ``--out``) so the train-loss perf and
memory trajectory is tracked from this PR forward.

    PYTHONPATH=src python benchmarks/bench_train_xent.py [--quick]
"""

from __future__ import annotations

BENCH_FILE = "BENCH_xent.json"        # regression-gated by benchmarks/run.py

import argparse
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import intermediate_avals, make_dense_case, timeit
from repro.kernels import ops, ref
from repro.kernels.mach_fused_xent import (DEFAULT_VMEM_BUDGET,
                                           choose_fused_blocks,
                                           dense_tile_bytes,
                                           mach_fused_xent_pallas)
from repro.launch.compile_cache import enable_compile_cache

# (N, d, R, B): acceptance config, paper's ODP (R=25, B=32) and
# ImageNet-21k (R=20, B=512) heads, and a 32k-column ODP-scale head
# that only the fused path can train without an (N, 32k) activation.
# N != d everywhere so batch-carrying and param-shaped intermediates
# are distinguishable by leading dim in the jaxpr scan.
SWEEP = [
    (256, 128, 16, 512),       # the PR's acceptance case (R·B = 8192)
    (512, 256, 25, 32),        # ODP-like head
    (320, 256, 20, 512),       # imagenet-21k-like head
    (192, 128, 16, 2048),      # R·B = 32768: ODP-scale column count
]
QUICK_SWEEP = SWEEP[:2]

# d-sweep (ISSUE 4): LM-trunk widths at the (R=32, B=512) head.  The
# chooser is asked at N=256 (the confirmed-blowout shape); parity runs
# at N=16 — the (C/bc)·(D/bd) grid axes, which the gate exercises, are
# N-independent, and interpret mode pays per grid step.
D_SWEEP = [1024, 4096, 12288]
D_SWEEP_RB = (32, 512)

# 500k-label workload (ISSUE 8): K Zipf classes hashed to R heads of B
# buckets (C = R·B fused columns); c_sel = B/8 → 8× C-axis cut (the
# gate requires ≥ 5×).  d/nnz are the sparse bag-of-words regime the
# gather kernel exists for.  N matters: the selected path pays a
# per-step O(d·R·c_sel) W-column gather/scatter that the batch must
# amortize — N = 512 is the realistic large-batch regime (and the
# smallest power of two where the 8× matmul saving clearly dominates
# the column traffic on CPU).
EXTREME_500K = {"num_labels": 500_000, "num_buckets": 4096, "R": 8,
                "d": 1024, "nnz": 64, "N": 512, "c_sel": 512,
                "refresh_every": 10}


def _memory_model(fn, args, n: int, nrb: int) -> dict:
    """Activation accounting over the traced jaxpr: intermediates whose
    leading dim is the (possibly block-padded) batch dim N.  Kernel
    block sizes never exceed 128, so padding adds < 128 rows."""
    avals = intermediate_avals(jax.make_jaxpr(fn)(*args).jaxpr)
    acts = [a for a in avals
            if getattr(a, "ndim", 0) >= 1 and a.size
            and n <= a.shape[0] < n + 128]
    return {"peak_act_bytes": max(a.size * a.dtype.itemsize for a in acts),
            "has_nrb_tensor": any(a.size >= nrb for a in acts)}


def _verify(h, w, bias, y, g, b, block_c=None, block_d=None
            ) -> tuple[float, bool]:
    """Interpret-mode kernel vs reference with the in-kernel bias:
    (max |Δloss|, dh/dW/dbias grads ok)."""
    lr = ref.mach_fused_xent_ref(h, w, y, b, bias=bias)
    lk = mach_fused_xent_pallas(h, w, bias, y, b, None, block_c, block_d,
                                True)
    loss_err = float(jnp.max(jnp.abs(lr - lk)))
    dr = jax.grad(lambda h_, w_, b_: jnp.sum(
        ref.mach_fused_xent_ref(h_, w_, y, b, bias=b_) * g),
        argnums=(0, 1, 2))(h, w, bias)
    dk = jax.grad(lambda h_, w_, b_: jnp.sum(
        mach_fused_xent_pallas(h_, w_, b_, y, b, None, block_c, block_d,
                               True) * g),
        argnums=(0, 1, 2))(h, w, bias)
    grads_ok = all(
        np.allclose(np.asarray(a), np.asarray(k), rtol=1e-4, atol=1e-6)
        for a, k in zip(dr, dk))
    return loss_err, grads_ok


def _d_sweep_gate(quick: bool, report=None) -> dict:
    """ISSUE 4's acceptance gate: budget accounting at every d, parity
    through the d-blocked kernels (at N=16; the d-blocked grid axes are
    N-independent)."""
    r, b = D_SWEEP_RB
    rows = []
    for d in D_SWEEP:
        bn, bc, bd, rp, bp = choose_fused_blocks(256, d, r, b)
        acct = dense_tile_bytes(bn, bc, bd, rp)
        row = {"d": d, "R": r, "B": b, "bn": bn, "bc": bc, "bd": bd,
               "rp": rp, "tile_bytes": acct,
               "within_budget": bool(acct <= DEFAULT_VMEM_BUDGET)}
        if not quick or d == D_SWEEP[0]:
            # parity at the N=256 choice's (bc, bd) — the exact tiling
            # the budget row is about (bn tracks the smaller N)
            h, w, bias, y, g = make_dense_case(16, d, r, b)
            loss_err, grads_ok = _verify(h, w, bias, y, g, b,
                                         block_c=bc, block_d=bd)
            row["parity_max_abs_err"] = loss_err
            row["grad_allclose"] = bool(grads_ok)
        rows.append(row)
        if report:
            report(f"train_xent/d_sweep_d{d}", 0.0,
                   f"blocks=({bn},{bc},{bd}) tile_kb={acct // 1024} "
                   f"within_budget={row['within_budget']} "
                   f"parity={row.get('parity_max_abs_err', 'skipped')} "
                   f"grads_ok={row.get('grad_allclose', 'skipped')}")
    ok = all(r_["within_budget"] for r_ in rows) and all(
        r_.get("grad_allclose", True) and
        r_.get("parity_max_abs_err", 0.0) <= 1e-4
        for r_ in rows)
    return {"rows": rows, "ok": bool(ok)}


def _bench_500k(quick: bool, report=None) -> dict:
    """ISSUE 8's acceptance gate: dynamic bucket selection on the
    500k-label sparse workload.

    Quick (CI): per-step value_and_grad wall-clock, selected (cached
    proxy — the trainer's steady state) vs full, must come in < 1× at
    the ≥ 5× C-axis reduction, and the per-example gap ``full − sel``
    must be one-sided and within ``mach_selected_bias_bound_ref``.
    Full run adds the wall-clock-to-target-accuracy race: both paths
    train (adamw) from the same init until the full path's final
    bucket accuracy; selected must get there in strictly less
    accumulated train-step time."""
    import time as _time

    from repro.core.mach import MACHConfig, MACHLinear
    from repro.data.extreme import (SparseExtremeDataConfig,
                                    SparseExtremeDataset)

    p = EXTREME_500K
    k, b, r = p["num_labels"], p["num_buckets"], p["R"]
    d, nnz, n, c_sel = p["d"], p["nnz"], p["N"], p["c_sel"]
    reduction = b // c_sel
    mcfg = MACHConfig(k, b, r)
    ds = SparseExtremeDataset(SparseExtremeDataConfig(
        num_classes=k, num_features=d, nnz=nnz, sig_features=16))
    head = MACHLinear(mcfg, d, fused=True)
    params = head.init(jax.random.key(0))
    sb, y = ds.batch_at(0, n)

    # cached proxy scores — what Trainer injects between refreshes
    proxy = jax.block_until_ready(head.bucket_proxy_scores(params, sb))

    def full_vag(params_):
        return jax.value_and_grad(
            lambda pp: head.fused_loss(pp, sb, y))(params_)

    def sel_vag(params_):
        return jax.value_and_grad(lambda pp: head.fused_loss(
            pp, sb, y, bucket_select=(c_sel, p["refresh_every"]),
            bucket_proxy=proxy))(params_)

    us_full = timeit(jax.jit(full_vag), params, iters=3)
    us_sel = timeit(jax.jit(sel_vag), params, iters=3)
    step_ratio = us_sel / us_full

    # parity within the documented one-sided bias bound (per example)
    hashed = jnp.moveaxis(mcfg.hash_labels(y), 0, -1).astype(jnp.int32)
    w2 = params["w"].reshape(d, -1)
    bias = params["b"].reshape(-1)
    selected = ops.mach_select_buckets(proxy, hashed, num_buckets=b,
                                       c_sel=c_sel)
    full_nll = ops.mach_fused_xent_csr(
        sb.indptr, sb.indices, sb.values, w2, hashed, num_buckets=b,
        nnz_max=sb.nnz_max, bias=bias)
    sel_nll = ops.mach_fused_xent_csr_selected(
        sb.indptr, sb.indices, sb.values, w2, hashed, selected,
        num_buckets=b, nnz_max=sb.nnz_max, bias=bias)
    bound = ref.mach_selected_bias_bound_ref(
        sb.to_dense(), w2, hashed, selected, b, bias=bias)
    gap = np.asarray(full_nll - sel_nll)
    tol = 1e-3 * float(np.max(np.asarray(full_nll)))     # f32 at ~R·log B
    one_sided = bool(np.all(gap >= -tol))
    within_bound = bool(np.all(gap <= np.asarray(bound) + tol))

    out = {"num_labels": k, "num_buckets": b, "R": r, "C": r * b,
           "d": d, "nnz": nnz, "N": n, "c_sel": c_sel,
           "c_axis_reduction": reduction,
           "us_full_step": us_full, "us_selected_step": us_sel,
           "step_ratio": step_ratio,
           "gap_one_sided": one_sided, "gap_within_bound": within_bound,
           "max_gap": float(np.max(gap)),
           "max_bound": float(np.max(np.asarray(bound)))}
    ok = step_ratio < 1.0 and reduction >= 5 and one_sided and within_bound
    if report:
        report("train_xent/extreme500k_step", us_sel,
               f"full={us_full:.0f}us ratio={step_ratio:.2f} "
               f"reduction={reduction}x one_sided={one_sided} "
               f"within_bound={within_bound}")

    if not quick:
        # wall-clock-to-target race, same init, fresh batch per step
        from repro.optim import (apply_updates, make_optimizer,
                                 make_schedule)
        opt = make_optimizer("adamw", make_schedule("constant", value=3e-2),
                             weight_decay=0.0)
        test_sb, test_y = ds.batch_at(10_000, 128, "test")
        test_x = test_sb.to_dense()
        test_hash = jnp.moveaxis(mcfg.hash_labels(test_y), 0, -1)

        @jax.jit
        def bucket_acc(params_):
            logits = jnp.einsum("nd,drb->nrb", test_x, params_["w"]) \
                + params_["b"]
            return jnp.mean((jnp.argmax(logits, -1) == test_hash)
                            .astype(jnp.float32))

        def race(select: bool, steps: int = 30, eval_every: int = 5):
            prms = head.init(jax.random.key(0))
            ost = opt.init(prms)
            prx = None

            @jax.jit
            def step(prms_, ost_, sb_, y_, prx_):
                def lf(pp):
                    if select:
                        return head.fused_loss(
                            pp, sb_, y_,
                            bucket_select=(c_sel, p["refresh_every"]),
                            bucket_proxy=prx_)
                    return head.fused_loss(pp, sb_, y_)
                loss, g = jax.value_and_grad(lf)(prms_)
                upd, ost_ = opt.update(g, ost_, prms_)
                return apply_updates(prms_, upd), ost_, loss

            trace, spent = [], 0.0
            for s in range(steps):
                sb_, y_ = ds.batch_at(1 + s, n)
                if select and s % p["refresh_every"] == 0:
                    prx = jax.block_until_ready(
                        head.bucket_proxy_scores(prms, sb_))
                t0 = _time.perf_counter()
                prms, ost, _ = step(prms, ost, sb_, y_, prx)
                jax.block_until_ready(prms)
                if s:                       # skip the compile step
                    spent += _time.perf_counter() - t0
                if (s + 1) % eval_every == 0:
                    trace.append((spent, float(bucket_acc(prms))))
            return trace

        full_trace = race(False)
        sel_trace = race(True)
        target = full_trace[-1][1]
        t_full = next(t for t, a in full_trace if a >= target)
        t_sel = next((t for t, a in sel_trace if a >= target), None)
        race_ok = t_sel is not None and t_sel < t_full
        out["wallclock"] = {
            "target_bucket_acc": target,
            "s_full_to_target": t_full,
            "s_selected_to_target": t_sel,
            "selected_final_acc": sel_trace[-1][1],
            "ok": bool(race_ok)}
        ok = ok and race_ok
        if report:
            report("train_xent/extreme500k_wallclock", 0.0,
                   f"target_acc={target:.3f} full={t_full:.1f}s "
                   f"selected={t_sel if t_sel is None else round(t_sel, 1)}s "
                   f"ok={race_ok}")

    out["ok"] = bool(ok)
    return out


def bench(quick: bool = False, report=None) -> dict:
    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    rows = []
    sweep = QUICK_SWEEP if quick else SWEEP
    for (n, d, r, b) in sweep:
        h, w, bias, y, g = make_dense_case(n, d, r, b)
        nrb = n * r * b

        def mat_vag(h_, w_, bias_):
            return jax.value_and_grad(lambda hh, ww, bb: jnp.sum(
                ref.mach_fused_xent_ref(hh, ww, y, b, bias=bb) * g),
                argnums=(0, 1, 2))(h_, w_, bias_)

        def fused_vag(h_, w_, bias_):
            # backend dispatch (kernel on TPU, reference elsewhere)
            return jax.value_and_grad(lambda hh, ww, bb: jnp.sum(
                ops.mach_fused_xent(hh, ww, y, num_buckets=b, bias=bb)
                * g),
                argnums=(0, 1, 2))(h_, w_, bias_)

        def kernel_vag(h_, w_, bias_):
            # the kernel path regardless of backend (for the jaxpr scan)
            return jax.value_and_grad(lambda hh, ww, bb: jnp.sum(
                mach_fused_xent_pallas(hh, ww, bb, y, b, None, None,
                                       None, True) * g),
                argnums=(0, 1, 2))(h_, w_, bias_)

        us_mat = timeit(jax.jit(mat_vag), h, w, bias, iters=5)
        us_fused = timeit(jax.jit(fused_vag), h, w, bias, iters=5)
        mem_mat = _memory_model(mat_vag, (h, w, bias), n, nrb)
        mem_fused = _memory_model(kernel_vag, (h, w, bias), n, nrb)
        loss_err, grads_ok = _verify(h, w, bias, y, g, b)

        row = {"N": n, "d": d, "R": r, "B": b, "RB": r * b,
               "us_materialized": us_mat, "us_fused": us_fused,
               "fused_is_kernel": on_tpu,
               "peak_act_bytes_materialized": mem_mat["peak_act_bytes"],
               "peak_act_bytes_fused": mem_fused["peak_act_bytes"],
               "has_nrb_tensor_materialized": mem_mat["has_nrb_tensor"],
               "has_nrb_tensor_fused": mem_fused["has_nrb_tensor"],
               "act_ratio": mem_mat["peak_act_bytes"]
               / mem_fused["peak_act_bytes"],
               "parity_max_abs_err": loss_err,
               "grad_allclose": bool(grads_ok)}
        rows.append(row)
        if report:
            report(f"train_xent/N{n}_d{d}_R{r}_B{b}", us_fused,
                   f"mat={us_mat:.0f}us act_ratio={row['act_ratio']:.1f}x "
                   f"loss_err={loss_err:.1e} grads_ok={grads_ok} "
                   f"kernel={on_tpu}")

    d_sweep = _d_sweep_gate(quick, report)
    extreme = _bench_500k(quick, report)
    verified = all(r["grad_allclose"] and r["parity_max_abs_err"] <= 1e-5
                   for r in rows)
    no_nrb = all(not r["has_nrb_tensor_fused"] for r in rows)
    out = {"backend": backend, "fused_is_kernel": on_tpu,
           "verified_interpret": bool(verified),
           "fused_free_of_nrb_tensor": bool(no_nrb),
           "d_sweep_ok": d_sweep["ok"],
           "d_sweep": d_sweep["rows"],
           "extreme_500k_ok": extreme["ok"],
           "extreme_500k": extreme,
           "configs": rows}
    if report:
        report("train_xent/verified", 0.0,
               f"interpret_match={verified} no_nrb_tensor={no_nrb} "
               f"d_sweep_ok={d_sweep['ok']} "
               f"extreme_500k_ok={extreme['ok']}")
    return out


def run(report) -> None:
    """benchmarks/run.py hook."""
    result = bench(quick=True, report=report)
    with open("BENCH_xent.json", "w") as f:
        json.dump(result, f, indent=2)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="small sweep (CI)")
    ap.add_argument("--out", default="BENCH_xent.json")
    args = ap.parse_args()
    enable_compile_cache()
    result = bench(quick=args.quick,
                   report=lambda n, us, d="": print(f"{n},{us:.2f},{d}",
                                                    flush=True))
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print(f"wrote {args.out} ({len(result['configs'])} configs, "
          f"backend={result['backend']}, "
          f"verified={result['verified_interpret']}, "
          f"no_nrb_tensor={result['fused_free_of_nrb_tensor']}, "
          f"d_sweep_ok={result['d_sweep_ok']}, "
          f"extreme_500k_ok={result['extreme_500k_ok']})")
    return 0 if (result["verified_interpret"]
                 and result["fused_free_of_nrb_tensor"]
                 and result["d_sweep_ok"]
                 and result["extreme_500k_ok"]) else 1


if __name__ == "__main__":
    sys.exit(main())
