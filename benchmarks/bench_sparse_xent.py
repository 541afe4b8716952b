"""Sparse-feature training-loss hot path: fused CSR projection+CE.

The claim under test is the paper's single-GPU ODP story: with CSR
inputs at fixed nnz, peak *training activation* memory is independent
of the feature dimension d — the fused kernel densifies per tile in
VMEM, so neither a dense (N, d) activation nor an (N, R·B) logits
tensor ever exists in HBM.  Sweeps (N, d, R, B, nnz) — including a
fixed-nnz d-progression — and records, per config:

  * ``us_fused`` / ``us_densified`` — value_and_grad wrt (W, bias) of
                  ``ops.mach_fused_xent_csr`` as dispatched on this
                  backend, vs the densifying reference (which scatters
                  the batch into a dense (N, d) activation first).  On
                  CPU the dispatcher itself falls back to that same
                  reference — ``fused_is_kernel`` records which ran.
  * ``peak_act_bytes_*`` — the largest batch-carrying intermediate
                  (leading dim in [N, N+block)) in each path's
                  fwd+bwd jaxpr.  Parameter/gradient-shaped tensors
                  (W, dW — the O(d log K) budget) and Pallas VMEM
                  tiles are excluded.  The structural claims: the
                  sparse path's peak is ELL-sized (O(N·nnz_max), d
                  never enters), equal across the fixed-nnz d sweep;
                  the densified path's peak is the (N, d) activation.
  * ``has_nrb_tensor_*`` / ``has_nd_tensor_*`` — whether any
                  batch-carrying intermediate of ≥ N·R·B (resp. ≥ N·d)
                  elements exists in the pass.
  * ``parity_rel_err`` / ``grad_allclose`` — interpret-mode kernel
                  vs densified reference (relative loss error and
                  dW/dbias at rtol 1e-4) on ragged-row CSR batches: the
                  PR's acceptance gate, checked on every sweep entry.

Writes ``BENCH_sparse.json`` (see ``--out``).

    PYTHONPATH=src python benchmarks/bench_sparse_xent.py [--smoke]
"""

from __future__ import annotations

BENCH_FILE = "BENCH_sparse.json"        # regression-gated by benchmarks/run.py

import argparse
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import intermediate_avals, make_csr_case, timeit
from repro.kernels import ops, ref
from repro.kernels.mach_fused_xent import GATHER_NNZ_THRESHOLD
from repro.launch.compile_cache import enable_compile_cache

# (N, d, R, B, nnz_max): the first three share (N, R, B, nnz) and sweep
# d only — the d-independence claim; the fourth is an ODP-like head
# (R=25, B=32) at a d no dense (N, d) scatter should be paid for; the
# last crosses GATHER_NNZ_THRESHOLD so the dispatcher routes it to the
# scalar-prefetch gather kernel (no (bn, jp, bd) one-hot densification
# — the regime the padded-ELL path could not block).  N is small there
# because the interpret-mode grid pays per example row.
SWEEP = [
    (64, 512, 8, 64, 16),
    (64, 2048, 8, 64, 16),
    (64, 8192, 8, 64, 16),
    (128, 4096, 25, 32, 32),
    (4, 1024, 8, 128, 512),     # high-nnz: gather path (nnz < R·B and
    #                             nnz < d, so the ELL operands stay
    #                             under the N·R·B / N·d thresholds)
]
SMOKE_SWEEP = SWEEP[:2] + SWEEP[-1:]
D_SWEEP_KEY = (64, 8, 64, 16)      # (N, R, B, nnz) of the d-progression


def _memory_model(fn, args, n: int, nrb: int, nd: int) -> dict:
    """Batch-carrying intermediates (leading dim in [N, N+128)) of the
    traced fwd+bwd jaxpr; kernel block sizes never exceed 128."""
    avals = intermediate_avals(jax.make_jaxpr(fn)(*args).jaxpr)
    acts = [a for a in avals
            if getattr(a, "ndim", 0) >= 1 and a.size
            and n <= a.shape[0] < n + 128]
    return {"peak_act_bytes": max(a.size * a.dtype.itemsize for a in acts),
            "has_nrb_tensor": any(a.size >= nrb for a in acts),
            "has_nd_tensor": any(a.size >= nd for a in acts)}


def bench(smoke: bool = False, report=None) -> dict:
    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    rows = []
    sweep = SMOKE_SWEEP if smoke else SWEEP
    for (n, d, r, b, nnz_max) in sweep:
        indptr, indices, values, w, bias, y, g = make_csr_case(
            n, d, r, b, nnz_max)
        nrb, nd = n * r * b, n * d

        def densified_vag(w_, bias_):
            return jax.value_and_grad(lambda ww, bb: jnp.sum(
                ref.mach_fused_xent_csr_ref(indptr, indices, values, ww,
                                            y, b, bias=bb) * g),
                argnums=(0, 1))(w_, bias_)

        def fused_vag(w_, bias_):
            # backend dispatch (kernel on TPU, densified ref elsewhere)
            return jax.value_and_grad(lambda ww, bb: jnp.sum(
                ops.mach_fused_xent_csr(indptr, indices, values, ww, y,
                                        num_buckets=b, nnz_max=nnz_max,
                                        bias=bb) * g),
                argnums=(0, 1))(w_, bias_)

        def kernel_vag(w_, bias_):
            # the kernel path regardless of backend (for the jaxpr scan)
            return jax.value_and_grad(lambda ww, bb: jnp.sum(
                ops.mach_fused_xent_csr(indptr, indices, values, ww, y,
                                        num_buckets=b, nnz_max=nnz_max,
                                        bias=bb, use_pallas=True,
                                        interpret=True) * g),
                argnums=(0, 1))(w_, bias_)

        us_dense = timeit(jax.jit(densified_vag), w, bias, iters=5)
        us_fused = timeit(jax.jit(fused_vag), w, bias, iters=5)
        mem_dense = _memory_model(densified_vag, (w, bias), n, nrb, nd)
        mem_fused = _memory_model(kernel_vag, (w, bias), n, nrb, nd)

        # parity gate: interpret-mode kernel vs densified reference
        # (lr/lk are g-weighted SUMS over the batch, so the loss gate is
        # relative — absolute error scales with N·R·log B)
        (lr, dr) = densified_vag(w, bias)
        (lk, dk) = kernel_vag(w, bias)
        loss_err = float(jnp.abs(lr - lk) / jnp.maximum(jnp.abs(lr), 1.0))
        grads_ok = all(
            np.allclose(np.asarray(a), np.asarray(k), rtol=1e-4, atol=1e-6)
            for a, k in zip(dr, dk))

        impl = "gather" if nnz_max >= GATHER_NNZ_THRESHOLD else "densify"
        row = {"N": n, "d": d, "R": r, "B": b, "RB": r * b,
               "nnz_max": nnz_max, "sparse_impl": impl,
               "us_densified": us_dense, "us_fused": us_fused,
               "fused_is_kernel": on_tpu,
               "peak_act_bytes_densified": mem_dense["peak_act_bytes"],
               "peak_act_bytes_fused": mem_fused["peak_act_bytes"],
               "has_nrb_tensor_densified": mem_dense["has_nrb_tensor"],
               "has_nrb_tensor_fused": mem_fused["has_nrb_tensor"],
               "has_nd_tensor_densified": mem_dense["has_nd_tensor"],
               "has_nd_tensor_fused": mem_fused["has_nd_tensor"],
               "act_ratio": mem_dense["peak_act_bytes"]
               / mem_fused["peak_act_bytes"],
               "parity_rel_err": loss_err,
               "grad_allclose": bool(grads_ok)}
        rows.append(row)
        if report:
            report(f"sparse_xent/N{n}_d{d}_R{r}_B{b}_nnz{nnz_max}",
                   us_fused,
                   f"densified={us_dense:.0f}us "
                   f"act_ratio={row['act_ratio']:.1f}x "
                   f"loss_err={loss_err:.1e} grads_ok={grads_ok} "
                   f"impl={impl} kernel={on_tpu}")

    verified = all(r["grad_allclose"] and r["parity_rel_err"] <= 1e-5
                   for r in rows)
    clean = all(not r["has_nrb_tensor_fused"]
                and not r["has_nd_tensor_fused"] for r in rows)
    d_peaks = {r["peak_act_bytes_fused"] for r in rows
               if (r["N"], r["R"], r["B"], r["nnz_max"]) == D_SWEEP_KEY}
    d_independent = len(d_peaks) == 1
    out = {"backend": backend, "fused_is_kernel": on_tpu,
           "verified_interpret": bool(verified),
           "fused_free_of_nrb_and_nd_tensors": bool(clean),
           "peak_act_independent_of_d": bool(d_independent),
           "configs": rows}
    if report:
        report("sparse_xent/verified", 0.0,
               f"interpret_match={verified} no_nrb_or_nd={clean} "
               f"d_independent={d_independent}")
    return out


def run(report) -> None:
    """benchmarks/run.py hook."""
    result = bench(smoke=True, report=report)
    with open("BENCH_sparse.json", "w") as f:
        json.dump(result, f, indent=2)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", "--quick", action="store_true",
                    help="small sweep (CI)")
    ap.add_argument("--out", default="BENCH_sparse.json")
    args = ap.parse_args()
    enable_compile_cache()
    result = bench(smoke=args.smoke,
                   report=lambda n, us, d="": print(f"{n},{us:.2f},{d}",
                                                    flush=True))
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print(f"wrote {args.out} ({len(result['configs'])} configs, "
          f"backend={result['backend']}, "
          f"verified={result['verified_interpret']}, "
          f"clean={result['fused_free_of_nrb_and_nd_tensors']}, "
          f"d_independent={result['peak_act_independent_of_d']})")
    return 0 if (result["verified_interpret"]
                 and result["fused_free_of_nrb_and_nd_tensors"]
                 and result["peak_act_independent_of_d"]) else 1


if __name__ == "__main__":
    sys.exit(main())
