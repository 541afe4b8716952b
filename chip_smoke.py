#!/usr/bin/env python3
"""Run the system's main paths once on one TPU chip, at full width.

    python chip_smoke.py

Three phases, all in this one process (a chip belongs to one process at
a time), each through the entry points a user calls:

  odp-train   the paper's ODP head (K=105,033, d=422,713, B=32, R=25,
              120 nonzeros per document) trained for a few adamw steps
              with ``MACHLinear(fused=True)`` on CSR batches of 512 —
              the sparse fused projection + cross-entropy kernels,
              forward and backward.  Step 0's per-example loss is
              checked against ``ref.mach_fused_xent_csr_ref``; the loss
              must be finite and fall.
  odp-decode  top-10 classes of a few hundred test documents through
              ``estimators.predict_topk`` on the trained head — the
              streaming top-k kernel — checked against the jnp
              reference, ids exact up to ties.
  serve       ``repro.launch.serve --local --full --arch
              recurrentgemma-2b`` (256k vocabulary behind a B=2048, R=8
              MACH head): a few requests through ``ServingEngine``,
              every one of which must finish.

Weights and data are random, made from seeds.  Each phase also checks
that its jitted program holds a Pallas TPU kernel (``tpu_custom_call``),
so a silent fall back to jnp fails the run.

The script exits non-zero, printing no result, when JAX finds no TPU,
when it is run outside a checkout of this repository, or when a phase
fails.  Otherwise the last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
TRAIN_STEPS, TRAIN_BATCH, TRAIN_LR = 8, 512, 0.05
DECODE_ROWS, DECODE_K = 256, 10
SERVE_ARGS = ["--arch", "recurrentgemma-2b", "--local", "--full",
              "--requests", "3", "--slots", "4", "--max-new", "4"]


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu() -> dict:
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke: {what}")


def holds_kernel(jitted, *args) -> bool:
    return "tpu_custom_call" in jitted.lower(*args).as_text()


def odp_train(small: bool = False):
    """-> (model, trained params, dataset)."""
    import jax
    import jax.numpy as jnp
    from repro.configs.odp_mach import ODP
    from repro.core import MACHLinear
    from repro.data import SparseExtremeDataset
    from repro.kernels import ops, ref
    from repro.optim import adamw
    from repro.train.trainer import make_head_step

    data = ODP.sparse_data(small=small)
    ds = SparseExtremeDataset(data)
    model = MACHLinear(ODP.mach(small=small), data.num_features, fused=True)
    cfg = model.cfg
    log(f"odp-train: K={cfg.num_classes} d={data.num_features} "
        f"B={cfg.num_buckets} R={cfg.num_repetitions} nnz={data.nnz} "
        f"N={TRAIN_BATCH}")
    params = model.init(jax.random.key(0))
    opt = adamw(TRAIN_LR)
    state = opt.init(params)
    step = make_head_step(model.loss, opt)

    def per_example(fn, params, x, y):
        hashed = jnp.moveaxis(cfg.hash_labels(y), 0, -1)
        return fn(x.indptr, x.indices, x.values,
                  params["w"].reshape(model.dim, -1), hashed,
                  bias=params["b"].reshape(-1))

    kernel_loss = jax.jit(lambda p, x, y: per_example(
        lambda *a, bias: ops.mach_fused_xent_csr(
            *a, num_buckets=cfg.num_buckets, nnz_max=x.nnz_max, bias=bias),
        p, x, y))
    ref_loss = jax.jit(lambda p, x, y: per_example(
        lambda *a, bias: ref.mach_fused_xent_csr_ref(
            *a, cfg.num_buckets, bias=bias), p, x, y))

    x0, y0 = ds.batch_at(0, TRAIN_BATCH)
    check(holds_kernel(step, params, state, x0, y0),
          "the ODP train step holds no Pallas kernel")
    got = np.asarray(kernel_loss(params, x0, y0))
    with jax.default_matmul_precision("float32"):
        want = np.asarray(ref_loss(params, x0, y0))
    err = float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1.0)))
    log(f"odp-train: step-0 per-example loss vs oracle: max rel err "
        f"{err:.3e} (mean {got.mean():.6f} vs {want.mean():.6f})")
    check(err <= 1e-4, f"step-0 loss is off the oracle by {err:.3e}")

    losses = []
    for s in range(TRAIN_STEPS):
        x, y = ds.batch_at(s, TRAIN_BATCH)
        jax.block_until_ready((x, y))
        t0 = time.perf_counter()
        params, state, loss = step(params, state, x, y)
        loss = float(jax.block_until_ready(loss))
        dt = time.perf_counter() - t0
        losses.append(loss)
        log(f"odp-train step {s}: loss {loss:.6f}  {dt * 1e3:.1f} ms"
            f"{'  (includes compile)' if s == 0 else ''}")
    check(all(math.isfinite(v) for v in losses), f"loss not finite: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    return model, params, ds


def odp_decode(model, params, ds) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core.estimators import predict_topk
    from repro.kernels import ref

    table = model.cfg.table()
    x, _ = ds.batch_at(0, DECODE_ROWS, "test")
    meta = jax.jit(model.meta_probs)(params, x)               # (R, N, B)
    topk = jax.jit(lambda m: predict_topk(m, table, DECODE_K))
    check(holds_kernel(topk, meta), "predict_topk holds no Pallas kernel")
    t0 = time.perf_counter()
    val, idx = jax.block_until_ready(topk(meta))
    log(f"odp-decode: top-{DECODE_K} of {DECODE_ROWS} rows over "
        f"K={model.cfg.num_classes} in {(time.perf_counter() - t0) * 1e3:.1f}"
        f" ms (includes compile)")
    probs = jnp.moveaxis(meta, 0, 1)                         # (N, R, B)
    rv, ri = (np.asarray(a) for a in ref.mach_topk_ref(probs, table,
                                                       DECODE_K))
    val, idx = np.asarray(val), np.asarray(idx)
    np.testing.assert_allclose(val, rv, rtol=1e-5, atol=1e-6)
    for row in idx:
        check(len(set(row.tolist())) == DECODE_K, "duplicate class ids")
    moved = idx != ri
    if moved.any():
        # ids may differ only where scores tie: the reference score at
        # the kernel's id must equal the reference value at that rank
        scores = np.asarray(ref.mach_estimator_scores_ref(probs, table))
        picked = scores[np.arange(len(idx))[:, None], idx]
        np.testing.assert_allclose(picked[moved], rv[moved], rtol=1e-5,
                                   atol=1e-6)
    log(f"odp-decode: matches the reference ({int(moved.sum())} ids "
        f"differ, all at tied scores)")


def serve(argv=SERVE_ARGS) -> None:
    from repro.launch import serve as serve_cli

    args = serve_cli.parse_args(argv)
    log(f"serve: {' '.join(argv)}")
    outs, metrics, dt = serve_cli.run(args)
    for r in outs:
        log(f"serve: request {r.request_id} ({r.finish_reason}, "
            f"{r.latency_steps} ticks): {list(r.tokens)}")
    log(f"serve: {len(outs)} requests, {metrics.tokens_generated} tokens "
        f"in {dt:.2f} s (includes compile), {metrics.decode_steps} "
        f"decode steps")
    check(len(outs) == args.requests, f"{len(outs)} of {args.requests} "
          f"requests came back")
    for r in outs:
        check(r.finish_reason in ("length", "eos") and len(r.tokens) > 0,
              f"request {r.request_id} did not finish: {r.finish_reason}")


def main() -> int:
    device = require_tpu()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    log(f"chip_smoke: {device}, compile cache {enable_compile_cache()}")
    t0 = time.perf_counter()
    model, params, ds = odp_train()
    odp_decode(model, params, ds)
    del model, params, ds                   # free the chip for serving
    t1 = time.perf_counter()
    log(f"chip_smoke: odp-train and odp-decode passed in {t1 - t0:.1f} s")
    serve()
    log(f"chip_smoke: serve passed in {time.perf_counter() - t1:.1f} s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
