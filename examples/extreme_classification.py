"""Paper reproduction driver: the ODP / ImageNet-21k experiments.

Runs the reduced-scale stand-ins of the paper's two benchmarks (the
datasets themselves are not offline-redistributable; the synthetic task
has a *known Bayes optimum*, which the paper's datasets lack) and prints
the paper-style report: accuracy at each (B, R), model-size reduction,
all three estimators, plus the full-scale arithmetic of Table 2.

For sparse-feature tasks (ODP — bag-of-words, d=422k at full scale) the
driver additionally trains the SAME MACHLinear model twice on identical
Zipf-sparse data: once through the materializing dense path and once
through the fused CSR path (``MACHLinear(fused=True)`` on CSR batches,
no (n, R·B) logits and no dense (n, d) activation on TPU), reporting
both accuracies — the two must agree to within a couple of points at
equal steps, since the fused path computes identical gradients.

    PYTHONPATH=src python examples/extreme_classification.py
    PYTHONPATH=src python examples/extreme_classification.py --task odp --small
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.odp_mach import IMAGENET, ODP
from repro.core import MACHLinear
from repro.data import (ExtremeDataConfig, ExtremeDataset,
                        SparseExtremeDataset)
from repro.optim import adamw
from repro.train.trainer import make_head_step


def train(ds, model, params, steps=150, bs=512, lr=0.05, format=None):
    opt = adamw(lr)
    state = opt.init(params)
    step = make_head_step(model.loss, opt)
    params = jax.tree.map(jnp.copy, params)     # the step donates them
    for s in range(steps):
        if format is None:
            x, y = ds.batch_at(s, bs)
        else:
            x, y = ds.batch_at(s, bs, format=format)
        params, state, _ = step(params, state, x, y)
    return params


def accuracy(ds, predict, bs=512, format=None):
    accs = []
    for s in range(4):
        if format is None:
            x, y = ds.batch_at(9000 + s, bs, "test")
        else:
            x, y = ds.batch_at(9000 + s, bs, "test", format=format)
        accs.append(float(jnp.mean(predict(x) == y)))
    return sum(accs) / len(accs)


def run_dense(task, steps):
    """The original paper-style report on the dense centroid stand-in."""
    ds = ExtremeDataset(ExtremeDataConfig(
        num_classes=task.small_classes, dim=task.small_dim, noise=0.1,
        zipf_a=1.0))
    cfg = task.mach(small=True)
    m = MACHLinear(cfg, task.small_dim)
    t0 = time.perf_counter()
    params = train(ds, m, m.init(jax.random.key(0)), steps=steps)
    t = time.perf_counter() - t0
    bayes = ds.bayes_accuracy(steps=2)
    print(f"    reduced-scale stand-in (K={task.small_classes}, "
          f"d={task.small_dim}, B={cfg.num_buckets}, "
          f"R={cfg.num_repetitions}; Zipf classes): "
          f"train {t:.0f}s, Bayes={bayes:.3f}")
    for est in ("unbiased", "min", "median"):
        acc = accuracy(ds, lambda x, e=est: m.predict(params, x,
                                                      estimator=e))
        marker = "   <- paper Eq. 2" if est == "unbiased" else ""
        print(f"      {est:9s} estimator: acc={acc:.3f}{marker}")


def run_sparse(task, steps):
    """Fused-CSR vs materializing-dense training on identical sparse
    data — the ODP §4 sparse-feature regime."""
    ds = SparseExtremeDataset(task.sparse_data(small=True))
    cfg = task.mach(small=True)
    nnz = ds.cfg.nnz
    print(f"    sparse stand-in (K={ds.cfg.num_classes}, "
          f"d={ds.cfg.num_features}, nnz={nnz}, B={cfg.num_buckets}, "
          f"R={cfg.num_repetitions}; Zipf features):")

    m_dense = MACHLinear(cfg, ds.cfg.num_features)
    m_fused = MACHLinear(cfg, ds.cfg.num_features, fused=True)
    init = m_dense.init(jax.random.key(0))

    t0 = time.perf_counter()
    p_dense = train(ds, m_dense, init, steps=steps, format="dense")
    t_dense = time.perf_counter() - t0
    t0 = time.perf_counter()
    p_fused = train(ds, m_fused, init, steps=steps, format="csr")
    t_fused = time.perf_counter() - t0

    acc_dense = accuracy(ds, lambda x: m_dense.predict(p_dense, x),
                         format="dense")
    acc_fused = accuracy(ds, lambda x: m_fused.predict(p_fused, x),
                         format="dense")
    delta = abs(acc_dense - acc_fused)
    print(f"      dense materializing path: acc={acc_dense:.3f} "
          f"({t_dense:.0f}s / {steps} steps)")
    print(f"      fused CSR path:           acc={acc_fused:.3f} "
          f"({t_fused:.0f}s / {steps} steps)")
    print(f"      |Δ| = {delta:.3f}  "
          f"{'OK (<= 0.02)' if delta <= 0.02 else 'DIVERGED'}")
    return delta


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--task", default="all",
                    choices=["all", "odp", "imagenet21k"])
    ap.add_argument("--small", action="store_true",
                    help="reduced-scale stand-in (the only offline mode; "
                         "kept explicit for scripts)")
    ap.add_argument("--steps", type=int, default=150)
    args = ap.parse_args()

    tasks = [t for t in (ODP, IMAGENET)
             if args.task in ("all", t.name)]
    ok = True
    for task in tasks:
        print(f"=== {task.name}: full scale K={task.num_classes:,} "
              f"d={task.dim:,} B={task.mach_b} R={task.mach_r}"
              f"{f' nnz~{task.nnz}' if task.sparse_features else ''}")
        oaa_gb = task.num_classes * task.dim * 4 / 1e9
        mach_gb = task.mach_b * task.mach_r * task.dim * 4 / 1e9
        print(f"    model size: OAA {oaa_gb:.0f} GB -> MACH {mach_gb:.2f} GB "
              f"({oaa_gb/mach_gb:.0f}x reduction; paper reports "
              f"{'125x/0.3GB-480x' if task.name == 'odp' else '2x'})")
        run_dense(task, args.steps)
        if task.sparse_features:
            ok = run_sparse(task, args.steps) <= 0.02 and ok
        print()
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
