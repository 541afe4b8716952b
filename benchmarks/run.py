"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (shared report hook).

  fig1_tradeoff     paper Figure 1  (accuracy vs B, R)
  table2_resources  paper Table 2   (model size / time / accuracy)
  table3_estimators paper Table 3   (unbiased / min / median)
  bench_kernels     decode-cost claims (O(RBd+KR) vs O(Kd))
  bench_decode_topk streaming top-k decode vs (B, V) reference
                    (also writes BENCH_decode.json)
  bench_train_xent  fused projection+CE training loss vs materialized
                    logits, plus the 500k-label dynamic bucket-selection
                    gate: selected step must beat the full step at ≥5×
                    C-axis reduction with the NLL gap inside the
                    one-sided bias bound (also writes BENCH_xent.json)
  bench_sparse_xent fused CSR projection+CE vs densified reference —
                    the ODP sparse-feature path (also writes
                    BENCH_sparse.json)
  bench_serve       serving suite on Zipf ragged workloads: continuous
                    (slot) vs lockstep scheduler, paged KV pool vs
                    contiguous strips at equal HBM (4× slots + exact
                    parity + no-max_len-strip jaxpr gate), and
                    sustained Poisson traffic (p50/p99 latency ticks,
                    tokens/step) — also writes BENCH_serve.json
  roofline          §Roofline aggregation from the dry-run artifacts
"""

from __future__ import annotations

import argparse
import sys
import traceback

from repro.launch.compile_cache import enable_compile_cache


def _report(name: str, us_per_call: float, derived: str = "") -> None:
    print(f"{name},{us_per_call:.2f},{derived}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None,
                    help="subset of benchmark module names")
    args = ap.parse_args()
    enable_compile_cache()

    from benchmarks import (bench_decode_topk, bench_kernels, bench_serve,
                            bench_sparse_xent, bench_train_xent,
                            fig1_tradeoff, roofline, table2_resources,
                            table3_estimators)
    modules = {
        "table2_resources": table2_resources,
        "table3_estimators": table3_estimators,
        "bench_kernels": bench_kernels,
        "bench_decode_topk": bench_decode_topk,
        "bench_train_xent": bench_train_xent,
        "bench_sparse_xent": bench_sparse_xent,
        "bench_serve": bench_serve,
        "roofline": roofline,
        "fig1_tradeoff": fig1_tradeoff,
    }
    failed = []
    for name, mod in modules.items():
        if args.only and name not in args.only:
            continue
        try:
            mod.run(_report)
        except Exception as e:  # noqa: BLE001
            failed.append(name)
            traceback.print_exc()
            _report(f"{name}/FAILED", 0.0, repr(e))
            continue
        if not _check_regression(name, mod):
            failed.append(name)
    return 1 if failed else 0


def _check_regression(name: str, mod, fail_ratio: float = 1.25) -> bool:
    """Compare the module's freshly written BENCH file against the last
    committed version (median new/old ratio over all shared ``us_*``
    fields).  A median slowdown beyond ``fail_ratio`` fails the run —
    the perf trajectory is a gate, not a snapshot.  Modules without a
    ``BENCH_FILE``, or files with no committed baseline yet, pass."""
    import json

    from benchmarks.common import bench_regression, load_committed_bench

    bench_file = getattr(mod, "BENCH_FILE", None)
    if bench_file is None:
        return True
    old = load_committed_bench(bench_file)
    try:
        with open(bench_file) as f:
            new = json.load(f)
    except (OSError, json.JSONDecodeError):
        return True
    med, ratios, ok = bench_regression(old, new, fail_ratio)
    if med is None:
        # warning, not a crash: the suite ran, but its perf trajectory
        # is NOT gated until a baseline is committed
        print(f"WARNING: {bench_file} has no committed baseline "
              f"(`git show HEAD:{bench_file}` failed) — regression gate "
              f"skipped for {name}; commit the freshly written "
              f"{bench_file} to put this suite under the gate.",
              file=sys.stderr, flush=True)
        _report(f"{name}/regression", 0.0,
                f"WARNING: no committed baseline for {bench_file} — "
                "gate skipped")
        return True
    worst_key = max(ratios, key=ratios.get)
    _report(f"{name}/regression", 0.0,
            f"median={med:.2f}x over {len(ratios)} fields vs HEAD:"
            f"{bench_file} worst={worst_key}@{ratios[worst_key]:.2f}x "
            f"{'ok' if ok else f'FAIL(>{fail_ratio}x)'}")
    return ok


if __name__ == "__main__":
    sys.exit(main())
