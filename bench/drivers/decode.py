"""Traffic kind "decode": one client scoring batches in a closed loop.

Set-up makes the weights and a ring of query batches on the device from
the seed and warms the two jitted calls the window drives, the
program's ``meta_probs`` (projection + per-head softmax) and
``predict_topk`` (the streaming top-k kernel).  In the window each
batch is timed from its dispatch to its ids being ready, and the next
is sent only then.  ``decode_queries_per_s`` is the queries of every
batch over the window; ``decode_p95_ms`` the 95th percentile of every
batch's latency (a query's latency is its batch's).

After the window a sample of the finished batches, drawn from the seed,
is compared with the reference: their top-k values and ids.

Calibration (``bench/calibrate.py``): the check compares what a window
produced; the control is the reference at a lower precision over the
first ``check_batches`` batches of the ring.  Decode has no half-batch
fault.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from bench import compare, generate
from bench.spec import load_module

CHECK_READS_WINDOW = True


def _ring(cell, key):
    return generate.ring(generate.stream(key, "data"), cell.config,
                         cell.traffic, cell.traffic["queries_per_batch"])


def half_batch_rows(cell) -> None:
    return None


def readings(cell, seed: int, mode: str, rows: int | None = None) -> dict:
    """The reference at ``mode`` in the program's place over the first
    ``check_batches`` batches, compared with the reference; the worst
    batch counts."""
    if rows is not None:
        raise ValueError("decode has no half-batch fault")
    ref = load_module("reference", cell.config["reference"])
    key = generate.seed_key(seed)
    params = generate.head_params(key, cell.config, cell.traffic)
    batches = _ring(cell, key)
    c, k = cell.config, cell.traffic["k"]
    worst: dict = {}
    for batch in batches[:cell.traffic["check_batches"]]:
        vals_r, _, scores_r = ref.topk(c, params, batch, k)
        vals_c, ids_c, _ = ref.topk(c, params, batch, k, mode)
        got = compare.decode_numbers(vals_c, ids_c, vals_r, scores_r)
        worst = {n: max(v, worst.get(n, -1.0)) for n, v in got.items()}
    return worst


class Driver:

    def __init__(self, cell, seed: int):
        self.cell = cell
        self.config, self.traffic = cell.config, cell.traffic
        self.seed = seed
        self.n = self.traffic["queries_per_batch"]
        self.k = self.traffic["k"]
        self.key = generate.seed_key(seed)
        self.program = load_module("programs", self.config["model"])

    def setup(self) -> None:
        c, program = self.config, self.program
        model = program.head(c)
        self.meta, self.topk, self.table = program.decode_calls(model, self.k)
        self.params = generate.head_params(self.key, self.config,
                                           self.traffic)
        self.batches = _ring(self.cell, self.key)
        self.inputs = [program.inputs(c, b)[0] for b in self.batches]
        for x in self.inputs[:2]:
            jax.block_until_ready(self.topk(self.meta(self.params, x),
                                            self.table))

    def window(self, seconds: float) -> dict:
        meta, topk, params, table = (self.meta, self.topk, self.params,
                                     self.table)
        ring = self.inputs
        outputs, latency = [], []
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                x = ring[len(outputs) % len(ring)]
                start = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    vals, ids = topk(meta(params, x), table)
                with jax.profiler.TraceAnnotation("bench.wait"):
                    ids.block_until_ready()
                latency.append(time.perf_counter() - start)
                outputs.append((vals, ids))
            elapsed = time.perf_counter() - t0
        self.outputs = outputs
        vals = np.stack([v for v, _ in jax.device_get(outputs)])
        ids = np.sort(np.stack([i for _, i in jax.device_get(outputs)]), -1)
        bad = ((~np.isfinite(vals)) | (ids < 0)
               | (ids >= self.config["num_classes"])).any(axis=(1, 2))
        bad |= (ids[..., 1:] == ids[..., :-1]).any(axis=(1, 2))
        failed = int(bad.sum())
        batches = len(outputs)
        return {"attempted": batches, "failed": failed,
                "items": batches * self.n, "seconds": elapsed,
                "longest_s": max(latency),
                "metrics": {
                    "decode_queries_per_s": batches * self.n / elapsed,
                    "decode_p95_ms": float(np.percentile(latency, 95)) * 1e3}}

    def release(self) -> None:
        del self.params, self.inputs, self.meta, self.topk

    def sample(self) -> list[int]:
        """Indices of the finished batches the check compares."""
        rng = np.random.default_rng([self.seed, 3])
        n = len(self.outputs)
        size = min(self.traffic["check_batches"], n)
        return sorted(int(i) for i in rng.choice(n, size, replace=False))

    def check(self) -> dict:
        ref = load_module("reference", self.config["reference"])
        params = generate.head_params(self.key, self.config,
                                      self.traffic)
        worst: dict = {}
        for i in self.sample():
            vals_r, _, scores_r = ref.topk(
                self.config, params, self.batches[i % len(self.batches)],
                self.k)
            vals_p, ids_p = self.outputs[i]
            got = compare.decode_numbers(vals_p, ids_p, vals_r, scores_r)
            worst = {k: max(v, worst.get(k, -np.inf)) for k, v in got.items()}
        return worst
