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
"""

from __future__ import annotations

import time

import jax
import numpy as np

from bench import compare, generate, program
from bench.spec import load_module


class Driver:

    def __init__(self, cell, seed: int):
        self.config, self.traffic = cell.config, cell.traffic
        self.seed = seed
        self.n = self.traffic["queries_per_batch"]
        self.k = self.traffic["k"]
        self.key = generate.seed_key(seed)

    def _params(self):
        c, t = self.config, self.traffic
        return generate.make_params(
            generate.stream(self.key, "weights"), dim=c["dim"],
            reps=c["num_repetitions"], buckets=c["num_buckets"],
            w_std=float(t["w_std"]), b_std=float(t["b_std"]))

    def setup(self) -> None:
        c = self.config
        model = program.head(c)
        self.meta, self.topk, self.table = program.decode_calls(model, self.k)
        self.params = self._params()
        self.batches = generate.ring(generate.stream(self.key, "data"), c,
                                     self.traffic, self.n)
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
        params = self._params()
        worst: dict = {}
        for i in self.sample():
            vals_r, _, scores_r = ref.topk(
                self.config, params, self.batches[i % len(self.batches)],
                self.k)
            vals_p, ids_p = self.outputs[i]
            got = compare.decode_numbers(vals_p, ids_p, vals_r, scores_r)
            worst = {k: max(v, worst.get(k, -np.inf)) for k, v in got.items()}
        return worst
