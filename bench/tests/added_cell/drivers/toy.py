"""Traffic kind "toy": one client sending ring batches of routed rows to
the toy expert layer in a closed loop.  The window counts the rows that
the experts held here computed (a program counter); the check compares
the first ``check_batches`` outputs with the reference."""

import time

import jax
import numpy as np

from bench import generate
from bench.spec import load_module

CHECK_READS_WINDOW = True


def _inputs(cell, seed):
    c, t = cell.config, cell.traffic
    key = generate.seed_key(seed)
    kx, kr = jax.random.split(generate.stream(key, "data"))
    w = jax.random.normal(generate.stream(key, "weights"),
                          (c["num_experts"], c["dim"],
                           c["num_buckets"] * c["num_repetitions"]))
    xs = jax.random.normal(kx, (t["ring"], t["rows"], c["dim"]))
    routes = jax.random.randint(kr, (t["ring"], t["rows"]), 0,
                                t["routed_over"])
    return w, xs, routes


def half_batch_rows(cell):
    return None


def readings(cell, seed, mode, rows=None):
    ref = load_module("reference", cell.config["reference"])
    w, xs, routes = _inputs(cell, seed)
    return {"out_gap": max(
        ref.gap(ref.forward(cell.config, w, xs[i], routes[i], mode),
                ref.forward(cell.config, w, xs[i], routes[i]))
        for i in range(cell.traffic["check_batches"]))}


class Driver:

    def __init__(self, cell, seed):
        self.cell, self.seed = cell, seed
        self.program = load_module("programs", cell.config["model"])

    def setup(self):
        self.w, self.xs, self.routes = _inputs(self.cell, self.seed)
        jax.block_until_ready(self.program.forward(self.w, self.xs[0],
                                                   self.routes[0]))

    def window(self, seconds):
        outputs, held, ring = [], [], len(self.xs)
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            while not outputs or time.perf_counter() - t0 < seconds:
                i = len(outputs) % ring
                y, n = self.program.forward(self.w, self.xs[i],
                                            self.routes[i])
                outputs.append(y.block_until_ready())
                held.append(n)
            elapsed = time.perf_counter() - t0
        self.outputs = outputs
        rows = len(outputs) * self.cell.traffic["rows"]
        return {"attempted": len(outputs), "failed": 0, "items": rows,
                "seconds": elapsed, "longest_s": elapsed,
                "metrics": {"toy_rows_per_s": rows / elapsed},
                "counters": {"toy_held_rows": int(np.sum(held))}}

    def release(self):
        del self.w

    def check(self):
        ref = load_module("reference", self.cell.config["reference"])
        w, xs, routes = _inputs(self.cell, self.seed)
        n = min(self.cell.traffic["check_batches"], len(self.outputs))
        return {"out_gap": max(
            ref.gap(self.outputs[i],
                    ref.forward(self.cell.config, w, xs[i], routes[i]))
            for i in range(n))}
