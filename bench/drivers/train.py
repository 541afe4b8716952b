"""Traffic kind "train": a closed training loop of the program's step.

Set-up makes the weights and a ring of batches on the device from the
seed, builds the jitted step and its optimizer state once, and drives
that same object through the first ``check_steps`` steps on ring
entries 0, 1, 2 (all rows differ).  Those steps compile and warm the
step, and the check reads from them: each step's loss, the first
gradient (Adam's first moment after step 1 over 1 - b1), and the
parameters after the last, copied to the host so that the device holds
only what training holds.  The window then goes on from the next ring
entry with the same call, steps dispatched back to back and at most
two in flight.  ``train_examples_per_s`` is the examples of every step
dispatched in the window over the time from its start to the end of the
last of them.
"""

from __future__ import annotations

import collections
import time

import jax
import numpy as np

from bench import compare, generate, program
from bench.spec import load_module

IN_FLIGHT = 2


class Driver:

    def __init__(self, cell, seed: int):
        self.config, self.traffic = cell.config, cell.traffic
        self.seed = seed
        self.n = self.traffic["examples_per_step"]
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
        opt = program.optimizer(c)
        self.step = program.train_step(model, opt)
        params = self._params()
        state = jax.jit(opt.init)(params)
        self.batches = generate.ring(generate.stream(self.key, "data"), c,
                                     self.traffic, self.n)
        if len(self.batches) <= self.traffic["check_steps"]:
            raise ValueError("the ring must outlast the checked steps")
        self.inputs = [program.inputs(c, b) for b in self.batches]
        scale = np.float32(1.0 / (1.0 - c["b1"]))
        losses = []
        for i in range(self.traffic["check_steps"]):
            params, state, loss = self.step(params, state, *self.inputs[i])
            losses.append(loss)
            if i == 0:
                self.grad = jax.tree.map(
                    lambda m: m * scale,
                    jax.device_get(program.first_moment(state)))
        self.after = jax.device_get(params)
        self.losses = [float(v) for v in losses]
        self.params, self.state = params, state

    def window(self, seconds: float) -> dict:
        step, p, s = self.step, self.params, self.state
        ring = self.inputs
        i = self.traffic["check_steps"]
        losses, pending = [], collections.deque()
        longest = 0.0
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = last = time.perf_counter()
            while True:
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    p, s, loss = step(p, s, *ring[i % len(ring)])
                i += 1
                losses.append(loss)
                pending.append(loss)
                if len(pending) > IN_FLIGHT:
                    with jax.profiler.TraceAnnotation("bench.wait"):
                        pending.popleft().block_until_ready()
                now = time.perf_counter()
                longest, last = max(longest, now - last), now
                if now - t0 >= seconds:
                    break
            with jax.profiler.TraceAnnotation("bench.wait"):
                jax.block_until_ready((p, s, loss))
            elapsed = time.perf_counter() - t0
        self.params, self.state = p, s
        steps = len(losses)
        failed = int(np.sum(~np.isfinite(np.asarray(jax.device_get(losses)))))
        return {"attempted": steps, "failed": failed,
                "items": steps * self.n, "seconds": elapsed,
                "longest_s": longest,
                "metrics": {"train_examples_per_s":
                            steps * self.n / elapsed}}

    def release(self) -> None:
        del self.params, self.state, self.step, self.inputs

    def check(self) -> dict:
        """The reference over the checked steps, and the numbers."""
        ref = load_module("reference", self.config["reference"])
        p0 = self._params()
        k = self.traffic["check_steps"]
        losses, grad, after = ref.train(self.config, p0, self.batches[:k])
        return compare.train_numbers(self.losses, losses, self.grad, grad,
                                     p0, self.after, after)
