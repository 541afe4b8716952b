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

Calibration (``bench/calibrate.py``): the check reads set-up's steps, so
no window is needed; the control is the reference at a lower precision
over the same steps, and the half-batch fault the reference taking its
loss over the first half of each batch.
"""

from __future__ import annotations

import collections
import time

import jax
import numpy as np

from bench import compare, generate
from bench.spec import load_module

IN_FLIGHT = 2
CHECK_READS_WINDOW = False


def _ring(cell, key):
    return generate.ring(generate.stream(key, "data"), cell.config,
                         cell.traffic, cell.traffic["examples_per_step"])


def half_batch_rows(cell) -> int:
    return cell.traffic["examples_per_step"] // 2


def readings(cell, seed: int, mode: str, rows: int | None = None) -> dict:
    """The reference at ``mode``, over ``rows`` rows of each batch (all
    where None), in the program's place over the checked steps, compared
    with the reference."""
    ref = load_module("reference", cell.config["reference"])
    key = generate.seed_key(seed)
    params = generate.head_params(key, cell.config, cell.traffic)
    batches = _ring(cell, key)[:cell.traffic["check_steps"]]
    hi = ref.train(cell.config, params, batches)
    lo = ref.train(cell.config, params, batches, mode, rows)
    return compare.train_numbers(lo[0], hi[0], lo[1], hi[1], params, lo[2],
                                 hi[2])


class Driver:

    def __init__(self, cell, seed: int):
        self.cell = cell
        self.config, self.traffic = cell.config, cell.traffic
        self.seed = seed
        self.n = self.traffic["examples_per_step"]
        self.key = generate.seed_key(seed)
        self.program = load_module("programs", self.config["model"])

    def setup(self) -> None:
        c, program = self.config, self.program
        model = program.head(c)
        opt = program.optimizer(c)
        self.step = program.train_step(model, opt)
        params = generate.head_params(self.key, self.config,
                                      self.traffic)
        state = jax.jit(opt.init)(params)
        self.batches = _ring(self.cell, self.key)
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
        p0 = generate.head_params(self.key, self.config, self.traffic)
        k = self.traffic["check_steps"]
        losses, grad, after = ref.train(self.config, p0, self.batches[:k])
        return compare.train_numbers(self.losses, losses, self.grad, grad,
                                     p0, self.after, after)
