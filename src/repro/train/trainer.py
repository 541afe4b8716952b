"""Training loop: jit/pjit train step, microbatching, clipping, metrics.

The train step is a single jit-compiled function (state, batch) ->
(state, metrics).  Under a mesh, state and batch shardings come from
``sharding/partitioning.py`` and the same code runs SPMD — there is no
separate "distributed trainer".  MACH drops in through the model's loss
(the R-head hashed cross-entropy); nothing in the loop is MACH-specific,
which is exactly the paper's point that the R meta-classifiers are
plain classifiers.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.optim import (accumulate_grads, apply_updates,
                         clip_by_global_norm, make_optimizer, make_schedule)
from repro.train.train_state import TrainState, new_train_state


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    optimizer: str = "adamw"
    peak_lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    schedule: str = "warmup_cosine"
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    num_microbatches: int = 1
    master_weights: bool = False     # f32 masters for bf16 params
    checkpoint_every: int = 100
    keep_checkpoints: int = 3
    log_every: int = 10


def make_optimizer_from_config(tcfg: TrainConfig):
    if tcfg.schedule == "warmup_cosine":
        sched = make_schedule("warmup_cosine", peak=tcfg.peak_lr,
                              warmup_steps=tcfg.warmup_steps,
                              total_steps=tcfg.total_steps)
    elif tcfg.schedule == "constant":
        sched = make_schedule("constant", value=tcfg.peak_lr)
    else:
        sched = make_schedule(tcfg.schedule, peak=tcfg.peak_lr,
                              warmup_steps=tcfg.warmup_steps)
    kw = {}
    if tcfg.optimizer in ("adamw",):
        kw["weight_decay"] = tcfg.weight_decay
    return make_optimizer(tcfg.optimizer, sched,
                          master_weights=tcfg.master_weights, **kw), sched


def make_train_step(loss_fn: Callable[[Any, dict], tuple],
                    tcfg: TrainConfig):
    """loss_fn(params, batch) -> (loss, metrics).  Returns the pure
    (state, batch) -> (state, metrics) step (jit it with shardings)."""
    opt, sched = make_optimizer_from_config(tcfg)

    def step_fn(state: TrainState, batch: dict):
        (loss, metrics), grads = accumulate_grads(
            loss_fn, state.params, batch, tcfg.num_microbatches)
        grads, gnorm = clip_by_global_norm(grads, tcfg.clip_norm)
        updates, opt_state = opt.update(grads, state.opt_state, state.params)
        params = apply_updates(state.params, updates)
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        metrics["lr"] = sched(state.step)
        return TrainState(state.step + 1, params, opt_state), metrics

    return step_fn, opt


def make_head_step(loss_fn: Callable, opt):
    """The jitted step of a MACH head trained on its own (the paper's
    logistic-regression runs): ``loss_fn(params, x, y)`` -> scalar;
    returns ``step(params, opt_state, x, y) -> (params, opt_state,
    loss)``.  params and opt_state are donated — updated in place."""

    def step(params, opt_state, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
        updates, opt_state = opt.update(grads, opt_state, params)
        return apply_updates(params, updates), opt_state, loss

    return jax.jit(step, donate_argnums=(0, 1))


class Trainer:
    """Single-host convenience driver (examples, tests).  The pod-scale
    path is launch/train.py which jits the same step under a mesh."""

    def __init__(self, model, tcfg: TrainConfig,
                 loss_fn: Optional[Callable] = None,
                 bucket_proxy_fn: Optional[Callable] = None):
        self.model = model
        self.tcfg = tcfg
        self.loss_fn = loss_fn or model.loss
        self.step_fn, self.opt = make_train_step(self.loss_fn, tcfg)
        self._jit_step = jax.jit(self.step_fn, donate_argnums=(0,))
        # Dynamic bucket selection: ``bucket_proxy_fn(params, batch)``
        # -> (R, B) proxy scores, recomputed every ``refresh_every``
        # steps (cfg.mach_bucket_select = (c_sel, refresh_every)) and
        # injected as batch["bucket_proxy"].  Without it the model's
        # loss recomputes the proxy in-graph each step — same math,
        # no cross-step caching.
        self.bucket_proxy_fn = bucket_proxy_fn
        sel = getattr(getattr(model, "cfg", None),
                      "mach_bucket_select", None)
        self._proxy_every = sel[1] if sel is not None and len(sel) > 1 else 1
        self._proxy = None

    def _with_bucket_proxy(self, state: TrainState, batch, step: int):
        """Refresh the cached bucket-proxy scores on schedule and hand
        them to the loss.  Selection itself is recomputed in-graph with
        the current batch's label buckets force-included, so a stale
        proxy only affects which *negative* buckets the loss sees."""
        if self.bucket_proxy_fn is None or not isinstance(batch, dict):
            return batch
        if self._proxy is None or step % max(self._proxy_every, 1) == 0:
            self._proxy = self.bucket_proxy_fn(state.params, batch)
        return {**batch, "bucket_proxy": self._proxy}

    def init_state(self, key) -> TrainState:
        params, _ = self.model.init(key)
        return new_train_state(params, self.opt)

    def fit(self, state: TrainState, stream, num_steps: int,
            manager=None, monitor=None, log=print) -> TrainState:
        start = int(state.step)
        for s in range(start, start + num_steps):
            t0 = time.perf_counter()
            batch = self._with_bucket_proxy(state, stream.batch_at(s), s)
            state, metrics = self._jit_step(state, batch)
            if monitor is not None:
                jax.block_until_ready(state.params)
                monitor.record(s, time.perf_counter() - t0)
            if manager is not None and (s + 1) % self.tcfg.checkpoint_every == 0:
                manager.save(s + 1, state, blocking=False)
            if (s + 1) % self.tcfg.log_every == 0 and log:
                log(f"step {s+1}: loss={float(metrics['loss']):.4f} "
                    f"gnorm={float(metrics['grad_norm']):.3f} "
                    f"lr={float(metrics['lr']):.2e}")
        if manager is not None:
            manager.save(start + num_steps, state, blocking=True)
        return state
