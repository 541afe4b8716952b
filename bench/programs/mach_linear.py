"""The system under test for configurations whose "model" is
``mach_linear``, built from the configuration file.

Everything the ``train`` and ``decode`` drivers take from the program
(``src/repro``) goes through here: the MACH head, its optimizer and
jitted train step, the jitted decode calls, and the program's own input
type for CSR batches.
"""

from __future__ import annotations


def head(config: dict):
    """``MACHLinear(fused=True)`` at the configuration's sizes."""
    from repro.core import MACHConfig, MACHLinear
    if config["model"] != "mach_linear" or config["dtype"] != "float32":
        raise ValueError("the benchmark builds float32 MACHLinear heads")
    cfg = MACHConfig(num_classes=config["num_classes"],
                     num_buckets=config["num_buckets"],
                     num_repetitions=config["num_repetitions"],
                     seed=config["hash_seed"],
                     estimator=config["estimator"],
                     hash_kind=config["hash"])
    return MACHLinear(cfg, config["dim"], fused=True)


def optimizer(config: dict):
    from repro.optim import adamw
    if config["optimizer"] != "adamw":
        raise ValueError(f"unknown optimizer {config['optimizer']!r}")
    return adamw(config["learning_rate"], b1=config["b1"], b2=config["b2"],
                 eps=config["eps"], weight_decay=config["weight_decay"])


def train_step(model, opt):
    """The program's jitted step: (params, opt_state, x, y) -> (params,
    opt_state, loss), params and state donated."""
    from repro.train.trainer import make_head_step
    return make_head_step(model.loss, opt)


def first_moment(opt_state):
    """Adam's first moment (a tree like the parameters)."""
    return opt_state.mu


def decode_calls(model, k: int):
    """-> (meta, topk): jitted ``meta_probs(params, x)`` -> (R, N, B) and
    ``predict_topk(meta, table)`` -> (values (N, k), ids (N, k)), and the
    (R, K) table the second takes."""
    import jax
    from repro.core import estimators
    meta = jax.jit(model.meta_probs)
    topk = jax.jit(estimators.predict_topk,
                   static_argnames=("k", "estimator"))
    estimator = model.cfg.estimator
    return (meta, lambda m, t: topk(m, t, k=k, estimator=estimator),
            model.cfg.table())


def inputs(config: dict, batch: dict):
    """A generated batch as the program takes it: (x, y)."""
    if "x" in batch:
        return batch["x"], batch["y"]
    from repro.data.extreme import SparseBatch
    return (SparseBatch(batch["indptr"], batch["indices"], batch["values"],
                        num_features=config["dim"], nnz_max=config["nnz"]),
            batch["y"])
