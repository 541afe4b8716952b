"""MACH — Merged-Averaged Classifiers via Hashing (the paper's algorithm).

Three integration levels, lowest to highest:

* ``mach_loss``        — loss-level: R-head cross-entropy on hashed labels
                         (Algorithm 1's trainLogistic target transform).
* ``MACHLinear``       — the paper-faithful model: R independent B-way
                         *logistic regressions* over raw features (dense or
                         CSR-sparse), trained jointly or per-repetition
                         (embarrassingly parallel).
* ``MACHOutputHead``   — the framework feature: drop-in replacement for an
                         LM's d×V softmax head, producing (…, R, B) logits
                         with O(d·R·B) = O(d log K) parameters.

Both trainable heads implement the shared ``MACHHead`` abstraction, so
``loss`` / ``fused_loss`` / ``predict`` / ``param_count`` are one
surface from the paper's ODP logistic regression to LM output heads —
they cannot drift apart, and the fused logit-free training kernels
(``ops.mach_fused_xent`` / ``ops.mach_fused_xent_csr``) serve both.

Prediction (Algorithm 2) lives in ``estimators.py`` (reference) and
``kernels/mach_decode.py`` (fused TPU path).
"""

from __future__ import annotations

import abc
import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import estimators as est
from repro.core import hashing
from repro.kernels import phase


@dataclasses.dataclass(frozen=True)
class MACHConfig:
    """Static configuration of a MACH classifier/head.

    B and R are the paper's two knobs (memory BRd, inference RBd + KR).
    """

    num_classes: int            # K
    num_buckets: int            # B
    num_repetitions: int        # R
    seed: int = 0
    estimator: str = "unbiased"         # unbiased | min | median
    hash_kind: str = "auto"             # auto | carter_wegman | mult_shift

    def __post_init__(self):
        if self.num_buckets < 2:
            raise ValueError("B must be >= 2")
        if self.num_repetitions < 1:
            raise ValueError("R must be >= 1")
        if self.estimator not in est.ESTIMATORS:
            raise ValueError(f"estimator {self.estimator!r} not in {est.ESTIMATORS}")
        if self.hash_kind not in hashing.HASH_KINDS:
            raise ValueError(f"hash_kind {self.hash_kind!r} not in "
                             f"{hashing.HASH_KINDS}")

    @property
    def family(self):
        return hashing.make_hash_family(
            self.num_buckets, self.num_repetitions, self.seed, self.hash_kind)

    def table(self) -> jnp.ndarray:
        return self.family.table(self.num_classes)

    def table_np(self) -> np.ndarray:
        return self.family.table_np(self.num_classes)

    def hash_labels(self, labels: jnp.ndarray) -> jnp.ndarray:
        """(...,) class ids -> (R, ...) bucket ids."""
        return self.family.hash_labels(labels, self.num_classes)

    def inverted_table_np(self, pad_to: int = 128) -> np.ndarray:
        """(R·B, L) bucket -> class lists for candidate-filtered decode."""
        return hashing.inverted_table_np(self.table_np(), self.num_buckets,
                                         pad_to)

    def inverted_table(self, pad_to: int = 128) -> jnp.ndarray:
        return hashing.inverted_table(self.table_np(), self.num_buckets,
                                      pad_to)

    # --- theory (paper §3.1) ---
    def indistinguishable_bound(self) -> float:
        return hashing.indistinguishable_pair_bound(
            self.num_classes, self.num_buckets, self.num_repetitions)

    def memory_reduction(self) -> float:
        return hashing.memory_reduction(
            self.num_classes, self.num_buckets, self.num_repetitions)

    @staticmethod
    def from_delta(num_classes: int, num_buckets: int, delta: float = 1e-3,
                   **kw) -> "MACHConfig":
        """Build a config with R chosen by Theorem 2."""
        r = hashing.r_required(num_classes, num_buckets, delta)
        return MACHConfig(num_classes, num_buckets, r, **kw)


# ---------------------------------------------------------------------------
# Loss (training): R independent B-way cross entropies on hashed labels.
# ---------------------------------------------------------------------------

def mach_loss(logits: jnp.ndarray, hashed_labels: jnp.ndarray,
              weights: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean (over batch) of the summed R-head cross-entropy.

    logits:        (..., R, B)
    hashed_labels: (R, ...)  bucket ids — note leading R (hash-family layout)
    weights:       (...,) optional 0/1 mask (e.g. padding tokens)

    Each head j is its own B-way classifier on dataset D_j = {x, h_j(y)}
    (Algorithm 1); the joint loss is the sum over heads, which is exactly
    training the R models independently when the trunk is fixed — and
    shares the trunk forward pass when it is not.
    """
    r, b = logits.shape[-2], logits.shape[-1]
    if hashed_labels.shape[0] != r:
        raise ValueError(f"R mismatch: logits {logits.shape}, labels "
                         f"{hashed_labels.shape}")
    # (..., R, B) log-softmax over B per head
    logz = jax.nn.logsumexp(logits, axis=-1, keepdims=True)
    logp = logits - logz
    # move labels R-axis last to align with logits' (..., R)
    lbl = jnp.moveaxis(hashed_labels, 0, -1)          # (..., R)
    picked = jnp.take_along_axis(logp, lbl[..., None], axis=-1)[..., 0]  # (..., R)
    nll = -jnp.sum(picked, axis=-1)                   # (...,) summed over heads
    return _weighted_mean(nll, weights)


def _weighted_mean(nll: jnp.ndarray,
                   weights: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Mean per-example loss, optionally masked (all-zero weights -> 0)."""
    if weights is not None:
        return jnp.sum(nll * weights) / jnp.maximum(jnp.sum(weights), 1.0)
    return jnp.mean(nll)


def is_sparse_batch(x: Any) -> bool:
    """Duck-typed CSR batch check (``data.extreme.SparseBatch`` or any
    object with indptr/indices/values) — core stays import-free of the
    data layer."""
    return hasattr(x, "indptr") and hasattr(x, "indices") \
        and hasattr(x, "values")


def mach_meta_probs(logits: jnp.ndarray) -> jnp.ndarray:
    """(..., R, B) logits -> (R, ..., B) per-head probabilities P^j."""
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.moveaxis(p, -2, 0)


# ---------------------------------------------------------------------------
# The shared head abstraction: one training/prediction surface from the
# paper's ODP logistic regression to LM output heads.
# ---------------------------------------------------------------------------

class MACHHead(abc.ABC):
    """Abstract base for trainable MACH heads.

    Implementations provide ``init`` / ``head_logits`` / ``fused_loss``
    / ``param_count``; the base derives ``loss`` (materializing R-head
    CE on hashed labels), ``meta_probs``, ``predict`` and
    ``class_probs`` from ``head_logits``, so the two heads share one
    semantic definition of training and Algorithm-2 decoding.

    ``loss`` materializes the (…, R, B) logits; ``fused_loss`` is the
    logit-free counterpart (same value and gradients) routed through
    the fused kernels — implementations pick the dense or CSR-sparse
    entry point from their input type.
    """

    cfg: MACHConfig

    @abc.abstractmethod
    def init(self, key: jax.Array) -> dict:
        ...

    @abc.abstractmethod
    def head_logits(self, params: dict, inputs: Any) -> jnp.ndarray:
        """inputs -> (..., R, B) per-head bucket logits."""

    @abc.abstractmethod
    def fused_loss(self, params: dict, inputs: Any, labels: jnp.ndarray,
                   weights: Optional[jnp.ndarray] = None,
                   bucket_select: Optional[tuple] = None,
                   bucket_proxy: Optional[jnp.ndarray] = None,
                   use_pallas: Optional[bool] = None,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
        """Logit-free counterpart of ``loss`` (fused projection+CE).

        ``bucket_select=(c_sel, refresh_every)`` enables dynamic bucket
        selection: the fused loss runs over the top-``c_sel``
        proxy-scored bucket columns per repetition (label buckets
        force-included — one-sided, bounded bias; see
        ``ops.mach_fused_xent``).  ``bucket_proxy`` passes cached (R, B)
        proxy scores (``train.Trainer`` refreshes them every
        ``refresh_every`` steps via ``bucket_proxy_scores``)."""

    def bucket_proxy_scores(self, params: dict, inputs: Any) -> jnp.ndarray:
        """(R, B) proxy scores for dynamic bucket selection — the
        logits of the batch-mean activation.  Cacheable across steps;
        cheap (one d·R·B matvec)."""
        raise NotImplementedError

    @abc.abstractmethod
    def param_count(self) -> int:
        ...

    def loss(self, params: dict, inputs: Any, labels: jnp.ndarray,
             weights: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        return mach_loss(self.head_logits(params, inputs),
                         self.cfg.hash_labels(labels), weights)

    @phase.tagged(phase.DECODE_PROJECT)
    def meta_probs(self, params: dict, inputs: Any) -> jnp.ndarray:
        """getProbability of Algorithm 2: (R, ..., B)."""
        return mach_meta_probs(self.head_logits(params, inputs))

    def predict(self, params: dict, inputs: Any,
                estimator: Optional[str] = None,
                candidate_mode=None,
                inverted: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """argmax-class prediction (Algorithm 2).

        ``candidate_mode``: None | "exact" score all K classes; an
        (m, t) tuple routes through the count-min candidate filter —
        cost independent of K.  ``inverted`` is the table from
        ``cfg.inverted_table()`` (built here when omitted — pass it
        explicitly under jit, construction is host-side).
        """
        name = estimator or self.cfg.estimator
        meta = self.meta_probs(params, inputs)
        if candidate_mode is not None and candidate_mode != "exact":
            if inverted is None:
                inverted = self.cfg.inverted_table()
            _, idx = est.predict_topk(meta, self.cfg.table(), 1, name,
                                      candidate_mode=candidate_mode,
                                      inverted=inverted)
            return idx[..., 0]
        return est.predict_classes(meta, self.cfg.table(), name)

    def class_probs(self, params: dict, inputs: Any,
                    estimator: Optional[str] = None) -> jnp.ndarray:
        table = self.cfg.table()
        return est.estimate_class_probs(self.meta_probs(params, inputs),
                                        table,
                                        estimator or self.cfg.estimator)


# ---------------------------------------------------------------------------
# Paper-faithful model: R independent logistic regressions.
# ---------------------------------------------------------------------------

class MACHLinear(MACHHead):
    """R B-way logistic regressions on d features — the paper's §4 model.

    Parameters: W (d, R, B), b (R, B) — total d·R·B + R·B, i.e. the
    paper's BRd model size versus OAA's Kd.

    Inputs may be dense (n, d) arrays or CSR ``SparseBatch``es (the ODP
    bag-of-words regime).  With ``fused=True`` the training ``loss``
    routes through the fused logit-free kernels — dense or CSR entry
    point by input type, the bias a native in-kernel operand — so the
    (n, R·B) logits tensor (and for CSR the dense (n, d) activation)
    never materializes.  The per-repetition slice/merge API (paper
    §6.1 embarrassing parallelism) is unchanged.
    """

    def __init__(self, cfg: MACHConfig, dim: int, fused: bool = False):
        self.cfg = cfg
        self.dim = dim
        self.fused = fused

    def init(self, key: jax.Array) -> dict:
        wkey, _ = jax.random.split(key)
        scale = 1.0 / math.sqrt(self.dim)
        return {
            "w": jax.random.normal(wkey, (self.dim, self.cfg.num_repetitions,
                                          self.cfg.num_buckets), jnp.float32) * scale,
            "b": jnp.zeros((self.cfg.num_repetitions, self.cfg.num_buckets),
                           jnp.float32),
        }

    def head_logits(self, params: dict, x: Any) -> jnp.ndarray:
        """(n, d) dense or CSR SparseBatch -> (n, R, B)."""
        if is_sparse_batch(x):
            x = x.to_dense()          # materializing path only; fused stays sparse
        return jnp.einsum("nd,drb->nrb", x, params["w"]) + params["b"]

    # back-compat alias (pre-MACHHead name)
    def logits(self, params: dict, x: Any) -> jnp.ndarray:
        return self.head_logits(params, x)

    def loss(self, params: dict, x: Any, y: jnp.ndarray,
             weights: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """Routes through the fused logit-free path when ``fused=True``
        (identical value/grads), else materializes the (n, R, B) logits."""
        if self.fused:
            return self.fused_loss(params, x, y, weights)
        return super().loss(params, x, y, weights)

    @phase.tagged(phase.LOSS_FWD)
    def fused_loss(self, params: dict, x: Any, y: jnp.ndarray,
                   weights: Optional[jnp.ndarray] = None,
                   bucket_select: Optional[tuple] = None,
                   bucket_proxy: Optional[jnp.ndarray] = None,
                   use_pallas: Optional[bool] = None,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
        """Logit-free loss via ``ops.mach_fused_xent`` (dense x) or
        ``ops.mach_fused_xent_csr`` (SparseBatch x).  The bias is a
        native kernel operand on both branches — no per-step
        (d+1, R·B) W-concat on the dense path and no ELL widening on
        the CSR path; dbias comes from the kernels' (1, bc) scratch
        reduction.  ``bucket_select``/``bucket_proxy`` as on
        ``MACHHead.fused_loss``."""
        from repro.kernels import ops  # deferred: kernels import core
        c = self.cfg
        hashed = jnp.moveaxis(c.hash_labels(y), 0, -1)       # (n, R)
        w2 = params["w"].reshape(self.dim, -1)               # (d, R·B)
        bias = params["b"].reshape(-1)                       # (R·B,)
        if is_sparse_batch(x):
            nll = ops.mach_fused_xent_csr(
                x.indptr, x.indices, x.values, w2, hashed,
                num_buckets=c.num_buckets, nnz_max=x.nnz_max, bias=bias,
                bucket_select=bucket_select, bucket_proxy=bucket_proxy,
                use_pallas=use_pallas, interpret=interpret)
        else:
            nll = ops.mach_fused_xent(
                x, w2, hashed, num_buckets=c.num_buckets, bias=bias,
                bucket_select=bucket_select, bucket_proxy=bucket_proxy,
                use_pallas=use_pallas, interpret=interpret)
        return _weighted_mean(nll, weights)

    def bucket_proxy_scores(self, params: dict, x: Any) -> jnp.ndarray:
        """(R, B) dynamic-bucket-selection proxy from a dense or CSR
        batch (the CSR mean is a scatter-add — never densified)."""
        from repro.kernels import ops  # deferred: kernels import core
        w2 = params["w"].reshape(self.dim, -1)
        bias = params["b"].reshape(-1)
        if is_sparse_batch(x):
            return ops.mach_bucket_proxy(
                w=w2, num_buckets=self.cfg.num_buckets, bias=bias,
                csr=(x.indptr, x.indices, x.values))
        return ops.mach_bucket_proxy(
            x, w2, num_buckets=self.cfg.num_buckets, bias=bias)

    def param_count(self) -> int:
        c = self.cfg
        return self.dim * c.num_repetitions * c.num_buckets \
            + c.num_repetitions * c.num_buckets

    # --- embarrassing parallelism (paper §6.1): per-repetition slices ---
    @staticmethod
    def slice_repetition(params: dict, j: int) -> dict:
        """Extract repetition j's independent model (train anywhere)."""
        return {"w": params["w"][:, j], "b": params["b"][j]}

    @staticmethod
    def merge_repetitions(slices: list[dict]) -> dict:
        """Inverse of slice_repetition — merge R separately-trained models."""
        return {
            "w": jnp.stack([s["w"] for s in slices], axis=1),
            "b": jnp.stack([s["b"] for s in slices], axis=0),
        }


# ---------------------------------------------------------------------------
# LM integration: MACH output head replacing the d×V softmax.
# ---------------------------------------------------------------------------

class MACHOutputHead(MACHHead):
    """Drop-in replacement for an LM's unembedding: d -> (R, B) logits.

    The kernel is stored as (d, R*B) so the forward pass is a single
    MXU-friendly matmul; logits are reshaped to (..., R, B) for the loss.
    Sharding: logical axes ("embed", "mach_rb") — the R·B axis shards
    over the model axis exactly like a vocab-sharded softmax, at
    V/(R·B)× less collective volume.
    """

    def __init__(self, cfg: MACHConfig, dim: int, dtype=jnp.float32):
        self.cfg = cfg
        self.dim = dim
        self.dtype = dtype

    @property
    def out_features(self) -> int:
        return self.cfg.num_repetitions * self.cfg.num_buckets

    def init(self, key: jax.Array) -> dict:
        scale = 1.0 / math.sqrt(self.dim)
        return {"kernel": (jax.random.normal(key, (self.dim, self.out_features),
                                             jnp.float32) * scale).astype(self.dtype)}

    def apply(self, params: dict, h: jnp.ndarray) -> jnp.ndarray:
        """(..., d) hidden states -> (..., R, B) logits."""
        out = h @ params["kernel"].astype(h.dtype)
        return out.reshape(out.shape[:-1] + (self.cfg.num_repetitions,
                                             self.cfg.num_buckets))

    def head_logits(self, params: dict, h: jnp.ndarray) -> jnp.ndarray:
        return self.apply(params, h)

    @phase.tagged(phase.LOSS_FWD)
    def fused_loss(self, params: dict, h: jnp.ndarray, labels: jnp.ndarray,
                   weights: Optional[jnp.ndarray] = None,
                   bucket_select: Optional[tuple] = None,
                   bucket_proxy: Optional[jnp.ndarray] = None,
                   use_pallas: Optional[bool] = None,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
        """Logit-free counterpart of ``loss``: the projection is fused
        into the hashed cross-entropy (``ops.mach_fused_xent``), so the
        (…, R, B) logits tensor never exists — train-time activation
        memory is O(N·d), not O(N·R·B).  Same value and gradients as
        ``loss`` (the VJP accumulates dW and dh in-kernel).
        ``bucket_select``/``bucket_proxy`` as on ``MACHHead.fused_loss``."""
        from repro.kernels import ops  # deferred: kernels import core
        hashed = jnp.moveaxis(self.cfg.hash_labels(labels), 0, -1)
        nll = ops.mach_fused_xent(h, params["kernel"], hashed,
                                  num_buckets=self.cfg.num_buckets,
                                  bucket_select=bucket_select,
                                  bucket_proxy=bucket_proxy,
                                  use_pallas=use_pallas, interpret=interpret)
        return _weighted_mean(nll, weights)

    def bucket_proxy_scores(self, params: dict, h: jnp.ndarray) -> jnp.ndarray:
        """(R, B) dynamic-bucket-selection proxy from hidden states."""
        from repro.kernels import ops  # deferred: kernels import core
        return ops.mach_bucket_proxy(
            h, params["kernel"], num_buckets=self.cfg.num_buckets)

    def param_count(self) -> int:
        return self.dim * self.out_features

    def full_softmax_param_count(self) -> int:
        return self.dim * self.cfg.num_classes
