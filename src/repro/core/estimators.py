"""MACH probability estimators (paper Eq. 2, 7, 8).

Given the R meta-class probability vectors ``meta_probs`` with shape
(R, ..., B) and the hash table (R, K), each estimator recovers per-class
probability estimates of shape (..., K):

  unbiased  p̂_i = B/(B−1) · [ mean_j P^j_{h_j(i)} − 1/B ]      (Eq. 2)
  min       p̂_i = min_j    P^j_{h_j(i)}                        (Eq. 7, count-min)
  median    p̂_i = median_j P^j_{h_j(i)}                        (Eq. 8, count-median)

The gathered tensor (R, ..., K) is materialized here — this module is
the *reference* path (and the oracle for the Pallas decode kernel, which
never materializes it).  ``argmax`` under the unbiased estimator equals
``argmax`` of the plain sum (the affine map is monotone), which is what
the fused kernel computes.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels import phase

ESTIMATORS = ("unbiased", "min", "median")


def gather_class_probs(meta_probs: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """(R, ..., B), (R, K) -> (R, ..., K): P^j_{h_j(i)} for every class i."""
    if meta_probs.shape[0] != table.shape[0]:
        raise ValueError(
            f"R mismatch: meta_probs {meta_probs.shape} vs table {table.shape}")
    return jnp.take_along_axis(
        meta_probs,
        table.reshape(table.shape[:1] + (1,) * (meta_probs.ndim - 2) + table.shape[1:]),
        axis=-1,
    )


def unbiased_estimator(meta_probs: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Paper Eq. 2 — unbiased estimate of Pr(y=i|x); shape (..., K)."""
    B = meta_probs.shape[-1]
    g = gather_class_probs(meta_probs, table)  # (R, ..., K)
    return (B / (B - 1.0)) * (jnp.mean(g, axis=0) - 1.0 / B)


def min_estimator(meta_probs: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Paper Eq. 7 — count-min sketch estimate; shape (..., K)."""
    g = gather_class_probs(meta_probs, table)
    return jnp.min(g, axis=0)


def median_estimator(meta_probs: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Paper Eq. 8 — count-median sketch estimate; shape (..., K)."""
    g = gather_class_probs(meta_probs, table)
    return jnp.median(g, axis=0)


_FNS = {
    "unbiased": unbiased_estimator,
    "min": min_estimator,
    "median": median_estimator,
}


def estimate_class_probs(meta_probs: jnp.ndarray, table: jnp.ndarray,
                         estimator: str = "unbiased") -> jnp.ndarray:
    """Dispatch over the three paper estimators."""
    try:
        fn = _FNS[estimator]
    except KeyError:
        raise ValueError(f"estimator must be one of {ESTIMATORS}, got {estimator!r}")
    return fn(meta_probs, table)


def predict_classes(meta_probs: jnp.ndarray, table: jnp.ndarray,
                    estimator: str = "unbiased") -> jnp.ndarray:
    """argmax_i p̂_i — the paper's classification rule; shape (...,)."""
    return jnp.argmax(estimate_class_probs(meta_probs, table, estimator), axis=-1)


@phase.tagged(phase.DECODE_TOPK)
def predict_topk(meta_probs: jnp.ndarray, table: jnp.ndarray, k: int,
                 estimator: str = "unbiased", *,
                 candidate_mode=None,
                 inverted: Optional[jnp.ndarray] = None,
                 use_pallas: Optional[bool] = None,
                 interpret: Optional[bool] = None
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k (p̂ values, class ids) under the chosen estimator.

    meta_probs: (R, ..., B) — same layout as the other estimators here.
    Routes to the fused streaming kernel when available (TPU, or forced
    with ``use_pallas=True``), which never materializes the (..., K)
    score matrix; otherwise the blocked streaming fallback.  Returns
    ((..., k) f32, (..., k) int32).

    ``candidate_mode``: None | "exact" stream all K classes; an (m, t)
    tuple routes through the count-min candidate filter (requires
    ``inverted``, the (R·B, L) table from ``hashing.inverted_table``) —
    cost independent of K, top-k approximate (see ops.mach_topk).
    """
    from repro.kernels import ops  # deferred: kernels sit above core
    return ops.mach_topk(jnp.moveaxis(meta_probs, 0, -2), table,
                         num_classes=table.shape[-1], k=k,
                         estimator=estimator, candidate_mode=candidate_mode,
                         inverted=inverted, use_pallas=use_pallas,
                         interpret=interpret)
