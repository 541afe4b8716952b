"""Fused streaming top-k MACH decode (the sampling/serving hot path).

Every path that needs more than the argmax — top-k sampling, the
min/median estimators, retrieval-style serving — goes through the
streaming kernel in ``mach_decode.py``: per K block the estimator's
scores are built in VMEM from on-the-fly one-hot matmuls and merged into
a running top-k held in VMEM scratch, so the (N, K) score matrix never
exists anywhere.  This module is its public top-k entry: argument
checks, and values on the estimator's scale —

    unbiased  (Eq. 2)  — the affine map of the summed scores, applied
                         after selection (it is monotone, so the order
                         is identical),
    min       (Eq. 7)  — the min over the R gathered values,
    median    (Eq. 8)  — their median.

Ties resolve to the lowest class id, like ``jax.lax.top_k`` on the full
score matrix.

HBM traffic: O(N·R·B + K·R [table mode] + N·k) vs the reference path's
O(N·K) score materialization — the paper's O(RBd + KR) serving claim,
extended from argmax to top-k.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.core.estimators import ESTIMATORS
from repro.kernels.mach_decode import streaming_topk


def mach_topk_pallas(meta_probs: jnp.ndarray,
                     table: Optional[jnp.ndarray] = None,
                     *,
                     num_classes: int,
                     k: int,
                     estimator: str = "unbiased",
                     inline_coeffs: Optional[jnp.ndarray] = None,
                     inline_shift: Optional[int] = None,
                     block_n: Optional[int] = None,
                     block_k: Optional[int] = None,
                     interpret: bool = False
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused streaming top-k.  meta_probs (N, R, B) -> (val, idx) (N, k).

    Values are on the chosen estimator's scale (matching
    ``estimators.estimate_class_probs`` + ``jax.lax.top_k`` up to tie
    order).  Exactly one of ``table`` ((R, K) int32) or
    (``inline_coeffs`` ((R,) uint32), ``inline_shift``) must be given.
    """
    _, r, b = meta_probs.shape
    if estimator not in ESTIMATORS:
        raise ValueError(f"estimator must be one of {ESTIMATORS}, "
                         f"got {estimator!r}")
    if not 1 <= k <= num_classes:
        raise ValueError(f"need 1 <= k <= num_classes, got k={k}, "
                         f"num_classes={num_classes}")
    val, idx = streaming_topk(
        meta_probs, table, num_classes=num_classes, k=k,
        estimator=estimator, inline_coeffs=inline_coeffs,
        inline_shift=inline_shift, block_n=block_n, block_k=block_k,
        interpret=interpret)
    if estimator == "unbiased":
        # Eq. 2's affine map of the summed scores — monotone, so applying
        # it after selection preserves the ordering bit-for-bit.
        val = (b / (b - 1.0)) * (val / r - 1.0 / b)
    return val, idx
