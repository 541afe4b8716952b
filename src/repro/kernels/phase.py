"""The program phases that tag every device op of the MACH hot path.

Each op carries its phase as the HLO frontend attribute ``mach_phase``,
which the profiler's device op events keep, so a trace splits device
time by phase.  XLA code is tagged with ``tag(phase)`` or the
``tagged(phase)`` decorator (JAX's ``set_xla_metadata``; the innermost
tag wins), a Pallas kernel with ``pallas_call(...,
metadata=kernel_metadata(phase))``.  Tags are
attributes fixed at compile time: there is no switch and no run-time
cost.  Ops that XLA adds itself (layout copies, for one) carry none.

Autodiff transposes an op under its forward op's tag, so the glue
around a custom VJP (the mean's cotangent, reshapes of dW) reads
``loss.fwd``; the custom backward bodies set ``loss.bwd`` themselves.
"""

from __future__ import annotations

import functools

from jax.experimental.xla_metadata import set_xla_metadata

KEY = "mach_phase"

LOSS_FWD = "loss.fwd"          # fused loss forward and its glue
LOSS_BWD = "loss.bwd"          # the custom-VJP backward bodies
OPTIM = "optim"                # optimizer update and apply_updates
DECODE_PROJECT = "decode.project"  # features -> per-head probabilities
DECODE_TOPK = "decode.topk"    # estimator scores and the running top-k


def tag(phase: str):
    """Context manager tagging every op traced inside it."""
    return set_xla_metadata(**{KEY: phase})


def tagged(phase: str):
    """Decorator: the function's ops are traced under ``tag(phase)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            with tag(phase):
                return fn(*args, **kwargs)
        return run
    return wrap


def kernel_metadata(phase: str) -> dict:
    """``metadata=`` of a ``pallas_call`` in ``phase``."""
    return {KEY: phase}
