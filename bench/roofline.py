"""Shares of the chip's peak, for the metric readers.

A roofline share is the least time the chip could take for a kernel's
work (the larger of its operations over the peak rate and its bytes
over the peak bandwidth) over the time the kernel took in the trace.
A model-FLOP utilization is the operations the model needs per item,
times items per second, over the peak rate.  Beside them, the device
time of one program phase per call.
"""

from __future__ import annotations

from bench.spec import ABSENT, load_module


def work(kernel: str, facts) -> dict:
    return load_module("work", kernel).work(facts.config, facts.traffic)


def kernel_share(kernel: str, modules: tuple[str, ...], calls: float,
                 facts):
    """Percent of the roofline that the kernels run inside ``modules``
    reached over ``calls`` units of work; None where the trace holds
    none of them."""
    if facts.trace is None or facts.peak is None:
        return None
    ns, count = facts.trace.kernel_ns(modules)
    if not count or ns <= 0:
        return None
    w = work(kernel, facts)
    t_flops = w["flops"] / facts.peak["bf16_flops_per_s"]
    t_bytes = w["bytes"] / facts.peak["hbm_bytes_per_s"]
    least = max(t_flops, t_bytes) * calls
    return {"value": 100.0 * least / (ns * 1e-9),
            "bound": "flops" if t_flops >= t_bytes else "bytes"}


def mfu(flops_per_call: float, calls: float, facts):
    if facts.peak is None or facts.window_s <= 0:
        return None
    return 100.0 * flops_per_call * calls / facts.window_s \
        / facts.peak["bf16_flops_per_s"]


def idle(facts):
    if facts.trace is None or facts.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - facts.trace.busy_s / facts.trace.window_s)


def phase_ms(phase: str, modules: tuple[str, ...], facts):
    """Device ms per call of the ops tagged ``phase`` (``""``: untagged)
    inside the jitted programs ``modules`` (``trace.Summary.phase_ms``);
    ABSENT where the program tags no op (one older than the tags), None
    where none of ``modules`` ran."""
    if facts.trace is None:
        return None
    if not facts.trace.tagged:
        return ABSENT
    return facts.trace.phase_ms(phase, modules)
