"""toy_fwd_ms: device ms per call of the ops tagged ``toy.fwd``."""

from bench import roofline


def read(facts):
    return roofline.phase_ms("toy.fwd", ("jit_forward",), facts)
