"""project_ms: device time of the decode's projection, in ms per batch:
the ops tagged ``decode.project`` (the CSR densify, the projection and
the per-head softmax) inside the jitted ``meta_probs``.  A program
without phase tags leaves the metric out."""

from bench import roofline

PHASE = "decode.project"
MODULES = ("jit_meta_probs",)


def read(facts):
    return roofline.phase_ms(PHASE, MODULES, facts)
