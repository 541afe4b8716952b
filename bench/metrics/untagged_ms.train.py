"""untagged_ms.train: device time of the ops that carry no phase tag
(ops XLA made itself, such as layout copies), in ms per training step,
inside the jitted train step, ``jit_step``.  A program without phase
tags leaves the metric out."""

from bench import roofline

PHASE = ""
MODULES = ("jit_step",)


def read(facts):
    return roofline.phase_ms(PHASE, MODULES, facts)
