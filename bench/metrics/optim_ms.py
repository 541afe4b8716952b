"""optim_ms: device time of the optimizer, in ms per training step: the
ops tagged ``optim`` (Adam's update and its application to the
parameters) inside the jitted train step, ``jit_step``.  A program
without phase tags leaves the metric out."""

from bench import roofline

PHASE = "optim"
MODULES = ("jit_step",)


def read(facts):
    return roofline.phase_ms(PHASE, MODULES, facts)
