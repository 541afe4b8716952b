"""xent_fwd_ms: device time of the fused loss's forward, in ms per
training step: the ops tagged ``loss.fwd`` (the projection and per-head
log-softmax kernel, label hashing, the CSR entry layout) inside the
jitted train step, ``jit_step``.  A program without phase tags leaves
the metric out."""

from bench import roofline

PHASE = "loss.fwd"
MODULES = ("jit_step",)


def read(facts):
    return roofline.phase_ms(PHASE, MODULES, facts)
