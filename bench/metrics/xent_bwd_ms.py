"""xent_bwd_ms: device time of the fused loss's backward, in ms per
training step: the ops tagged ``loss.bwd`` (the dW and dbias kernels,
with the W pad and layout copy they need) inside the jitted train step,
``jit_step``.  A program without phase tags leaves the metric out."""

from bench import roofline

PHASE = "loss.bwd"
MODULES = ("jit_step",)


def read(facts):
    return roofline.phase_ms(PHASE, MODULES, facts)
