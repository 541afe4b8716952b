"""topk_ms: device time of the streaming top-k, in ms per batch: the ops
tagged ``decode.topk`` inside the jitted ``predict_topk``.  A program
without phase tags leaves the metric out."""

from bench import roofline

PHASE = "decode.topk"
MODULES = ("jit_predict_topk",)


def read(facts):
    return roofline.phase_ms(PHASE, MODULES, facts)
