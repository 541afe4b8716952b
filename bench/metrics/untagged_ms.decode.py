"""untagged_ms.decode: device time of the decode's ops that carry no
phase tag, in ms per batch, inside the jitted ``meta_probs`` and
``predict_topk``: ops XLA made itself, in ODP the densify's scatter-add
rewritten into one whose ops lose the tag.  A program without phase
tags leaves the metric out."""

from bench import roofline

PHASE = ""
MODULES = ("jit_meta_probs", "jit_predict_topk")


def read(facts):
    return roofline.phase_ms(PHASE, MODULES, facts)
