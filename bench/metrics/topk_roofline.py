"""topk_roofline: the least time of the streaming top-k's work
(``work/topk.py``) over the device time of its kernel, in %.  The
kernel is the Pallas call inside the jitted ``predict_topk``, whose
module the trace names ``jit_predict_topk``."""

from bench import roofline

MODULES = ("jit_predict_topk",)


def read(facts):
    n = facts.traffic["queries_per_batch"]
    return roofline.kernel_share("topk", MODULES, facts.items / n, facts)
