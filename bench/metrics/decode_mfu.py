"""decode_mfu: operations of the decode per batch (the projection,
``work/mach_projection.py``, and the estimator's adds,
``work/topk.py``) times batches per second in the traced window, over
the chip's bf16 peak, in %."""

from bench import roofline


def read(facts):
    n = facts.traffic["queries_per_batch"]
    flops = (roofline.work("mach_projection", facts)["flops"]
             + roofline.work("topk", facts)["flops"])
    return roofline.mfu(flops, facts.items / n, facts)
