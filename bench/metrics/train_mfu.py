"""train_mfu: operations of the fused loss's forward and backward per
step (``work/fused_xent.py``, the model's own work) times steps per
second in the traced window, over the chip's bf16 peak, in %."""

from bench import roofline


def read(facts):
    n = facts.traffic["examples_per_step"]
    return roofline.mfu(roofline.work("fused_xent", facts)["flops"],
                        facts.items / n, facts)
