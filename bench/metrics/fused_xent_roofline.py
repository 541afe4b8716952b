"""fused_xent_roofline: the least time of the fused projection +
cross-entropy's work (``work/fused_xent.py``) over the device time of
its kernels, in %.  The kernels are the Pallas calls (Mosaic custom
calls) inside the program's jitted train step, whose module the trace
names ``jit_step``; the program gives the kernels no names of their
own yet."""

from bench import roofline

MODULES = ("jit_step",)


def read(facts):
    n = facts.traffic["examples_per_step"]
    return roofline.kernel_share("fused_xent", MODULES, facts.items / n,
                                 facts)
