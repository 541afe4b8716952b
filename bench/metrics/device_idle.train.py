"""device_idle.train: the share of the traced training window in which
no operation ran on the device, in %."""

from bench import roofline


def read(facts):
    return roofline.idle(facts)
