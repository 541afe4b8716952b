"""device_idle.decode: the share of the traced decode window in which
no operation ran on the device, in %."""

from bench import roofline


def read(facts):
    return roofline.idle(facts)
