"""toy_held_share: the share of the window's rows that the experts held
here computed, from the program's counter, in %."""

from bench.spec import ABSENT


def read(facts):
    held = facts.counters.get("toy_held_rows")
    if held is None:
        return ABSENT
    return 100.0 * held / facts.items
