"""Device time by program phase, and idle gaps on the device's clock,
from a profiler trace (``.xplane.pb``).

    python3 bench/phases.py TRACE.xplane.pb

prints one JSON object: busy and window time, the runs of each jitted
program, the device time of each phase per run of the jitted program
the phase ran in (one training step or one decode call), the clock
offset, and the largest idle gaps.  All of it is ``bench/trace.py``'s
reduction, which the phase metrics (``metrics/*_ms.py``) read too.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xplane")
    args = ap.parse_args(argv)
    s = trace.reduce(args.xplane)
    c = s.clock
    print(json.dumps({
        "window_s": s.window_s, "busy_s": s.busy_s, "runs": s.runs,
        "tagged": s.tagged, "phases": s.split(),
        "clock": {"offset_ms": c.offset_ns * 1e-6, "linked": c.linked,
                  "bracket_ms": [x * 1e-6 if math.isfinite(x) else None
                                 for x in (c.low_ns, c.high_ns)],
                  "pairs": c.pairs},
        "idle_gaps": s.top_gaps()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
