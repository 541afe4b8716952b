"""Run inside a copy of the harness to which the toy cell was added:
calibrate the toy cell through its driver's hooks, set its limits by
the rule, then run it through ``run.execute`` twice, untraced and with
the profiler's trace replaced by the recorded trace given as argument.
Prints the results as one JSON line."""

import json
import sys
import time
from unittest import mock

import jax

from bench import calibrate, run, spec, trace

CELL = "toy.rows"


def main(xplane: str) -> None:
    cell = spec.tiny(spec.resolve(CELL))
    readings = calibrate.load_readings(CELL)
    for s in (1, 2, 3):
        readings["program"][f"calibrate {s}"] = \
            calibrate.program_numbers(cell, s, 0.1)
        readings["control"][str(s)] = calibrate.control_numbers(cell, s)
    calibrate.save_readings(readings)
    limits = calibrate.set_limits(readings)
    with open(spec.BENCH / "limits" / f"{CELL}.json", "w") as f:
        json.dump(limits, f, indent=1)
    cell = spec.tiny(spec.resolve(CELL))
    plain = run.execute(cell, 2**40 + 5, 0.2, False, time.perf_counter())
    with mock.patch.object(jax.profiler, "start_trace"), \
            mock.patch.object(jax.profiler, "stop_trace"), \
            mock.patch.object(trace, "find_xplane", lambda _: xplane):
        traced = run.execute(cell, 2**40 + 6, 0.2, True, time.perf_counter())
    print(json.dumps(run.plain({"limits": limits, "plain": plain,
                                "traced": traced})))


if __name__ == "__main__":
    main(sys.argv[1])
