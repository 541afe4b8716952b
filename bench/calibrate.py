#!/usr/bin/env python3
"""The readings that the limits of `correct` are set from, and the rule
that sets them (not run by a benchmark run).

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,... \
        --control-seeds 1,2,3 [--seconds 2]     # on a TPU: take readings
    python3 bench/calibrate.py --workload <cell> --add-runs OUT...
    python3 bench/calibrate.py --workload <cell> --limits

Readings, in one process at the cell's own sizes, through the hooks of
the cell's driver module (``drivers/<kind>.py``):

* program: for each seed, the cell's set-up (and, where the driver's
  ``CHECK_READS_WINDOW`` says the check compares a window's output, a
  short window at the cell's load) and the check, exactly as a run
  makes them;
* control: the driver's ``readings``: the plain reference put in the
  program's place and computed one precision below the configuration's
  (``bf16_3x``, three bfloat16 passes, for float32 at ``highest``),
  compared with the reference;
* half_batch (where the driver's ``half_batch_rows`` gives rows): the
  reference taking its loss over the first half of each batch only,
  compared with the reference.

They are added to ``limits/<cell>.readings.json``, as are, with
``--add-runs``, the numbers of benchmark runs (files whose last line is
a run's result).  ``--limits`` sets ``limits/<cell>.json`` from that
file alone:

* lower: the largest reading of sound program runs;
* upper: the least of the control's smallest reading, where it is at
  least 3x lower; the half-batch fault's smallest, where at least 10x
  lower; and, for the change numbers, 1 (a state left unchanged reads
  1 by their definition and needs no run), where at least 3x lower;
* limit: lower^(1/3) * upper^(2/3), more room above the lower, or
  upper / 10 where lower is 0.  A number with no upper is not compared.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import spec  # noqa: E402


def driver_module(cell):
    return spec.load_module("drivers", cell.kind)


def control_numbers(cell, seed: int, rows: int | None = None,
                    mode: str = "bf16_3x") -> dict:
    """The reference at ``mode`` (and over ``rows`` rows of each batch)
    in the program's place, compared with the reference."""
    return driver_module(cell).readings(cell, seed, mode, rows)


def program_numbers(cell, seed: int, seconds: float) -> dict:
    mod = driver_module(cell)
    driver = mod.Driver(cell, seed)
    driver.setup()
    if mod.CHECK_READS_WINDOW:
        driver.window(seconds)
    driver.release()
    return driver.check()


def readings_path(cell: str):
    return spec.BENCH / "limits" / f"{cell}.readings.json"


def load_readings(cell: str) -> dict:
    path = readings_path(cell)
    if path.is_file():
        return spec.load_json(path)
    return {"cell": cell, "program": {}, "control": {}, "half_batch": {}}


def save_readings(readings: dict) -> None:
    with open(readings_path(readings["cell"]), "w") as f:
        json.dump(readings, f, indent=1)
        f.write("\n")


def run_numbers(path: str) -> dict:
    """The numbers compared in a benchmark run's result (its last
    line)."""
    with open(path) as f:
        return json.loads(f.read().strip().splitlines()[-1])["numbers"]


def set_limits(readings: dict) -> dict:
    """The limits of one cell from its readings (the rule above)."""
    program = list(readings["program"].values())
    control = list(readings["control"].values())
    half = list(readings["half_batch"].values())
    numbers = {}
    for name in control[0]:
        lower = max(v[name] for v in program)
        uppers = []
        least = min(v[name] for v in control)
        if least >= 3 * lower:
            uppers.append(("control", least))
        if half:
            least = min(v[name] for v in half)
            if least >= 10 * lower:
                uppers.append(("half batch", least))
            if name.startswith("change_") and 1.0 >= 3 * lower:
                uppers.append(("state unchanged", 1.0))
        if not uppers:
            print(f"{name}: no upper reading (lower {lower!r}); not "
                  f"compared", file=sys.stderr)
            continue
        src, upper = min(uppers, key=lambda u: u[1])
        limit = upper / 10 if lower == 0 else \
            lower ** (1 / 3) * upper ** (2 / 3)
        numbers[name] = {"limit": float(f"{limit:.3g}"), "lower": lower,
                         "upper": upper, "upper_from": src,
                         "program_runs": len(program),
                         "control_seeds": len(control)}
    return {"numbers": numbers,
            "how": f"set by bench/calibrate.py --limits from "
                   f"limits/{readings['cell']}.readings.json"}


def measure(cell, seeds, control_seeds, seconds: float) -> dict:
    """Readings on the accelerator, added to the cell's readings file."""
    import jax
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("calibrate: needs a TPU")
    from bench.run import configure_jax
    configure_jax(cell.config)
    readings = load_readings(cell.name)
    readings["device"] = jax.devices()[0].device_kind
    for s in seeds:
        got = program_numbers(cell, s, seconds)
        readings["program"][f"calibrate {s}"] = got
        print(f"program {s} {got}", file=sys.stderr, flush=True)
        gc.collect()
    half = driver_module(cell).half_batch_rows(cell)
    for s in control_seeds:
        got = control_numbers(cell, s)
        readings["control"][str(s)] = got
        print(f"control {s} {got}", file=sys.stderr, flush=True)
        if half is not None:
            got = control_numbers(cell, s, half, "highest")
            readings["half_batch"][str(s)] = got
            print(f"half_batch {s} {got}", file=sys.stderr, flush=True)
        gc.collect()
    return readings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="",
                    help="comma-separated program seeds")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--add-runs", nargs="*", default=[],
                    help="result files of benchmark runs")
    ap.add_argument("--limits", action="store_true",
                    help="set limits/<cell>.json from the readings")
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    seeds = [int(x) for x in args.seeds.split(",") if x]
    control_seeds = [int(x) for x in args.control_seeds.split(",") if x]
    t0 = time.perf_counter()
    if seeds or control_seeds:
        save_readings(measure(cell, seeds, control_seeds, args.seconds))
    if args.add_runs:
        readings = load_readings(cell.name)
        for path in args.add_runs:
            readings["program"][f"run {pathlib.Path(path).stem}"] = \
                run_numbers(path)
        save_readings(readings)
    if args.limits:
        out = set_limits(load_readings(cell.name))
        with open(spec.BENCH / "limits" / f"{cell.name}.json", "w") as f:
            json.dump(out, f, indent=1)
            f.write("\n")
        print(json.dumps(out["numbers"]))
    print(f"calibrate: {time.perf_counter() - t0:.1f} s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
