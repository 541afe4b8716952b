#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is a workload of ``BENCHMARK.json``; its configuration, traffic
mix, driver, reference, metric readers and limits are files found by
name (``bench/spec.py``).  A run makes its weights and batches from the
seed on the device, warms every shape it will use (set-up), measures
for ``--seconds``, frees the program's state and then compares what the
timed path produced with the plain reference.

With ``--trace 0`` the result carries the cell's end-to-end metrics;
with ``--trace 1`` the window runs under the profiler and the result
carries the cell's per-layer metrics, the device's busy time and a
breakdown.  The last line of standard output is one JSON object; the
numbers compared, each beside its limit, are the last lines of standard
error and the last key of that object.

Exits non-zero and prints no result where JAX finds no TPU, fewer chips
than the cell asks for, a device missing from ``bench/peaks.json``, or
no program beside the benchmark.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import compare, spec  # noqa: E402


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(count: int) -> None:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < count:
        raise SystemExit(f"bench: the cell needs {count} chips, JAX found "
                         f"{len(devs)}")


def peak_of(kind: str) -> dict:
    peaks = spec.load_json(spec.BENCH / "peaks.json")
    if kind not in peaks:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return peaks[kind]


def configure_jax(config: dict) -> None:
    """Compile cache in the checkout (or where JAX_COMPILATION_CACHE_DIR
    says), every program cached, and the configuration's precision."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_default_matmul_precision",
                      config["matmul_precision"])


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    used = devs[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in used]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


def per_layer(cell, facts) -> dict:
    """Every per-layer metric of the cell; a reader returns a number or a
    dict with "value" and notes beside it.  One that returns
    ``spec.ABSENT`` (the program has nothing it reads, such as phase tags
    or a counter) is left out of the line.  A reader that finds nothing
    in a cell that lists its metric (a kernel or program renamed out of
    its sight) returns None and ends the run without a result."""
    out, missing = {}, []
    for m in cell.per_layer:
        got = spec.load_module("metrics", m["name"]).read(facts)
        if got is spec.ABSENT:
            continue
        if got is None:
            missing.append(m["name"])
            continue
        entry = dict(got) if isinstance(got, dict) else {"value": got}
        out[m["name"]] = {"value": entry.pop("value"), "unit": m["unit"],
                          **entry}
    if missing:
        raise SystemExit(f"bench: {cell.name}: nothing to read for "
                         f"{', '.join(missing)} in the trace")
    return out


def plain(x):
    """JSON has no inf or nan: such a number is written as a string."""
    if isinstance(x, dict):
        return {k: plain(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [plain(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return repr(x)
    return x


def execute(cell, seed: int, seconds: float, trace: bool, t0: float
            ) -> dict:
    """Set-up, window, check: the result object of one run, on whatever
    backend JAX has (the caller checks for the chip)."""
    import jax

    driver = spec.load_module("drivers", cell.kind).Driver(cell, seed)
    driver.setup()
    setup_s = time.perf_counter() - t0
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    res = driver.window(seconds)
    summary = None
    if trace:
        jax.profiler.stop_trace()
        from bench import trace as trace_mod
        summary = trace_mod.reduce(trace_mod.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    device = device_info(cell.chips)
    driver.release()
    t_check = time.perf_counter()
    numbers = driver.check()
    print(f"bench: setup {setup_s:.1f} s, window {res['seconds']:.1f} s "
          f"({res['attempted']} calls, longest {res['longest_s'] * 1e3:.1f}"
          f" ms), check {time.perf_counter() - t_check:.1f} s",
          file=sys.stderr)
    correct, checks = compare.judge(numbers, cell.limits.get("numbers", {}))
    correct = correct and res["failed"] == 0
    out = {"correct": bool(correct), "attempted": res["attempted"],
           "failed": res["failed"]}
    if trace:
        facts = types.SimpleNamespace(
            config=cell.config, traffic=cell.traffic, trace=summary,
            counters=res.get("counters", {}),
            items=res["items"], window_s=res["seconds"],
            peak=peak_of(device["kind"]) if device["platform"] == "tpu"
            else None)
        out["metrics"] = per_layer(cell, facts)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    else:
        measured = dict(res["metrics"], setup_s=setup_s)
        out["metrics"] = {m["name"]: {"value": measured[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
    out["device"] = device
    if trace:
        out["breakdown"] = {"device_ops": summary.top_ops(),
                            "idle_gaps": summary.top_gaps()}
    out["numbers"] = numbers
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    args = parse(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        raise SystemExit(f"bench: no program under {ROOT}/src/repro")
    cell = spec.resolve(args.workload)
    require_chips(cell.chips)
    import jax
    peak_of(jax.devices()[0].device_kind)
    configure_jax(cell.config)
    out = execute(cell, args.seed, args.seconds, bool(args.trace), T0)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    if not out["checks"]:
        print(f"check none: no limits for {cell.name}; numbers "
              f"{out['numbers']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(plain(out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
