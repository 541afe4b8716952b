"""The harness takes a cell added as new files and ``BENCHMARK.json``
entries alone.

A copy of ``bench/`` and ``BENCHMARK.json`` gains, from
``added_cell/``: a configuration cut to one chip's share (``reduced``,
``published``, ``deployment``), a new traffic kind with its driver
(which returns a program counter), program, reference and calibration
hooks, its tiny sizes, a per-layer metric that reads the counter and
one that reads a phase tag.  In the copy, on the CPU, calibrate's
reading functions and limit rule, ``run.execute`` (untraced, and traced
with the trace of a program older than the phase tags) and the rules of
``test_bench_spec.py`` all take it, and no file the copy had is
edited."""

import hashlib
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from bench import spec

HERE = pathlib.Path(__file__).resolve().parent
ADDED = HERE / "added_cell"
UNTAGGED = spec.BENCH / "testdata" / "odp.train.xplane.pb"
LISTS = ("configs", "workloads", "end_to_end", "per_layer")


def _digests(root: pathlib.Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def _call(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONDONTWRITEBYTECODE="1",
               PYTHONPATH=os.pathsep.join([str(cwd), str(spec.ROOT / "src")]))
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The copy with the toy cell added, its digests from before, and
    the toy run's result."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(spec.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", root)
    before = _digests(root)
    for path in ADDED.rglob("*"):
        rel = path.relative_to(ADDED)
        if path.is_file() and len(rel.parts) > 1:
            dest = root / "bench" / rel
            assert not dest.exists(), f"{rel} would edit a file"
            dest.parent.mkdir(exist_ok=True)
            shutil.copy(path, dest)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entries = json.loads((ADDED / "entries.json").read_text())
    for key in LISTS:
        bench[key] += entries[key]
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    proc = _call([str(ADDED / "drive.py"), str(UNTAGGED)], root)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return root, before, json.loads(proc.stdout.strip().splitlines()[-1])


def test_calibrated_by_its_driver(copy):
    root, _, got = copy
    numbers = got["limits"]["numbers"]
    assert set(numbers) == {"out_gap"}
    assert numbers["out_gap"]["upper_from"] == "control"
    assert (root / "bench" / "limits" / "toy.rows.readings.json").is_file()


def test_runs_correct_with_its_metrics(copy):
    _, _, got = copy
    plain, traced = got["plain"], got["traced"]
    assert plain["correct"] and traced["correct"], (plain, traced)
    assert set(plain["metrics"]) == {"toy_rows_per_s", "setup_s"}
    assert plain["checks"]["out_gap"]["limit"] == \
        got["limits"]["numbers"]["out_gap"]["limit"]


def test_counter_read_and_untagged_phase_left_out(copy):
    """The counter reaches its reader through ``facts.counters``; the
    phase reader finds no tag in the recorded trace and is left out."""
    _, _, got = copy
    traced = got["traced"]
    assert set(traced["metrics"]) == {"toy_held_share"}
    share = traced["metrics"]["toy_held_share"]
    assert share["unit"] == "%" and 0 < share["value"] < 100


def test_spec_rules_take_it(copy):
    root, _, _ = copy
    proc = _call(["-m", "pytest", "-v", "-p", "no:cacheprovider",
                  "-p", "no:randomly", "bench/tests/test_bench_spec.py"],
                 root)
    assert proc.returncode == 0, proc.stdout[-4000:]
    for case in ("test_config_file[toy]", "test_cell_resolves[toy.rows]",
                 "test_limits_follow_from_readings[toy.rows]"):
        assert f"{case} PASSED" in proc.stdout, case


def test_no_existing_file_edited(copy):
    root, before, _ = copy
    after = _digests(root)
    edited = [p for p, d in before.items()
              if p != "BENCHMARK.json" and after.get(p) != d]
    assert not edited
    old = spec.load_json(spec.ROOT / "BENCHMARK.json")
    new = json.loads((root / "BENCHMARK.json").read_text())
    assert {k: v for k, v in new.items() if k not in LISTS} == \
        {k: v for k, v in old.items() if k not in LISTS}
    for key in LISTS:
        assert new[key][:len(old[key])] == old[key]
