"""Find a cell's pieces by name.

``BENCHMARK.json`` names cells, configurations, traffic mixes and
metrics; each piece is a file of its own under ``bench/``:

    configs/<config>.json      sizes of one configuration (+ its source;
                               a configuration cut to one chip's share
                               gives the published value of each key in
                               its BENCHMARK.json "reduced" list under
                               "published", and its "deployment")
    programs/<model>.py        the system under test, built from a
                               configuration whose "model" names it
    traffic/<traffic>.json     parameters of one traffic mix; its "kind"
                               names the driver drivers/<kind>.py
    drivers/<kind>.py          set-up, window and check of one kind, and
                               its calibration hooks (calibrate.py)
    reference/<name>.py        the plain reference a configuration names
    metrics/<metric>.py        the reader of one per-layer metric
    work/<kernel>.py           operations and bytes of one kernel
    limits/<cell>.json         the limits of the comparison that decides
                               `correct` in one cell
    tiny/<cell>.json           configuration and traffic overrides that
                               make one cell small enough for the CPU tests

A driver module holds ``Driver(cell, seed)`` with ``setup()``,
``window(seconds)``, ``release()`` and ``check()``.  ``window`` returns
"attempted", "failed", "items", "seconds", "longest_s", the end-to-end
"metrics", and may return "counters": {name: number}, the program's
counts summed over the window's steps, which the per-layer readers get
as ``facts.counters``.  Beside it: ``readings(cell, seed, mode, rows)``
(the reference at ``mode``, over ``rows`` rows of each batch, in the
program's place, compared with the reference), ``CHECK_READS_WINDOW``
(whether ``check`` compares what the window produced) and
``half_batch_rows(cell)`` (the rows of the half-batch fault, or None
where the kind has none).

A metric reader's ``read(facts)`` returns a number, a dict with "value",
``ABSENT`` where the run has nothing for it to read (a program older
than what it reads), which leaves the metric out of the line, or None
where it should have found something, which ends the run.

A later cell, mix, configuration or metric is added by adding files and
entries; no file here has to change.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
from typing import Any

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class _Absent:
    def __repr__(self) -> str:
        return "ABSENT"


ABSENT = _Absent()  # what a metric reader returns with nothing to read


def load_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """bench/<kind>/<name>.py as a module (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    mod_name = f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def applies(entry: dict, cell: str) -> bool:
    """A metric with no "workloads" key applies to every cell."""
    return "workloads" not in entry or cell in entry["workloads"]


@dataclasses.dataclass
class Cell:
    """One workload of BENCHMARK.json with its files read."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    limits: dict

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def resolve(name: str, bench_json: pathlib.Path = ROOT / "BENCHMARK.json"
            ) -> Cell:
    spec = load_json(bench_json)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {bench_json}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(ROOT / configs[w["config"]]["file"])
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    limits_path = BENCH / "limits" / f"{name}.json"
    limits = load_json(limits_path) if limits_path.is_file() else {}
    return Cell(
        name=name, chips=w["chips"], config=config, traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if applies(m, name)],
        limits=limits)


def tiny(cell: Cell) -> Cell:
    """The cell at the sizes of ``tiny/<cell>.json``, for runs on the
    CPU."""
    over = load_json(BENCH / "tiny" / f"{cell.name}.json")
    return dataclasses.replace(
        cell, config=dict(cell.config, **over["config"]),
        traffic=dict(cell.traffic, **over["traffic"]))
