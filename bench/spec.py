"""Find a cell's pieces by name.

``BENCHMARK.json`` names cells, configurations, traffic mixes and
metrics; each piece is a file of its own under ``bench/``:

    configs/<config>.json      sizes of one configuration (+ its source)
    traffic/<traffic>.json     parameters of one traffic mix; its "kind"
                               names the driver drivers/<kind>.py
    reference/<name>.py        the plain reference a configuration names
    metrics/<metric>.py        the reader of one per-layer metric
    work/<kernel>.py           operations and bytes of one kernel
    limits/<cell>.json         the limits of the comparison that decides
                               `correct` in one cell

A later cell, mix or metric is added by adding files and entries; no
file here has to change.
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
