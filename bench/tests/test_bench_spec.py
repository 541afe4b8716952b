"""BENCHMARK.json keeps to its contract and every cell finds its files."""

import json
import re

import pytest

from bench import spec

SPEC = spec.load_json(spec.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_names_units_and_bounds():
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in SPEC[k]]
    assert all(NAME.match(n) for n in names)
    for kind in ("end_to_end", "per_layer"):
        assert len({m["name"] for m in SPEC[kind]}) == len(SPEC[kind])
        for m in SPEC[kind]:
            assert UNIT.match(m["unit"]) and m["better"] in ("lower",
                                                             "higher")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
    assert "setup_s" in e2e


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    c = spec.resolve(cell)
    assert c.chips in (1, 4)
    spec.load_module("drivers", c.kind).Driver
    spec.load_module("reference", c.config["reference"])
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert callable(spec.load_module("metrics", m["name"]).read)
        assert m["moves"] in names
    assert c.limits["numbers"], f"no limits for {cell}"


# a width may never be cut: a hidden, intermediate, latent, state or
# projection size, a head size, an expansion factor, experts per token
WIDTH = re.compile(r"(_dim$|_rank$|^dim$|^d_model$|hidden_size|"
                   r"intermediate|latent|state_size|d_state|proj|head_size|"
                   r"head_dim|expan|per_tok|top_?k)", re.IGNORECASE)


@pytest.mark.parametrize("config", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file(config):
    """A configuration cut to one chip's share (the model-configs guide,
    section 4) names each key it changed under "reduced" in
    BENCHMARK.json; its file holds each such key, its published value
    (which differs) under "published", and a "deployment" that says over
    how many chips each layer is divided, and how.  No width is cut."""
    data = spec.load_json(spec.ROOT / config["file"])
    assert data["name"] == config["name"]
    assert config["file"].startswith("bench/configs/")
    reduced = config["reduced"]
    assert len(reduced) == len(set(reduced)) <= 16
    published = data.get("published", {})
    for key in reduced:
        assert NAME.match(key) and not WIDTH.search(key), key
        assert key in data and key in published, key
        assert published[key] != data[key], key
    assert set(published) <= set(reduced)
    if reduced:
        assert isinstance(data["deployment"], str)
        assert re.search(r"\d+ chips", data["deployment"]), \
            "deployment: over how many chips each layer is divided"
    for key in ("num_classes", "dim", "num_buckets", "num_repetitions"):
        assert isinstance(data[key], int) and data[key] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_limits_follow_from_readings(cell):
    """limits/<cell>.json is what the rule in calibrate.py makes of the
    committed readings, so a later PR can set it again."""
    from bench import calibrate
    got = calibrate.set_limits(calibrate.load_readings(cell))
    assert got["numbers"] == spec.resolve(cell).limits["numbers"]
