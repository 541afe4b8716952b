"""Each cell's window and its check, end to end at a tiny size on the
CPU, through the program's own CPU path: a sound run is correct under
the cell's limits; the timed path broken underneath, or the reference
put in the program's place one precision lower (the control), is not.
The harness's look for a chip is skipped by calling ``run.execute``.
Every workload of ``BENCHMARK.json`` is run at the sizes of its
``tiny/<cell>.json``."""

import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from bench import calibrate, spec
from bench.run import execute

CELLS = sorted(w["name"] for w in
               spec.load_json(spec.ROOT / "BENCHMARK.json")["workloads"])
SEED = 2**33 + 7


def tiny(name):
    """The cell at the sizes of tiny/<cell>.json; a cell without that
    file fails here."""
    return spec.tiny(spec.resolve(name))


def run(cell):
    return execute(cell, SEED, 0.3, False, time.perf_counter())


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, highest_precision):
    out = run(tiny(name))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    cell = tiny(name)
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name, highest_precision):
    """The reference at bf16_3x in the program's place fails a limit."""
    cell = tiny(name)
    got = calibrate.control_numbers(cell, SEED)
    limits = cell.limits["numbers"]
    assert any(got[k] > v["limit"] for k, v in limits.items()), got


def _unchanged_step(make_head_step):
    def build(loss_fn, opt):
        step = make_head_step(loss_fn, opt)

        def frozen(params, state, x, y):
            _, _, loss = step(jax.tree.map(jnp.copy, params),
                              jax.tree.map(jnp.copy, state), x, y)
            return params, state, loss
        return frozen
    return build


def _half_batch(loss):
    from repro.data.extreme import SparseBatch

    def half(self, params, x, y, weights=None):
        n = y.shape[0] // 2
        if isinstance(x, SparseBatch):
            cut = n * x.nnz_max
            x = SparseBatch(x.indptr[:n + 1], x.indices[:cut],
                            x.values[:cut], x.num_features, x.nnz_max)
        else:
            x = x[:n]
        return loss(self, params, x, y[:n], weights)
    return half


@pytest.mark.parametrize("name", ["odp.train", "imagenet21k.train"])
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_broken_train_step_is_not_correct(name, fault, monkeypatch,
                                          highest_precision):
    from repro.core.mach import MACHLinear
    from repro.train import trainer
    if fault == "state_unchanged":
        monkeypatch.setattr(trainer, "make_head_step",
                            _unchanged_step(trainer.make_head_step))
    else:
        monkeypatch.setattr(MACHLinear, "loss", _half_batch(MACHLinear.loss))
    assert not run(tiny(name))["correct"]


@pytest.mark.parametrize("name", ["odp.decode", "imagenet21k.decode"])
def test_altered_answer_is_not_correct(name, monkeypatch, highest_precision):
    from repro.core import estimators
    topk = estimators.predict_topk

    def altered(meta, table, k, estimator="unbiased", **kw):
        vals, ids = topk(meta, table, k, estimator, **kw)
        return vals, ids.at[0, 0].set((ids[0, 0] + 1) % table.shape[-1])

    monkeypatch.setattr(estimators, "predict_topk", altered)
    assert not run(tiny(name))["correct"]


def test_run_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH, "run.py"), "--workload",
         "odp.train", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert "{" not in proc.stdout
