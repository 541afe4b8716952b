"""The trace reduction on a trace the benchmark recorded on a TPU v5e
(``testdata/odp.train.xplane.pb``: a traced odp.train window, kept
small).  Reading it needs no chip and describes no TPU topology."""

import pathlib

import pytest

from bench import trace

TRACE = pathlib.Path(__file__).resolve().parent.parent / "testdata" \
    / "odp.train.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(str(TRACE))


def test_small_enough():
    assert TRACE.stat().st_size < 1 << 20


def test_window_and_busy(summary):
    assert summary.devices == 1
    assert 0 < summary.busy_s <= summary.window_s
    gaps = sum(ns for _, ns in summary.gaps)
    assert gaps + summary.busy_ns == pytest.approx(
        summary.window_ns[1] - summary.window_ns[0], rel=1e-9)


def test_busy_is_union_of_ops(summary):
    """Recomputed here by brute force over a 1 microsecond grid."""
    w0, w1 = summary.window_ns
    step = 1000.0
    cells = set()
    for op in summary.ops:
        s = int((op.start_ns if op.start_ns > w0 else w0) // step)
        e = int((max(op.start_ns, w0) + op.dur_ns) // step)
        cells.update(range(s, e))
    assert len(cells) * step == pytest.approx(summary.busy_ns, rel=0.01)


def test_kernels_found_by_module(summary):
    ns, count = summary.kernel_ns(("jit_step",))
    steps = sum(1 for o in summary.ops
                if o.module == "jit_step" and o.kernel) // 2
    assert count >= 2 and count == 2 * steps   # forward and backward
    assert 0 < ns <= summary.busy_ns
    assert summary.kernel_ns(("jit_no_such_program",)) == (0.0, 0)


def test_breakdown(summary):
    ops = summary.top_ops()
    assert 0 < len(ops) <= 10
    assert ops == sorted(ops, key=lambda o: -o[1])
    assert ops[0][0].startswith("jit_step:")
    gaps = summary.top_gaps()
    assert 0 < len(gaps) <= 10
    assert all(g[1] > 0 for g in gaps)


def test_union():
    assert trace._union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trace._union([]) == []


def test_unread_metric_ends_the_run(summary, monkeypatch):
    """A kernel renamed out of its reader's sight ends a traced run
    instead of leaving its metric out of the line."""
    import types

    from bench import run, spec
    cell = spec.resolve("odp.train")
    facts = types.SimpleNamespace(
        config=cell.config, traffic=cell.traffic, trace=summary,
        items=512 * 12, window_s=summary.window_s,
        peak=spec.load_json(spec.BENCH / "peaks.json")["TPU v5 lite"])
    assert "fused_xent_roofline" in run.per_layer(cell, facts)
    reader = spec.load_module("metrics", "fused_xent_roofline")
    monkeypatch.setattr(reader, "MODULES", ("jit_renamed_step",))
    with pytest.raises(SystemExit, match="fused_xent_roofline"):
        run.per_layer(cell, facts)
