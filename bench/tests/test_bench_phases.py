"""Device time by program phase and the shared clock (``bench/phases.py``)
on two traces recorded on a TPU v5e: ``testdata/odp.train.xplane.pb``,
of a program older than the phase tags, and
``testdata/imagenet21k.train.tagged.xplane.pb``, a short
``imagenet21k.train`` window of the tagged program.  The benchmark's
per-layer metrics read both as before."""

import pathlib
import types

import pytest

from bench import phases, run, spec, trace

DATA = pathlib.Path(__file__).resolve().parent.parent / "testdata"
UNTAGGED = DATA / "odp.train.xplane.pb"
TAGGED = DATA / "imagenet21k.train.tagged.xplane.pb"
TRAIN_PHASES = {"loss.fwd", "loss.bwd", "optim"}


@pytest.fixture(scope="module", params=[UNTAGGED, TAGGED],
                ids=["untagged", "tagged"])
def both(request):
    path = str(request.param)
    return request.param, trace.reduce(path), phases.reduce(path)


@pytest.fixture(scope="module")
def tagged():
    return phases.reduce(str(TAGGED))


def test_small_enough():
    assert TAGGED.stat().st_size < 1 << 20


@pytest.mark.parametrize("text, phase", [
    ('%f = f32[8]{0} fusion(%a), kind=kLoop, calls=%c, '
     'frontend_attributes={mach_phase="optim"}', "optim"),
    ('%k = (f32[8]) custom-call(%a), custom_call_target="tpu_custom_call", '
     'frontend_attributes={kernel_metadata={\n"mach_phase":"loss.bwd"\n},'
     'mach_phase="loss.fwd"}', "loss.bwd"),
    ('%k = custom-call(%a), frontend_attributes={kernel_metadata='
     '"{\\"mach_phase\\":\\"decode.topk\\"}"}', "decode.topk"),
    ('%k = custom-call(%a), custom_call_target="tpu_custom_call", '
     'frontend_attributes={kernel_metadata={}}', ""),
    ("%copy.8 = f32[8]{0} copy(%a)", ""),
])
def test_phase_of(text, phase):
    """XLA ops carry ``mach_phase="..."``; a kernel's own tag inside its
    ``kernel_metadata`` wins; no tag reads ""."""
    assert phases.phase_of(text) == phase


def test_window_busy_and_ops_as_trace_reduces(both):
    """Same window, same busy time, the same ops: the phases are only
    added to what ``bench/trace.py`` reads."""
    _, s, p = both
    assert p.window_ns == s.window_ns and p.busy_ns == s.busy_ns
    assert [o.dur_ns for o in p.ops] == [o.dur_ns for o in s.ops]
    assert [o.module for o in p.ops] == [o.module for o in s.ops]
    assert sum(ns for _, ns in p.gaps) == sum(ns for _, ns in s.gaps)


def test_phases_only_in_the_tagged_trace(both):
    path, _, p = both
    assert p.tagged == (path == TAGGED)
    if path == UNTAGGED:
        assert set(o.phase for o in p.ops) == {""}
        assert p.phase_ns("loss.fwd") == (0.0, 0)


def test_clock_offset_and_gap_names(both):
    """The runs linked by run_id bracket the host-minus-device offset;
    each gap is named from the host at its middle plus the offset."""
    path, s, p = both
    c = p.clock
    assert c.linked and c.pairs >= 3
    assert c.low_ns <= c.offset_ns <= c.high_ns
    if path == UNTAGGED:
        assert 1.59e6 <= c.low_ns and c.high_ns <= 2.02e6
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(str(path)).planes)
    _, host = trace._host_window(planes)
    mids = []
    w0, w1 = p.window_ns
    edges = [w0]
    for a, b in trace._union([(max(o.start_ns, w0),
                                max(o.start_ns, w0) + o.dur_ns)
                               for o in s.ops]):
        edges += [a, b]
    edges.append(w1)
    mids = [(a + b) / 2 for a, b in zip(edges[::2], edges[1::2]) if b > a]
    assert [n for n, _ in p.gaps] == trace._activities(
        host, [m + c.offset_ns for m in mids])


def test_clock_unlinked_is_zero():
    c = phases.clock([])
    assert (c.offset_ns, c.pairs, c.linked) == (0.0, 0, False)


def test_tagged_split(tagged):
    """Every op of the tagged step carries a training phase except XLA's
    own (layout copies); the split adds up to the ops' device time."""
    got = {o.phase for o in tagged.ops}
    assert TRAIN_PHASES <= got <= TRAIN_PHASES | {""}
    steps = tagged.runs["jit_step"]
    split = tagged.split()
    assert {k for k in split} <= TRAIN_PHASES | {"untagged"}
    assert all(v["calls"] == steps for v in split.values())
    total = sum(o.dur_ns for o in tagged.ops) * 1e-6 / steps
    assert sum(v["ms"] for v in split.values()) == pytest.approx(total)
    assert split["loss.bwd"]["ms"] > split["loss.fwd"]["ms"] > 0
    assert split["optim"]["ms"] > 0


def _facts(name, summary, steps):
    cell = spec.resolve(name)
    return cell, types.SimpleNamespace(
        config=cell.config, traffic=cell.traffic, trace=summary,
        items=cell.traffic["examples_per_step"] * steps,
        window_s=summary.window_s,
        peak=spec.load_json(spec.BENCH / "peaks.json")["TPU v5 lite"])


PINNED = {
    "odp.train": (UNTAGGED, 15, {
        "train_mfu": 0.00012641307692001758,
        "fused_xent_roofline": 0.25272585235260714,
        "device_idle.train": 0.039169922086734754}),
    "imagenet21k.train": (TAGGED, 11, {
        "train_mfu": 4.474635360944021,
        "fused_xent_roofline": 7.42377885763857,
        "device_idle.train": 0.5814479210388579}),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_metrics_read_as_before(name):
    """The benchmark's per-layer metrics read each trace as their
    readers did when it was recorded: the odp.train cell's traced runs
    of that program read 1.2644e-4 % and 0.25272 %, and the run that
    recorded the tagged trace printed the same roofline and idle share
    (its ``train_mfu`` divides by the host's window, not the
    trace's)."""
    path, steps, want = PINNED[name]
    s = trace.reduce(str(path))
    cell, facts = _facts(name, s, steps)
    got = run.per_layer(cell, facts)
    assert {k: v["value"] for k, v in got.items()} == want
