"""Device time by program phase and the shared clock (``bench/trace.py``,
printed by ``bench/phases.py``) on two traces recorded on a TPU v5e:
``testdata/odp.train.xplane.pb``, of a program older than the phase
tags, and ``testdata/imagenet21k.train.tagged.xplane.pb``, a short
``imagenet21k.train`` window of the tagged program.  The benchmark's
per-layer metrics read both as before, and the phase metrics read the
tagged one as ``bench/phases.py`` splits it."""

import hashlib
import json
import pathlib
import types

import pytest

from bench import phases, run, spec, trace

DATA = pathlib.Path(__file__).resolve().parent.parent / "testdata"
UNTAGGED = DATA / "odp.train.xplane.pb"
TAGGED = DATA / "imagenet21k.train.tagged.xplane.pb"
TRAIN_PHASES = {"loss.fwd", "loss.bwd", "optim"}
TRAIN_READERS = {"xent_fwd_ms": "loss.fwd", "xent_bwd_ms": "loss.bwd",
                 "optim_ms": "optim", "untagged_ms.train": "untagged"}
PHASE_READERS = [*TRAIN_READERS, "project_ms", "topk_ms",
                 "untagged_ms.decode"]


@pytest.fixture(scope="module", params=[UNTAGGED, TAGGED],
                ids=["untagged", "tagged"])
def both(request):
    return request.param, trace.reduce(str(request.param))


@pytest.fixture(scope="module")
def tagged():
    return trace.reduce(str(TAGGED))


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
    assert trace.phase_of(text) == phase


# (window_ns, busy_ns, ops, sha256 of the ops' (name, module, start, dur,
# kernel), idle ns) as trace.reduce read each trace before it read phases
BEFORE = {
    UNTAGGED: ((50602477.0, 11892852226.0), 11837611149.0, 748,
               "98c5300338dee66d01752d762f96c779"
               "5e95503a8ba6aa122a784f6d70bb7565", 4638600.0),
    TAGGED: ((44024640.0, 365597894.0), 319703473.0, 698,
             "67a28adc76fe5c0c1b5034d0f5d5f7a7"
             "46a7bf56eea7f452d6845118b7af9e0c", 1869781.0),
}


def test_window_busy_and_ops_as_trace_reduces(both):
    """Same window, same busy time, the same ops and idle time as before
    the phases were read: they are only added to what ``trace.reduce``
    reads."""
    path, s = both
    window, busy, count, digest, idle = BEFORE[path]
    assert s.window_ns == window and s.busy_ns == busy
    ops = [(o.name, o.module, o.start_ns, o.dur_ns, o.kernel) for o in s.ops]
    assert len(ops) == count
    assert hashlib.sha256(repr(ops).encode()).hexdigest() == digest
    assert sum(ns for _, ns in s.gaps) == idle


def test_phases_only_in_the_tagged_trace(both):
    path, s = both
    assert s.tagged == (path == TAGGED)
    if path == UNTAGGED:
        assert set(o.phase for o in s.ops) == {""}
        assert s.phase_ns("loss.fwd") == (0.0, 0)


def test_clock_offset_and_gap_names(both):
    """The runs linked by run_id bracket the host-minus-device offset;
    each gap is named from the host at its middle plus the offset."""
    path, s = both
    c = s.clock
    assert c.linked and c.pairs >= 3
    assert c.low_ns <= c.offset_ns <= c.high_ns
    if path == UNTAGGED:
        assert 1.59e6 <= c.low_ns and c.high_ns <= 2.02e6
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(str(path)).planes)
    _, host = trace._host_window(planes)
    w0, w1 = s.window_ns
    edges = [w0]
    for a, b in trace._union([(max(o.start_ns, w0),
                                max(o.start_ns, w0) + o.dur_ns)
                               for o in s.ops]):
        edges += [a, b]
    edges.append(w1)
    mids = [(a + b) / 2 for a, b in zip(edges[::2], edges[1::2]) if b > a]
    assert [n for n, _ in s.gaps] == trace._activities(
        host, [m + c.offset_ns for m in mids])


def test_clock_unlinked_is_zero():
    c = trace.clock([])
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


def test_cli_prints_the_split(capsys):
    """``bench/phases.py`` prints trace.reduce's split, clock and gaps."""
    assert phases.main([str(TAGGED)]) == 0
    got = json.loads(capsys.readouterr().out)
    assert got["tagged"] and got["runs"] == {"jit_step": 11}
    assert got["phases"]["loss.fwd"]["ms"] == 4.260748363636363
    assert got["clock"]["linked"] and got["idle_gaps"]


def _facts(name, summary, steps):
    cell = spec.resolve(name)
    return cell, types.SimpleNamespace(
        config=cell.config, traffic=cell.traffic, trace=summary,
        counters={}, items=cell.traffic["examples_per_step"] * steps,
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
        "device_idle.train": 0.5814479210388579,
        "xent_fwd_ms": 4.260748363636363,
        "xent_bwd_ms": 14.902709454545453,
        "optim_ms": 6.015846,
        "untagged_ms.train": 3.8846482727272726}),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_metrics_read_as_before(name):
    """The benchmark's per-layer metrics read each trace as their
    readers did when it was recorded: the odp.train cell's traced runs
    of that program read 1.2644e-4 % and 0.25272 %, and the run that
    recorded the tagged trace printed the same roofline and idle share
    (its ``train_mfu`` divides by the host's window, not the
    trace's).  The phase metrics read the tagged trace as
    ``bench/phases.py`` splits it, and are left out of the line on the
    untagged one."""
    path, steps, want = PINNED[name]
    s = trace.reduce(str(path))
    cell, facts = _facts(name, s, steps)
    got = run.per_layer(cell, facts)
    assert {k: v["value"] for k, v in got.items()} == want
    if path == TAGGED:
        split = s.split()
        for metric, phase in TRAIN_READERS.items():
            assert got[metric]["value"] == split[phase]["ms"]
            assert got[metric]["unit"] == "ms"


@pytest.mark.parametrize("metric", PHASE_READERS)
def test_phase_reader_absent_without_tags(metric):
    """On a program older than the tags every phase reader returns
    ABSENT, so ``run.per_layer`` leaves it out of the line."""
    facts = types.SimpleNamespace(trace=trace.reduce(str(UNTAGGED)))
    assert spec.load_module("metrics", metric).read(facts) is spec.ABSENT


def test_train_phases_sum_to_busy_per_step(tagged):
    facts = types.SimpleNamespace(trace=tagged)
    total = sum(spec.load_module("metrics", m).read(facts)
                for m in TRAIN_READERS)
    busy = tagged.busy_ns * 1e-6 / tagged.runs["jit_step"]
    assert total == pytest.approx(busy, rel=1e-6)


def _op(phase, module, dur_ns, start_ns=0.0):
    return trace.Op("op", module, start_ns, dur_ns, False, phase)


def test_decode_readers():
    """The three decode readers on a window of 4 decode calls: each
    phase's ops summed inside its program, over that program's runs."""
    meta, topk = "jit_meta_probs", "jit_predict_topk"
    ops = [_op("decode.project", meta, 5e6), _op("decode.project", meta,
                                                   3e6),
           _op("", meta, 2e6), _op("", meta, 2e6), _op("", topk, 4e5),
           _op("decode.topk", topk, 7e6), _op("decode.topk", topk, 1e6),
           _op("decode.project", "jit_other", 9e9)]
    s = trace.Summary((0.0, 1e9), 0.0, 1, ops, [], {meta: 4, topk: 4})
    facts = types.SimpleNamespace(trace=s)

    def read(metric):
        return spec.load_module("metrics", metric).read(facts)

    assert read("project_ms") == pytest.approx(8.0 / 4)
    assert read("topk_ms") == pytest.approx(8.0 / 4)
    assert read("untagged_ms.decode") == pytest.approx(4.4 / 4)
    assert read("xent_fwd_ms") is None       # its program never ran


def test_tagged_phase_without_ops_reads_zero():
    """A tagged program whose step ran with no op of a phase reads 0 ms
    for it; one whose program never ran ends the run."""
    s = trace.Summary((0.0, 1e9), 0.0, 1, [_op("loss.fwd", "jit_step", 5e6)],
                      [], {"jit_step": 2})
    assert s.phase_ms("optim", ("jit_step",)) == 0.0
    assert s.phase_ms("loss.fwd", ("jit_step",)) == pytest.approx(2.5)
    assert s.phase_ms("loss.fwd", ("jit_renamed",)) is None
