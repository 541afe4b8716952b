"""Program phase tags (``repro/kernels/phase.py``) on the CPU: every op
that the MACH head step and the decode calls write carries its phase,
and the tags survive XLA's compile.  The kernels' tags as the TPU
compiler keeps them are checked in ``test_tpu_compile.py``."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.extend import core as jcore

from repro.core import MACHConfig, MACHLinear, estimators
from repro.data.extreme import SparseBatch
from repro.kernels import ops, phase
from repro.optim import adamw
from repro.train.trainer import make_head_step

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))
import compiled_hlo  # noqa: E402
from compiled_hlo import normalize  # noqa: E402

N, D, R, B, K, NNZ = 16, 64, 2, 8, 100, 4


def _model():
    return MACHLinear(MACHConfig(num_classes=K, num_buckets=B,
                                 num_repetitions=R, hash_kind="mult_shift"),
                      D, fused=True)


def _inputs(features):
    s = jax.ShapeDtypeStruct
    x = (SparseBatch(s((N + 1,), jnp.int32), s((N * NNZ,), jnp.int32),
                     s((N * NNZ,), jnp.float32), num_features=D, nnz_max=NNZ)
         if features == "csr" else s((N, D), jnp.float32))
    return ({"w": s((D, R, B), jnp.float32), "b": s((R, B), jnp.float32)},
            x, s((N,), jnp.int32))


def _equations(jaxpr):
    """Every equation, inside nested jaxprs too (a kernel's body is not
    a device op of its own: a pallas_call counts once)."""
    for e in jaxpr.eqns:
        subs = [v for v in e.params.values()
                if isinstance(v, (jcore.ClosedJaxpr, jcore.Jaxpr))]
        if subs and e.primitive.name != "pallas_call":
            for sub in subs:
                yield from _equations(getattr(sub, "jaxpr", sub))
        else:
            yield e


def _phases(fn, *args):
    """phase -> primitive names of the equations ``fn`` traces to."""
    out = {}
    for e in _equations(jax.make_jaxpr(fn)(*args).jaxpr):
        tag = (e.ctx.xla_metadata or {}).get(phase.KEY, "")
        out.setdefault(tag, set()).add(e.primitive.name)
    return out


def _step_args(features):
    params, x, y = _inputs(features)
    opt = adamw(1e-3)
    return opt, (params, jax.eval_shape(opt.init, params), x, y)


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "kernel"])
@pytest.mark.parametrize("features", ["csr", "dense"])
def test_head_step_every_op_tagged(monkeypatch, features, kernel):
    """Loss forward, the custom VJP's backward (kernel path only: the
    jnp path differentiates the reference) and the optimizer update."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: kernel)
    opt, args = _step_args(features)
    got = _phases(make_head_step(_model().loss, opt), *args)
    assert "" not in got, got.get("")
    assert set(got) == ({phase.LOSS_FWD, phase.LOSS_BWD, phase.OPTIM}
                        if kernel else {phase.LOSS_FWD, phase.OPTIM})
    if kernel:
        assert "pallas_call" in got[phase.LOSS_FWD]
        assert "pallas_call" in got[phase.LOSS_BWD]


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "kernel"])
@pytest.mark.parametrize("features", ["csr", "dense"])
def test_decode_calls_every_op_tagged(monkeypatch, features, kernel):
    monkeypatch.setattr(ops, "_on_tpu", lambda: kernel)
    model = _model()
    params, x, _ = _inputs(features)
    assert set(_phases(model.meta_probs, params, x)) == {
        phase.DECODE_PROJECT}
    meta = jax.ShapeDtypeStruct((R, N, B), jnp.float32)
    got = _phases(lambda m, t: estimators.predict_topk(m, t, 5), meta,
                  model.cfg.table())
    assert set(got) == {phase.DECODE_TOPK}
    assert ("pallas_call" in got[phase.DECODE_TOPK]) == kernel


@pytest.mark.parametrize("features", ["csr", "dense"])
def test_tags_survive_cpu_compile(features):
    """XLA keeps the tags on the ops it compiles (the device trace reads
    them there); the CPU step has no custom VJP, so no ``loss.bwd``."""
    opt, args = _step_args(features)
    text = make_head_step(_model().loss, opt).lower(*args).compile() \
        .as_text()
    assert set(re.findall(r'mach_phase="([\w.]+)"', text)) == {
        phase.LOSS_FWD, phase.OPTIM}


HLO = """HloModule jit_f, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

FileNames
1 "/src/a.py"

ENTRY %main.3 (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %c.2 = f32[] constant(NUM)
  %b.3 = f32[8]{0} broadcast(%c.2), dimensions={}
  ROOT %NAME = f32[8]{0} multiply(%x.1, %b.3), ATTRS
}
"""


def test_normalize_keeps_only_the_program():
    """Frontend attributes (a kernel's multi-line metadata too), op
    metadata, source tables and names go; a changed op stays."""
    plain = HLO.replace("NUM", "2").replace("%NAME", "%multiply.4") \
        .replace(", ATTRS", "")
    tagged = HLO.replace("NUM", "2").replace("%NAME", "%step_mul.9") \
        .replace("ATTRS", 'frontend_attributes={kernel_metadata={\n'
                 '"mach_phase":"optim"\n},mach_phase="optim"}, '
                 'metadata={op_name="jit(f)/mul" stack_frame_id=3}') \
        .replace('"/src/a.py"', '"/src/b.py"')
    changed = plain.replace("constant(2)", "constant(3)")
    assert normalize(tagged) == normalize(plain)
    assert "mach_phase" not in normalize(tagged)
    assert normalize(changed) != normalize(plain)


@pytest.mark.parametrize("kinds, changed, want", [
    (("TPU v5 lite", "TPU v5 lite"), False, 0),
    (("TPU v5 lite", "TPU v5 lite"), True, 1),
    (("TPU v5 lite", "cpu"), False, 2),
    (("TPU v5 lite", None), False, 2),
    ((None, None), False, 2),
])
def test_diff_compares_one_device_kind(tmp_path, capsys, kinds, changed,
                                       want):
    """A changed program differs; dumps compiled for different devices,
    or for none named, are refused rather than called equal."""
    plain = HLO.replace("NUM", "2").replace("%NAME", "%m.4") \
        .replace(", ATTRS", "")
    for side, kind in zip("ab", kinds):
        (tmp_path / side).mkdir()
        head = f"{compiled_hlo.KIND}{kind}\n" if kind else ""
        text = plain.replace("(2)", "(3)") if changed and side == "b" \
            else plain
        (tmp_path / side / "c.jit_f.txt").write_text(head + text)
    assert compiled_hlo.diff(tmp_path / "a", tmp_path / "b") == want
    assert ("not compared" in capsys.readouterr().out) == (want == 2)


def test_dump_needs_a_tpu(tmp_path):
    """Off the chip the dispatch compiles its jnp branches, which hold
    no kernel: dump refuses instead of writing them."""
    with pytest.raises(SystemExit, match="needs a TPU"):
        compiled_hlo.dump(str(tmp_path), compiled_hlo.ROOT, [])
    assert not list(tmp_path.iterdir())
