"""Attribution of device time to the program's named scopes, on hand-built
traces, against values worked out by hand. Runs on the CPU; touches no
device."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fastbench import scopes, trace  # noqa: E402
from fastbench.readers import Context, scope_time  # noqa: E402

ROW = ["layer_norm", "bias_dropout_add", "bias_sigmoid_mul", "softmax"]
FIVE = {"attention": ["attention"], "rowwise": ROW, "triangle": ["triangle"],
        "opm": ["opm"], "other": None}
J = "jit(train_step)/"
# One device, a window of 100 ns from 1000 to 1100, two units traced:
#   a while loop [1000,1060) holding: the attention's pad [1000,1010), its
#   kernel [1010,1030), the layer norm's backward under transpose(jvp(..))
#   [1030,1040), a copy with no program scope [1040,1045), the optimizer
#   [1045,1060); then the OPM kernel [1070,1080) and a triangle op cut by
#   the window's end [1090,1120).
RAW = {0: [
    (990, 1000, J + "alphafold.embed/dot_general"),
    (1000, 1060, J + "jvp(evoformer.msa_row_attention)/while"),
    (1000, 1010, J + "jvp(evoformer.msa_row_attention)/ops.attention/"
                     "ops.attention/pad"),
    (1010, 1030, J + "evoformer.msa_row_attention/ops.attention/"
                     "jit(flash_attention_pallas)"),
    (1030, 1040, J + "transpose(jvp(evoformer.pair_transition))/"
                     "ops.layer_norm/mul"),
    (1040, 1045, ""),
    (1045, 1060, J + "train.optimizer/add"),
    (1070, 1080, J + "evoformer.outer_product_mean/ops.opm/"
                     "jit(fused_opm_pallas)"),
    (1090, 1120, J + "transpose(jvp(evoformer.triangle_mult_incoming/"
                     "ops.triangle))/while/body/dot_general"),
]}
OPS = {0: [(s, e, "op") for s, e, _ in RAW[0]]}
HOST = [(1000, 1100, "bench.window")]


@pytest.fixture
def t():
    t = trace.from_events(OPS, {}, HOST)
    t.scopes = scopes.clip(RAW, t.window)
    return t


def test_family_and_submodule_of_a_path():
    assert scopes.family(J + "evoformer.x/ops.attention/ops.attention/pad") \
        == "attention"
    # the innermost family wins; backward wrappers do not hide it
    assert scopes.family(J + "ops.triangle/ops.layer_norm/mul") == \
        "layer_norm"
    assert scopes.family(J + "transpose(jvp(ops.bias_dropout_add))/mul") \
        == "bias_dropout_add"
    assert scopes.family(J + "train.optimizer/add") is None
    assert scopes.family("") is None
    assert scopes.family(J + "jax.ops.segment_sum/add") is None
    assert scopes.submodule(J + "transpose(jvp(evoformer.pair_transition))/"
                            "ops.layer_norm/mul") == \
        "evoformer.pair_transition"
    assert scopes.submodule(J + "alphafold.loss/log") == "alphafold.loss"
    assert scopes.submodule(J + "ops.layer_norm/mul") is None
    assert scopes.has_scope(J + "structure.module/while")
    assert not scopes.has_scope(J + "while/body/add")


def test_clip_keeps_the_ops_of_the_trace(t):
    # the while container and the op before the window are out; the last
    # op is cut at the window's end
    assert [(s, e) for s, e, _ in t.scopes[0]] == \
        [(s, e) for s, e, _ in t.ops[0]]
    assert t.scopes[0][-1][:2] == (1090, 1100)


def test_seconds_by_family(t):
    assert scopes.seconds(t.scopes, ["attention"]) == pytest.approx(30e-9)
    assert scopes.seconds(t.scopes, ROW) == pytest.approx(10e-9)
    assert scopes.seconds(t.scopes, ["triangle"]) == pytest.approx(10e-9)
    assert scopes.seconds(t.scopes, ["opm"]) == pytest.approx(10e-9)
    # the copy (no scope) and the optimizer (a scope, no family)
    assert scopes.seconds(t.scopes, None) == pytest.approx(20e-9)


def test_readers_split_busy_time(t):
    ctx = Context(trace=t, units=2, chips=1, peak={}, config={}, shapes={})
    got = {k: scope_time.read(ctx, fams)[0] for k, fams in FIVE.items()}
    assert got == pytest.approx({"attention": 15e-9, "rowwise": 5e-9,
                                 "triangle": 5e-9, "opm": 5e-9,
                                 "other": 10e-9})
    assert sum(got.values()) * ctx.units == \
        pytest.approx(trace.mean_busy_s(t))


def test_seconds_average_over_devices():
    two = {0: [(0, 10, "ops.attention/x")],
           1: [(0, 30, "ops.attention/x"), (30, 40, "")]}
    assert scopes.seconds(two, ["attention"]) == pytest.approx(20e-9)
    assert scopes.seconds(two, None) == pytest.approx(5e-9)


def test_nothing_to_read_without_program_scopes(t):
    ctx = Context(trace=t, units=2, chips=1, peak={}, config={}, shapes={})
    t.scopes = {0: [(s, e, "jit(step)/while/body/add") for s, e, _ in
                    t.ops[0]]}
    assert scope_time.read(ctx, ["attention"]) is None
    assert scope_time.read(ctx, None) is None
    # a trace from before the scopes were kept has no such field
    bare = trace.from_events(OPS, {}, HOST)
    ctx = Context(trace=bare, units=2, chips=1, peak={}, config={},
                  shapes={})
    assert scope_time.read(ctx, None) is None


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000 }
    events { metadata_id: 2 offset_ps: 10000 duration_ps: 5000 }
    events { metadata_id: 3 offset_ps: 15000 duration_ps: 5000 }
  }
  lines { id: 2 name: "Async XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "%pad.1 = bf16[2]{0} pad()" } }
  event_metadata { key: 2 value { id: 2 name: "%copy.3 = f32[2]{0} copy()" } }
  event_metadata { key: 3 value {
    id: 3 name: "%layer_norm_pallas.7 = f32[2]{0} custom-call()" } }
}
planes { id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000 } }
  event_metadata { key: 1 value { id: 1 name: "%pad.1 = bf16[2]{0} pad()" } }
}
"""
# The compiled program's text: the copy carries no op_name.
HLO = """\
ENTRY %main.9 (p: bf16[1]) -> f32[2] {
  %pad.1 = bf16[2]{0} pad(bf16[1]{0} %p, bf16[] %z), padding=0_1, \
metadata={op_name="jit(s)/evoformer.a/ops.attention/pad" source_line=3}
  %copy.3 = f32[2]{0} copy(f32[2]{0} %q)
  ROOT %layer_norm_pallas.7 = f32[2]{0} custom-call(f32[2]{0} %copy.3), \
custom_call_target="tpu_custom_call", \
metadata={op_name="jit(s)/transpose(jvp(ops.layer_norm))/ln"}
}
"""


def test_op_names_of_a_compiled_program():
    assert scopes.op_names(HLO) == {
        "pad.1": "jit(s)/evoformer.a/ops.attention/pad",
        "layer_norm_pallas.7": "jit(s)/transpose(jvp(ops.layer_norm))/ln"}


def test_load_names_each_op_of_a_recorded_trace(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    names = scopes.op_names(HLO)
    assert scopes.load(str(path), names, [0]) == {0: [
        (1000, 1010, "jit(s)/evoformer.a/ops.attention/pad"),
        (1010, 1015, ""),
        (1015, 1020, "jit(s)/transpose(jvp(ops.layer_norm))/ln")]}
    assert set(scopes.load(str(path), names)) == {0, 1}
