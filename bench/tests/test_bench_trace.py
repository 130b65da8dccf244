"""Trace reduction on a hand-built trace, against values worked out by hand.
Runs on the CPU; touches no device."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fastbench import trace  # noqa: E402
from fastbench.readers import Context, exposed_comm, idle_share, roofline  # noqa: E402

# Two devices, a window of 100 ns from 1000 to 1100.
#   device 0: a while loop [1000,1045) holding fusion [1000,1010), flash fwd
#             [1010,1030), flash bwd [1025,1040) (overlaps the fwd by 5);
#             all-to-all [1050,1070), copy
#             [1060,1080) overlapping the collective by 10, an op cut by the
#             window's end [1090,1120).
#   device 1: all-gather-start..done async [1000,1040), compute [1000,1020),
#             fused_triangle [1030,1050).
OPS = {
    0: [(990, 1000, "before"), (1000, 1045, "while"),
        (1000, 1010, "fusion"),
        (1010, 1030, "flash_attention_pallas"),
        (1025, 1040, "flash_attention_bwd_pallas"),
        (1050, 1070, "all-to-all"), (1060, 1080, "copy"),
        (1090, 1120, "fusion")],
    1: [(1000, 1020, "convolution"), (1030, 1050, "fused_triangle_pallas")],
}
ASYNC = {1: [(1000, 1040, "all-gather-start")]}
HOST = [(1000, 1100, "bench.window"), (1000, 1045, "bench.dispatch"),
        (1080, 1100, "bench.fetch")]


@pytest.fixture
def t():
    return trace.from_events(OPS, ASYNC, HOST)


def test_op_name():
    assert trace.op_name("%layer_norm_pallas.384 = f32[1,128]{1,0} "
                         "custom-call(f32[1] %x)") == "layer_norm_pallas"
    assert trace.op_name("%all-gather-start.2 = (f32[2]) all-gather-start("
                         ")") == "all-gather-start"
    assert trace.op_name("fusion.12") == "fusion"


def test_window_and_busy(t):
    assert t.window_s == pytest.approx(100e-9)
    # device 0, without the while loop that only holds other ops:
    # [1000,1040) + [1050,1080) + [1090,1100) = 40 + 30 + 10
    assert trace.busy_s(t, 0) == pytest.approx(80e-9)
    # device 1: [1000,1020) + [1030,1050) = 40
    assert trace.busy_s(t, 1) == pytest.approx(40e-9)
    assert trace.mean_busy_s(t) == pytest.approx(60e-9)


def test_kernel_time_by_pattern(t):
    assert trace.kernel_s(t, "^flash_attention_(bwd_)?pallas$") == \
        pytest.approx(35e-9)
    assert trace.kernel_s(t, "^flash_attention_pallas$") == \
        pytest.approx(20e-9)
    assert trace.kernel_s(t, "^fused_triangle_pallas$") == \
        pytest.approx(20e-9)
    assert trace.kernel_s(t, "^nothing$") == 0.0


def test_exposed_collective_time(t):
    # device 0: the all-to-all [1050,1070) is covered by copy from 1060:
    # 10 exposed. device 1: all-gather [1000,1040) minus compute [1000,1020)
    # and [1030,1040): 10 exposed.
    assert trace.exposed_collective_s(t, 0) == pytest.approx(10e-9)
    assert trace.exposed_collective_s(t, 1) == pytest.approx(10e-9)


def test_idle_gaps_are_named_by_host_span(t):
    gaps = trace.idle_gaps(t)
    # device 0 gaps: [1040,1050) in dispatch, [1080,1090) in fetch
    assert sorted(gaps) == sorted([["bench.dispatch", pytest.approx(10e-9)],
                                   ["bench.fetch", pytest.approx(10e-9)]])


def test_top_ops(t):
    top = dict(trace.top_ops(t))
    assert top["fusion"] == pytest.approx(20e-9)
    assert top["convolution"] == pytest.approx(20e-9)
    assert "before" not in top and "while" not in top


def _ctx(t, units=1):
    cfg = dict(d_msa=256, d_pair=128, msa_heads=8, pair_heads=4, head_dim=32,
               opm_dim=32, tri_mult_dim=128, transition_factor=4,
               n_blocks=48, n_recycle=3)
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    return Context(trace=t, units=units, chips=2, peak=peak, config=cfg,
                   shapes={"n_res": 256, "n_seq": 128, "batch": 1, "dap": 1})


def test_readers(t):
    ctx = _ctx(t)
    assert idle_share.read(ctx)[0] == pytest.approx(40.0)
    assert exposed_comm.read(ctx)[0] == pytest.approx(10e-6)  # ms per unit
    got = roofline.read(ctx, kernels="^flash_attention_pallas$",
                        work="attention_fold")
    assert got is not None and got[0] > 0
    assert roofline.read(ctx, kernels="^absent$",
                         work="attention_fold") is None


def test_window_span_is_required():
    with pytest.raises(ValueError):
        trace.from_events(OPS, ASYNC, [(0, 1, "bench.fetch")])


def test_recorded_host_trace_has_no_device(tmp_path):
    """A trace recorded here on the CPU has the bench span on the host
    plane but no TPU plane, and the reduction says so."""
    import glob

    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        jax.block_until_ready(jnp.ones(8) * 2)
    jax.profiler.stop_trace()
    files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert len(files) == 1
    with pytest.raises(ValueError, match="no TPU device plane"):
        trace.load(files[0])
