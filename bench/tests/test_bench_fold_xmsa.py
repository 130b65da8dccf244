"""The AlphaFold-2 model_3 cell ``af_fold_model3_r256`` at a size a test run
holds, on the CPU, through its mode ``fold_xmsa``: the program's fold with
the extra-MSA stack against ``reference_xmsa`` on the reference's seeded
weights comes out correct under the cell's own limits, and in float32 it
computes the reference's function to round-off; the fold with the
extra stack left out, and the float8 control, do not follow the reference
as the sound fold does. At this size (2 trunk blocks, 2 extra blocks) the
control's gaps need not reach the full-size limits, so the control is held
to the separation; the fault, which drops a whole stack, to the limits."""
import dataclasses
import time

import jax
import numpy as np
import pytest

from tiny_cells import SEED, tiny

from fastbench import check, data, program_xmsa, reference, runtime
from fastbench.modes import RunContext, fold_xmsa

TINY_EXTRA = dict(extra_msa_channel=16, extra_msa_heads=2,
                  extra_msa_head_dim=8, extra_msa_stack_num_block=2,
                  num_extra_msa=24)


def tiny_model3():
    c = tiny("af_fold_model3_r256")
    return dataclasses.replace(
        c, config=dict(c.config, **TINY_EXTRA),
        traffic=dict(c.traffic, n_extra_seq=24, real_extra_seq=[18, 23]))


def run(cell, system=fold_xmsa.program_system, trace=False):
    ctx = RunContext(seed=SEED, seconds=0.3, trace=trace, cell=cell,
                     devices=jax.devices()[:1], t0=time.perf_counter(),
                     counter=runtime.CompileCounter())
    out = fold_xmsa.run(ctx, system=system)
    ok, checks = check.judge(out.numbers, cell.check["limits"])
    return ok and out.failed == 0, checks


@pytest.fixture(scope="module")
def cell():
    return tiny_model3()


@pytest.fixture(scope="module")
def sound(cell):
    return run(cell)


def gaps(checks):
    return {k: c["value"] for k, c in checks.items()
            if k != "window_compiles"}


def test_sound_fold_follows_the_reference(sound):
    ok, checks = sound
    assert ok, checks
    assert all(v < 0.05 for v in gaps(checks).values()), checks


def test_program_in_float32_computes_the_reference(cell):
    """With every dtype at float32 the program and the reference compute
    one function: the gaps are round-off."""
    from repro.exec.session import FastFold

    f32 = jax.numpy.float32
    af = program_xmsa.alphafold_config(cell.config)
    af = dataclasses.replace(
        af, compute_dtype=f32,
        evoformer=dataclasses.replace(af.evoformer, compute_dtype=f32),
        extra_msa=dataclasses.replace(af.extra_msa, compute_dtype=f32))
    dims = fold_xmsa.reference_xmsa.XDims.from_config(cell.config)
    feed = fold_xmsa.with_extra_msa(SEED, cell.traffic,
                                    data.feed(SEED, cell.traffic))
    with jax.default_matmul_precision("highest"):
        params = fold_xmsa.reference_xmsa.init_params(
            jax.random.PRNGKey(7), dims)
        got = FastFold(af).forward(params, feed[0])
        want = fold_xmsa.reference_xmsa.forward(params, feed[0], dims)
    gaps = check.fold_numbers(jax.device_get(got), jax.device_get(want),
                              feed[0])
    assert all(v < 1e-5 for v in gaps.values()), gaps


def test_extra_stack_left_out_is_not_correct(cell, sound):
    ok, checks = run(cell, fold_xmsa.extra_left_out)
    assert not ok, checks
    assert any(v >= 3 * gaps(sound[1])[k] for k, v in gaps(checks).items())


def test_float8_control_separates_from_the_program(cell, sound):
    dims = fold_xmsa.reference_xmsa.XDims.from_config(cell.config)
    wkey = jax.random.PRNGKey(data.jax_seed(SEED, fold_xmsa.WEIGHT_SALT))
    feed = fold_xmsa.with_extra_msa(SEED, cell.traffic,
                                    data.feed(SEED, cell.traffic))
    dev = jax.devices()[0]
    ref = fold_xmsa.reference_folds(dims, wkey, feed, range(len(feed)), dev,
                                    reference.FP32)
    each = list(fold_xmsa.control_numbers(dims, wkey, feed, ref,
                                          dev).values())
    control = {k: min(n[k] for n in each) for k in each[0]}
    assert any(v >= 3 * gaps(sound[1])[k] for k, v in control.items()), \
        (control, sound)


def test_extra_rows_are_masked_and_carry_deletions(cell):
    mix = cell.traffic
    feed = fold_xmsa.with_extra_msa(SEED, mix, data.feed(SEED, mix))
    for b in feed:
        rows = b["extra_msa_mask"][0].max(axis=1)
        assert 18 <= rows.sum() <= 23 and rows[:18].all()
        np.testing.assert_array_equal(b["extra_msa_mask"][0].max(axis=0),
                                      b["seq_mask"][0])
        assert b["extra_has_deletion"].any()
        assert b["extra_msa"].shape == (1, 24, 32)
    # the extra rows leave the trunk's feed as data.feed draws it
    plain = data.feed(SEED, mix)
    np.testing.assert_array_equal(plain[0]["msa"], feed[0]["msa"])


def test_traffic_of_another_depth_is_refused(cell):
    bad = dataclasses.replace(cell, traffic=dict(cell.traffic,
                                                 n_extra_seq=16))
    with pytest.raises(ValueError, match="n_extra_seq"):
        run(bad)


def test_a_program_without_the_extra_stack_is_refused():
    @dataclasses.dataclass(frozen=True)
    class TrunkOnly:
        n_recycle: int = 3

    with pytest.raises(RuntimeError, match="no extra-MSA stack"):
        program_xmsa.require_extra_stack(TrunkOnly)
