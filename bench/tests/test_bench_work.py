"""Model FLOPs and kernel work from shapes, and the peaks table. CPU only."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fastbench import peaks, work  # noqa: E402

FULL = dict(d_msa=256, d_pair=128, msa_heads=8, pair_heads=4, head_dim=32,
            opm_dim=32, tri_mult_dim=128, transition_factor=4, n_blocks=48,
            c_s=384, ipa_heads=12, ipa_c_hidden=16, ipa_qk_points=4,
            ipa_v_points=8, structure_iterations=8, recycle_bins=15,
            n_recycle=3)
TABLE_I = {"n_res": 256, "n_seq": 128, "batch": 1}


def test_block_matches_hand_count():
    # work.py's docstring: ~216.1 GFLOP a block at r 256, s 128
    assert work.block_flops(FULL, 256, 128) / 1e9 == pytest.approx(216.1,
                                                                   abs=0.1)


def test_pass_matches_hand_count():
    # ~10.4 TFLOP a pass; a train step is n_recycle + 3 passes
    assert work.pass_flops(FULL, 256, 128) / 1e12 == pytest.approx(10.4,
                                                                   abs=0.05)
    assert work.train_step_flops(FULL, TABLE_I) == \
        6 * work.pass_flops(FULL, 256, 128)
    assert work.fold_flops(FULL, TABLE_I) == 4 * work.pass_flops(FULL, 256,
                                                                 128)


def test_attention_work_splits_evenly_under_dap():
    r512 = dict(TABLE_I, n_res=512)
    one = work.attention_fold(FULL, r512, 1)
    four = work.attention_fold(FULL, r512, 4)
    flops = lambda calls: sum(f * c for f, _, c in calls)  # noqa: E731
    assert flops(four) == pytest.approx(flops(one))
    # the pair bias is read whole on every device
    byts = lambda calls: sum(b * c for _, b, c in calls)  # noqa: E731
    assert byts(four) > byts(one)


def test_least_time_names_its_bound():
    pk = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
    assert work.least_time([(10.0, 1.0, 2)], pk) == (20.0, "compute")
    assert work.least_time([(1.0, 10.0, 1)], pk) == (10.0, "bytes")


def test_peaks_of_v5e():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
