"""The model_3 cell's files and counts, on the CPU: its configuration is
``af2_infer_dap``'s with the extra stack's published sizes and nothing
reduced; the FLOPs of the extra-MSA stack are pinned to their hand count
(``work_xmsa.py``'s docstring); and the three readers the cell adds read
hand-built traces as worked out by hand."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fastbench import manifest, trace, work, work_xmsa  # noqa: E402
from fastbench.modes import fold_xmsa  # noqa: E402
from fastbench.readers import Context, mfu_xmsa, roofline_xmsa, \
    scope_path_s  # noqa: E402

CELL = "af_fold_model3_r256"
FOLD_NUMBERS = {"distogram_gap", "msa_logits_gap", "coords_gap",
                "window_compiles"}


@pytest.fixture(scope="module")
def cell():
    return manifest.cell(CELL)


def test_configuration_is_model_3_with_nothing_reduced(cell):
    trunk = manifest.cell("af_fold_r256").config
    cfg = cell.config
    assert set(trunk) <= set(cfg)
    for k, v in trunk.items():
        if k not in ("name", "source", "deployment", "num_extra_msa",
                     "extra_msa_stack_num_block", "max_templates",
                     "published", "reduced", "assumed"):
            assert cfg[k] == v, k
    assert cfg["reduced"] == []
    assert (cfg["num_extra_msa"], cfg["extra_msa_stack_num_block"],
            cfg["max_templates"]) == (5120, 4, 0)
    assert (cfg["extra_msa_channel"], cfg["extra_msa_heads"],
            cfg["extra_msa_head_dim"]) == (64, 8, 8)
    assert cell.traffic["n_extra_seq"] == cfg["num_extra_msa"]


def test_cell_checks_the_fold_numbers_through_its_mode(cell):
    assert cell.traffic["mode"] == "fold_xmsa"
    assert set(cell.check["limits"]) == FOLD_NUMBERS == set(fold_xmsa.NUMBERS)
    assert cell.check["limits"]["window_compiles"] == 0
    assert callable(fold_xmsa.run)


def test_new_metric_files_name_their_counts(cell):
    files = cell.metric_files
    assert files["mfu.fold_model3"]["params"]["flops"] in \
        work_xmsa.MODEL_FLOPS
    assert files["attn_roofline.fold_model3"]["params"]["calls"] in \
        work_xmsa.KERNEL_WORK
    assert files["tri_roofline.fold_model3"]["params"]["calls"] in \
        work_xmsa.KERNEL_WORK
    assert files["extra_msa_s.fold"]["params"] == {
        "scope": "alphafold.extra_msa_stack", "mode": "fold_xmsa"}


def test_extra_block_matches_hand_count(cell):
    # work_xmsa.py's docstring: ~1055.6 GFLOP a block at r 256, 5120 rows
    flops = work_xmsa.extra_block_flops(cell.config, 256, 5120)
    assert flops / 1e9 == pytest.approx(1055.6, abs=0.1)


def test_fold_adds_the_extra_stack_to_the_trunk(cell):
    d, sh = cell.config, cell.traffic
    trunk = work.fold_flops(d, sh)
    assert trunk / 1e12 == pytest.approx(113.92, abs=0.01)
    assert (work_xmsa.fold_model3_flops(d, sh) - trunk) / 1e12 == \
        pytest.approx(16.91, abs=0.01)
    # global column attention counts one query per column: full column
    # attention over the 5120 rows would cost more than the whole block
    full_col = work._attention(256, 5120, 64, 8, 8, 64, 1)
    assert full_col > work_xmsa.extra_block_flops(d, 256, 5120)


def test_attention_work_counts_the_extra_calls(cell):
    d, sh = cell.config, cell.traffic
    calls = work_xmsa.attention_fold_model3(d, sh)
    trunk = work.attention_fold(d, sh, 1)
    assert calls[:len(trunk)] == trunk
    (row_f, row_b, row_n), (tri_f, _, tri_n) = calls[len(trunk):]
    assert row_f == 4 * 5120 * 8 * 256 * 256 * 8       # QK^T and PV
    assert (row_n, tri_n) == (16, 32)                  # 4 blocks x 4 passes
    # unpadded q, k, v, out in bf16, the fp32 mask and the bias
    assert row_b == 4 * 5120 * 256 * 64 * 2 + 5120 * 256 * 4 \
        + 8 * 256 * 256 * 2


def test_triangle_work_counts_the_extra_calls(cell):
    d, sh = cell.config, cell.traffic
    calls = work_xmsa.triangle_fold_model3(d, sh)
    trunk = work.triangle_fold(d, sh, 1)
    assert calls[:len(trunk)] == trunk
    assert trunk[0][2] == 4 * 2 * 48
    # both updates of the 4 extra blocks in each of 4 passes, at the
    # trunk's pair shapes
    assert calls[len(trunk):] == [(*work.triangle_call(d, sh, 1), 32)]


# One device; a traced window of 100 ns from 1000 to 1100 holding two folds:
# two extra-stack ops (one cut by the window's start), a trunk op, and a
# reducer-free op outside every scope.
J = "jit(impl)/"
RAW = {0: [
    (990, 1010, J + "alphafold.extra_msa_stack/while/body/"
                    "evoformer.msa_row_attention/fusion"),
    (1010, 1040, J + "alphafold.extra_msa_stack/while/body/"
                     "evoformer.outer_product_mean/ops.opm/x"),
    (1040, 1060, J + "while/body/evoformer.msa_row_attention/fusion"),
    (1060, 1070, J + "alphafold.extra_msa_stack_other/fusion"),
    (1070, 1080, ""),
]}
HOST = [(1000, 1100, "bench.window")]


def _ctx(cell, units=2):
    t = trace.from_events({0: [(s, e, "op") for s, e, _ in RAW[0]]}, {},
                          HOST)
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    return Context(trace=t, units=units, chips=1, peak=peak,
                   config=cell.config, shapes=cell.traffic)


def test_extra_stack_seconds_from_the_kept_scopes(cell, monkeypatch):
    monkeypatch.setattr(fold_xmsa, "_traced_scopes", RAW)
    value, _ = scope_path_s.read(_ctx(cell), scope="alphafold.extra_msa_stack",
                                 mode="fold_xmsa")
    # 10 ns (clipped at the window) + 30 ns over 2 folds; the look-alike
    # scope and the trunk's op are out
    assert value == pytest.approx(20e-9)


def test_extra_stack_seconds_read_nothing_without_scopes(cell, monkeypatch):
    monkeypatch.setattr(fold_xmsa, "_traced_scopes", {})
    assert scope_path_s.read(_ctx(cell), scope="x", mode="fold_xmsa") is None
    monkeypatch.setattr(fold_xmsa, "_traced_scopes", RAW)
    assert scope_path_s.read(_ctx(cell), scope="alphafold.nothing",
                             mode="fold_xmsa") is None


def test_mfu_of_the_model3_fold(cell):
    value, _ = mfu_xmsa.read(_ctx(cell), flops="fold_model3")
    want = work_xmsa.fold_model3_flops(cell.config, cell.traffic) * 2 \
        / (100e-9 * 1e12)
    assert value == pytest.approx(100.0 * want)


def test_roofline_counts_the_extra_calls(cell):
    t = trace.from_events({0: [(1000, 1050, "flash_attention_pallas")]}, {},
                          HOST)
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    ctx = Context(trace=t, units=1, chips=1, peak=peak, config=cell.config,
                  shapes=cell.traffic)
    value, note = roofline_xmsa.read(ctx, kernels="^flash_attention_pallas$",
                                     calls="attention_fold_model3")
    least, bound = work.least_time(work_xmsa.attention_fold_model3(
        cell.config, cell.traffic), peak)
    assert value == pytest.approx(100.0 * least / 50e-9)
    assert note == f"bound: {bound}"
    assert roofline_xmsa.read(ctx, kernels="^nothing$",
                              calls="attention_fold_model3") is None
