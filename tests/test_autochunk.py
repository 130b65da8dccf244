"""AutoChunk planner: budget safety, no-chunk-when-it-fits, knob pinning,
and the forward-level wiring."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.alphafold import FULL, SMOKE
from repro.exec.plan import ExecutionPlan, KernelPolicy, use_plan
from repro.launch.mesh import HBM_BYTES
from repro.memory.autochunk import (
    ChunkPlan,
    apply_plan,
    attention_transient_bytes,
    evoformer_peak_bytes,
    plan_decoder_blocks,
    plan_evoformer_chunks,
    resolve_evoformer_config,
)

EVO = SMOKE.evoformer


def _total(cfg, **kw):
    return sum(evoformer_peak_bytes(cfg, **kw).values())


def test_no_chunk_when_unchunked_fits():
    plan = plan_evoformer_chunks(EVO, batch=1, n_seq=8, n_res=96,
                                 budget_bytes=HBM_BYTES)
    assert plan == ChunkPlan(0, 0, 0, plan.est_bytes, HBM_BYTES, True)
    assert plan.est_bytes <= HBM_BYTES


@pytest.mark.parametrize("frac", [0.9, 0.5, 0.25, 0.1])
def test_never_exceeds_budget_when_feasible(frac):
    """Across shrinking budgets, any plan returned with fits=True stays
    within the budget by construction."""
    base = plan_evoformer_chunks(EVO, batch=1, n_seq=16, n_res=128,
                                 budget_bytes=HBM_BYTES)
    budget = int(base.est_bytes * frac)
    plan = plan_evoformer_chunks(EVO, batch=1, n_seq=16, n_res=128,
                                 budget_bytes=budget)
    if plan.fits:
        assert plan.est_bytes <= budget
    else:
        # infeasible: the planner must have returned the minimal-memory plan,
        # and no candidate can beat the budget
        assert plan.est_bytes > budget


def test_infeasible_budget_flags_not_fits():
    plan = plan_evoformer_chunks(EVO, batch=1, n_seq=16, n_res=128,
                                 budget_bytes=1)
    assert not plan.fits and plan.est_bytes > 1


def test_tighter_budget_never_less_chunking():
    base = plan_evoformer_chunks(EVO, batch=1, n_seq=16, n_res=128,
                                 budget_bytes=HBM_BYTES)
    tight = plan_evoformer_chunks(EVO, batch=1, n_seq=16, n_res=128,
                                  budget_bytes=base.est_bytes // 2)
    assert tight.est_bytes <= base.est_bytes
    assert (tight.inference_chunk, tight.opm_chunk, tight.attn_kv_tile,
            tight.tri_k_tile, tight.opm_s_tile) != (0, 0, 0, 0, 0)


def test_dap_relieves_memory_pressure():
    """Paper Table V: the per-device plan relaxes as the DAP degree grows."""
    t1 = _total(FULL.evoformer, batch=1, n_seq=512, n_res=2048, dap=1)
    t8 = _total(FULL.evoformer, batch=1, n_seq=512, n_res=2048, dap=8)
    assert t8 < t1


def test_fused_attention_bytes_scale_with_kv_tile_not_r2():
    """Acceptance: fused-path attention transient scales with the KV tile;
    the materialized path scales with R^2."""
    kw = dict(dtype_bytes=4)
    f_1k = attention_transient_bytes(8, 4, 1024, 32, kv_tile=128, fused=True,
                                     **kw)
    f_2k = attention_transient_bytes(8, 4, 2048, 32, kv_tile=128, fused=True,
                                     **kw)
    m_1k = attention_transient_bytes(8, 4, 1024, 32, fused=False, **kw)
    m_2k = attention_transient_bytes(8, 4, 2048, 32, fused=False, **kw)
    assert f_2k / f_1k < 2.5          # ~linear in R at fixed tile
    assert m_2k / m_1k > 3.5          # ~quadratic in R
    # at Evoformer scale the fused transient is far below materialized
    assert f_1k * 4 < m_1k


def test_chunk_knobs_divide_their_extents():
    """Runtime chunking is a no-op for non-dividing chunks, so the planner
    must only hand out chunks that actually divide (regression: n_res=100 is
    not divisible by any power-of-two candidate, yet a plan once claimed
    fits=True on the strength of a no-op chunk)."""
    base = plan_evoformer_chunks(EVO, batch=1, n_seq=24, n_res=100,
                                 budget_bytes=HBM_BYTES)
    plan = plan_evoformer_chunks(EVO, batch=1, n_seq=24, n_res=100,
                                 budget_bytes=max(base.est_bytes // 2, 1))
    if plan.inference_chunk:
        assert 24 % plan.inference_chunk == 0 or \
            100 % plan.inference_chunk == 0
    if plan.opm_chunk:
        assert 100 % plan.opm_chunk == 0
    # the modeled estimate uses runtime-effective (divisibility-aware)
    # chunks, so fits=True really means the runtime stays within budget
    if plan.fits:
        assert plan.est_bytes <= max(base.est_bytes // 2, 1)


def test_hand_set_knobs_are_pinned():
    cfg = dataclasses.replace(EVO, inference_chunk=3)
    plan = plan_evoformer_chunks(cfg, batch=1, n_seq=16, n_res=64,
                                 budget_bytes=HBM_BYTES)
    assert plan.inference_chunk == 3
    out = apply_plan(cfg, ChunkPlan(8, 16, 128, 0, 0, True))
    assert out.inference_chunk == 3           # hand-set wins
    assert out.opm_chunk == 16 and out.attn_kv_tile == 128


def test_resolve_respects_auto_chunk_flag():
    cfg = dataclasses.replace(EVO, auto_chunk=False)
    assert resolve_evoformer_config(cfg, batch=1, n_seq=8, n_res=64) is cfg
    cfg2 = resolve_evoformer_config(EVO, batch=1, n_seq=8, n_res=64)
    assert (cfg2.inference_chunk, cfg2.opm_chunk) == (0, 0)  # fits -> off


def test_alphafold_forward_resolves_chunks():
    """End-to-end wiring: a tight hbm_budget through alphafold_forward makes
    the resolve branch pick a chunked plan, and the outputs stay identical to
    the free-budget run (chunking is a pure execution knob)."""
    from repro.core.alphafold import alphafold_forward, init_alphafold
    from repro.data import protein_batches

    params = init_alphafold(jax.random.PRNGKey(0), SMOKE)
    pb = next(protein_batches(batch=1, n_seq=8, n_res=24, seed=0))
    batch = {k: jnp.asarray(getattr(pb, k)) for k in
             ("msa", "msa_mask", "residue_index", "aatype", "seq_mask",
              "pseudo_beta", "bert_mask", "true_msa")}
    out_auto = alphafold_forward(params, batch, SMOKE, n_recycle=0)
    base = plan_evoformer_chunks(SMOKE.evoformer, batch=1, n_seq=8, n_res=24,
                                 budget_bytes=HBM_BYTES)
    tight = base.est_bytes // 2
    plan = plan_evoformer_chunks(SMOKE.evoformer, batch=1, n_seq=8, n_res=24,
                                 budget_bytes=tight)
    assert (plan.inference_chunk, plan.opm_chunk, plan.attn_kv_tile,
            plan.tri_k_tile, plan.opm_s_tile) != (0, 0, 0, 0, 0)
    # Same tight budget through the real forward-level resolve branch.
    out_chunk = alphafold_forward(params, batch, SMOKE, n_recycle=0,
                                  hbm_budget=tight)
    np.testing.assert_allclose(np.asarray(out_auto["coords"]),
                               np.asarray(out_chunk["coords"]), atol=2e-4)


# AlphaFold-2 model_3's extra-MSA block: 8 heads of 8 on a 64-wide MSA, the
# trunk's pair side, global column attention.
EXTRA_FULL = dataclasses.replace(FULL.evoformer, d_msa=64, msa_heads=8, n_blocks=4,
                                 global_column=True)


def test_extra_stack_counts_row_attention_at_the_staged_width():
    """The Pallas kernel stages q, k, v and its output at 128 lanes: at the
    extra stack's 8-wide heads the planner counts them 16 times over, at
    the trunk's 32-wide heads 4 times; the XLA leg stages them unpadded."""
    terms = evoformer_peak_bytes(EXTRA_FULL, batch=1, n_seq=5120, n_res=256,
                                 staged=True)
    assert terms["attention"] == attention_transient_bytes(5120, 8, 256, 128)
    assert terms["attention"] - attention_transient_bytes(5120, 8, 256, 8) \
        == 4 * 5120 * 256 * 8 * (128 - 8) * 2
    assert terms["msa_transition"] == 5120 * 256 * 4 * 64 * 2
    unstaged = evoformer_peak_bytes(EXTRA_FULL, batch=1, n_seq=5120,
                                    n_res=256)
    assert unstaged["attention"] == attention_transient_bytes(5120, 8, 256, 8)
    # the rule is the head width's, not the block variant's
    trunk = evoformer_peak_bytes(FULL.evoformer, batch=1, n_seq=512,
                                 n_res=256, staged=True)
    assert trunk["attention"] == attention_transient_bytes(512, 8, 256, 128)
    assert trunk["msa_transition"] == 512 * 256 * 4 * 256 * 2


def test_extra_stack_is_planned_apart_from_the_trunk():
    """At r 256 with model_3's 512 clusters and 5120 extra rows on the v5e
    budget and the chip's kernel leg, the trunk runs unchunked while the
    extra stack's row attention is chunked over rows, and the extra stack's
    planned peak fits."""
    with use_plan(ExecutionPlan(kernels=KernelPolicy(attention="pallas"))):
        trunk = resolve_evoformer_config(FULL.evoformer, batch=1, n_seq=512,
                                         n_res=256, budget_bytes=HBM_BYTES)
        extra = resolve_evoformer_config(EXTRA_FULL, batch=1, n_seq=5120,
                                         n_res=256, budget_bytes=HBM_BYTES)
    assert trunk.inference_chunk == 0
    assert 0 < extra.inference_chunk < 5120
    assert 5120 % extra.inference_chunk == 0
    plan = plan_evoformer_chunks(EXTRA_FULL, batch=1, n_seq=5120, n_res=256,
                                 budget_bytes=HBM_BYTES, staged=True)
    assert plan.fits and plan.est_bytes <= HBM_BYTES
    assert plan.inference_chunk == extra.inference_chunk
    # unchunked, the staging alone would not fit
    assert _total(EXTRA_FULL, batch=1, n_seq=5120, n_res=256,
                  staged=True) > HBM_BYTES


def test_alphafold_forward_resolves_the_extra_stack_on_its_own(monkeypatch):
    """alphafold_forward plans each stack at its own depth."""
    import repro.core.alphafold as af
    from repro.data import protein_batches

    seen = []
    real = af.resolve_evoformer_config

    def spy(cfg, **kw):
        seen.append((cfg.global_column, kw["n_seq"]))
        return real(cfg, **kw)

    monkeypatch.setattr(af, "resolve_evoformer_config", spy)
    extra = dataclasses.replace(SMOKE.evoformer, d_msa=16, msa_heads=2, n_blocks=1,
                                global_column=True)
    cfg = dataclasses.replace(SMOKE, extra_msa=extra)
    pb = next(protein_batches(batch=1, n_seq=8, n_res=16, seed=0,
                              n_extra_seq=24))
    batch = {k: jnp.asarray(getattr(pb, k)) for k in
             ("msa", "msa_mask", "residue_index", "aatype", "seq_mask",
              "extra_msa", "extra_msa_mask", "extra_has_deletion",
              "extra_deletion_value")}
    params = jax.eval_shape(lambda k: af.init_alphafold(k, cfg),
                            jax.random.PRNGKey(0))
    jax.eval_shape(lambda p, b: af.alphafold_forward(p, b, cfg, n_recycle=0),
                   params, batch)
    assert seen == [(False, 8), (True, 24)]


def test_decoder_plan_keeps_config_when_it_fits():
    from repro.configs import get_config

    cfg = get_config("qwen2-1.5b", reduced_variant=True)
    cfg2, plan = plan_decoder_blocks(cfg, n_slots=2, max_seq=64)
    assert plan.fits
    assert (cfg2.attn_q_block, cfg2.attn_kv_block) == \
        (cfg.attn_q_block, cfg.attn_kv_block)


def test_decoder_plan_shrinks_kv_first_under_pressure():
    from repro.configs import get_config

    cfg = get_config("qwen2-1.5b", reduced_variant=True)
    full, _ = plan_decoder_blocks(cfg, n_slots=2, max_seq=64)
    from repro.memory.autochunk import decoder_attention_bytes
    e_full = decoder_attention_bytes(cfg, n_slots=2, max_seq=64,
                                     q_block=cfg.attn_q_block,
                                     kv_block=cfg.attn_kv_block)
    cfg3, plan = plan_decoder_blocks(cfg, n_slots=2, max_seq=64,
                                     budget_bytes=e_full - 1)
    assert cfg3.attn_kv_block < cfg.attn_kv_block
    assert plan.est_bytes <= e_full - 1 or not plan.fits
