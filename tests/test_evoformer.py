"""Evoformer structural + mathematical tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.evoformer import (
    EvoformerConfig,
    evoformer_block,
    evoformer_stack,
    init_evoformer_block,
    init_evoformer_stack,
    outer_product_mean,
)
from repro.core.dist import LocalDist
from repro.layers.norms import layer_norm
from repro.layers.params import dense


CFG = EvoformerConfig(d_msa=32, d_pair=16, msa_heads=4, pair_heads=2,
                      head_dim=8, opm_dim=8, tri_mult_dim=16, n_blocks=2)


@pytest.fixture
def inputs():
    B, s, r = 2, 6, 10
    msa = jax.random.normal(jax.random.PRNGKey(1), (B, s, r, CFG.d_msa))
    pair = jax.random.normal(jax.random.PRNGKey(2), (B, r, r, CFG.d_pair))
    return (msa, pair, jnp.ones((B, s, r)), jnp.ones((B, r)),
            jnp.ones((B, r, r)))


def test_block_shapes_no_nan(inputs):
    params = init_evoformer_block(jax.random.PRNGKey(0), CFG)
    msa, pair = evoformer_block(params, *inputs, cfg=CFG)
    assert msa.shape == inputs[0].shape and pair.shape == inputs[1].shape
    assert not bool(jnp.isnan(msa).any() or jnp.isnan(pair).any())


def test_stack_grads_finite(inputs):
    params = init_evoformer_stack(jax.random.PRNGKey(0), CFG)

    def loss(p):
        m, z = evoformer_stack(p, *inputs, cfg=CFG, remat=True)
        return jnp.sum(m ** 2) + jnp.sum(z ** 2)

    g = jax.grad(loss)(params)
    total = sum(float(jnp.sum(jnp.abs(x))) for x in jax.tree.leaves(g))
    assert np.isfinite(total) and total > 0


def test_remat_matches_no_remat(inputs):
    params = init_evoformer_stack(jax.random.PRNGKey(0), CFG)
    m1, z1 = evoformer_stack(params, *inputs, cfg=CFG, remat=True)
    m2, z2 = evoformer_stack(params, *inputs, cfg=CFG, remat=False)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(z1), np.asarray(z2), atol=1e-6)


def test_opm_matches_direct_einsum(inputs):
    """Outer-product-mean vs its textbook definition einsum(bsid,bsje->bijde)."""
    params = init_evoformer_block(jax.random.PRNGKey(0), CFG)["opm"]
    msa, _, msa_mask, _, _ = inputs
    got = outer_product_mean(params, msa, msa_mask, LocalDist(), CFG)

    m_n = layer_norm(params["ln"], msa)
    ab = dense(params["proj"], m_n)
    a, b = jnp.split(ab, 2, axis=-1)
    o = jnp.einsum("bsid,bsje->bijde", a, b) / msa.shape[1]
    want = dense(params["out"],
                 o.reshape(o.shape[:3] + (-1,)) * (msa.shape[1] /
                                                   (msa.shape[1] + 1e-3)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-3)


def test_msa_row_permutation_equivariance(inputs):
    """Permuting MSA rows (non-target) permutes the MSA output identically
    and leaves the pair output unchanged — a core Evoformer symmetry."""
    params = init_evoformer_block(jax.random.PRNGKey(0), CFG)
    msa, pair, msa_mask, seq_mask, pair_mask = inputs
    perm = jnp.array([3, 0, 5, 1, 4, 2])
    m1, z1 = evoformer_block(params, msa, pair, msa_mask, seq_mask, pair_mask,
                             cfg=CFG)
    m2, z2 = evoformer_block(params, msa[:, perm], pair, msa_mask, seq_mask,
                             pair_mask, cfg=CFG)
    np.testing.assert_allclose(np.asarray(m1[:, perm]), np.asarray(m2),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(z1), np.asarray(z2), atol=2e-5)


def test_inference_chunking_equivalent(inputs):
    """Paper §V.C chunking technique must be numerically identical."""
    import dataclasses
    params = init_evoformer_block(jax.random.PRNGKey(0), CFG)
    m1, z1 = evoformer_block(params, *inputs, cfg=CFG)
    cfg_c = dataclasses.replace(CFG, inference_chunk=3)
    m2, z2 = evoformer_block(params, *inputs, cfg=cfg_c)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), atol=2e-5)
    np.testing.assert_allclose(np.asarray(z1), np.asarray(z2), atol=2e-5)


def test_masked_positions_do_not_leak(inputs):
    """Changing MSA content at masked-out sequence positions must not change
    outputs at valid positions."""
    params = init_evoformer_block(jax.random.PRNGKey(0), CFG)
    msa, pair, msa_mask, seq_mask, pair_mask = inputs
    seq_mask = seq_mask.at[:, -2:].set(0.0)
    pair_mask = seq_mask[:, :, None] * seq_mask[:, None, :]
    m1, z1 = evoformer_block(params, msa, pair, msa_mask, seq_mask, pair_mask,
                             cfg=CFG)
    msa2 = msa.at[:, :, -2:, :].add(100.0)
    m2, z2 = evoformer_block(params, msa2, pair, msa_mask, seq_mask,
                             pair_mask, cfg=CFG)
    np.testing.assert_allclose(np.asarray(m1[:, :, :-2]),
                               np.asarray(m2[:, :, :-2]), atol=2e-4)


@pytest.mark.parametrize("qkv_bias", [False, True])
def test_project_heads_matches_merged_projection(qkv_bias):
    """The Evoformer's per-output QKV GEMMs give the merged projection's q,
    k and v (``layers/attention.project_qkv``), with or without a bias."""
    from repro.core.evoformer import _project_heads
    from repro.layers.attention import AttnDims, init_attention, project_qkv

    dims = AttnDims(4, 4, 8)
    p = init_attention(jax.random.PRNGKey(0), 32, 4, 4, 8, gating=True,
                       qkv_bias=qkv_bias)
    if qkv_bias:
        p["wqkv"]["b"] = jax.random.normal(jax.random.PRNGKey(2),
                                           p["wqkv"]["b"].shape)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 10, 32))
    want = project_qkv(p, x, dims, compute_dtype=x.dtype)
    got = _project_heads(p, x, dims)
    for g, w in zip(got, want):
        assert g.shape == w.shape == (2, 3, 10, 4, 8)
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6,
                                   atol=1e-6)
