"""Fused triangle-multiplication + outer-product-mean kernels: forward and
gradient parity vs the materialized ref oracles across mask/tile/dtype
combos, leg equivalence (XLA scan vs interpret-mode Pallas), the
oracle-forcing envelope, and the evoformer-level A/B."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dist import LocalDist
from repro.core.evoformer import (
    EvoformerConfig,
    init_evoformer_block,
    outer_product_mean,
    triangle_mult_incoming,
    triangle_mult_outgoing,
)
from repro.exec.plan import preset, use_plan
from repro.kernels import ops, ref

ATOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}
# The fused OPM legs keep fp32 through the c²→d projection (the reassociated
# XLA contraction / the kernel's fp32 epilogue) while the materialized oracle
# rounds the normalized outer product to the compute dtype first — in bf16
# the A/B delta is the oracle's own rounding, so the OPM bound is wider.
OPM_ATOL = {jnp.float32: 2e-5, jnp.bfloat16: 5e-2}
# fp32 rounding bounds for the OPM checks against a float64 reference, in
# ulps of the checked array's largest magnitude: its outputs reach ~25 and
# its gradients ~1e4 (the (norm + 1e-3) divisor of an all-masked (i, j) pair
# scales its a/b cotangents by 1e3), so an absolute bound tight enough for
# O(1) values sits below one ulp there.
EPS32 = float(np.finfo(np.float32).eps)
OPM_FWD_ULPS = 8
OPM_GRAD_ULPS = 64


def _tri_inputs(dtype, mask_mode, B=2, I=5, J=7, K=6, C=16, D=12, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 10)
    a_lin = jax.random.normal(ks[0], (B, I, K, C), dtype)
    ga = jax.random.normal(ks[1], (B, I, K, C), dtype)
    if mask_mode == "ones":
        mask = jnp.ones((B, I, K), jnp.float32)
    elif mask_mode == "sparse":
        mask = jax.random.bernoulli(ks[2], 0.6, (B, I, K)).astype(jnp.float32)
    else:  # "zeros" — fully masked rows must stay finite
        mask = jnp.zeros((B, I, K), jnp.float32)
    b_full = jax.random.normal(ks[3], (B, J, K, C), dtype)
    gamma = jax.random.normal(ks[4], (C,))
    beta = jax.random.normal(ks[5], (C,))
    w_out = jax.random.normal(ks[6], (C, D))
    b_out = jax.random.normal(ks[7], (D,))
    g_lin = jax.random.normal(ks[8], (B, I, J, D), dtype)
    g_bias = jax.random.normal(ks[9], (D,))
    return (a_lin, ga, mask, b_full, gamma, beta, w_out, b_out, g_lin, g_bias)


def _opm_inputs(dtype, mask_mode, B=2, S=5, I=6, J=8, C=8, D=12, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    a = jax.random.normal(ks[0], (B, S, I, C), dtype)
    b = jax.random.normal(ks[1], (B, S, J, C), dtype)
    if mask_mode == "ones":
        ma = jnp.ones((B, S, I), jnp.float32)
        mb = jnp.ones((B, S, J), jnp.float32)
    elif mask_mode == "sparse":
        ma = jax.random.bernoulli(ks[2], 0.7, (B, S, I)).astype(jnp.float32)
        mb = jax.random.bernoulli(ks[3], 0.7, (B, S, J)).astype(jnp.float32)
    else:  # "zeros" — norm -> 0, the +1e-3 epsilon keeps it finite
        ma = jnp.zeros((B, S, I), jnp.float32)
        mb = jnp.zeros((B, S, J), jnp.float32)
    a = a * ma[..., None].astype(dtype)
    b = b * mb[..., None].astype(dtype)
    w = jax.random.normal(ks[4], (C * C, D))
    bias = jax.random.normal(ks[5], (D,))
    return (a, b, ma, mb, w, bias)


# ---------------------------------------------------------------------------
# forward parity: every mask mode x tile (incl. non-dividing) x dtype
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mask_mode", ["ones", "sparse", "zeros"])
@pytest.mark.parametrize("tile", [0, 3, 4, 16])
def test_triangle_fwd_parity(dtype, mask_mode, tile):
    args = _tri_inputs(dtype, mask_mode)
    got = ops.fused_triangle_mult(*args, tile=tile)
    want = ref.triangle_mult_ref(*args)
    assert not bool(jnp.isnan(got).any())
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=ATOL[dtype], rtol=1e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mask_mode", ["ones", "sparse", "zeros"])
@pytest.mark.parametrize("tile", [0, 3, 4, 16])
def test_opm_fwd_parity(dtype, mask_mode, tile):
    args = _opm_inputs(dtype, mask_mode)
    got = ops.fused_outer_product_mean(*args, tile=tile)
    want = ref.outer_product_mean_ref(*args)
    assert not bool(jnp.isnan(got).any())
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=OPM_ATOL[dtype], rtol=1e-2)


def test_triangle_tile_invariance():
    """The tile is a pure execution knob — results must not depend on it."""
    args = _tri_inputs(jnp.float32, "sparse", seed=3)
    outs = [ops.fused_triangle_mult(*args, tile=t) for t in (0, 2, 5, 7)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   atol=1e-6)


def _opm_sin_grads_f64(a, b, mask_a, mask_b, w, bias):
    """Float64 numpy OPM forward and the gradients of ``sum(sin(out))``
    for every input — the reference both fused and oracle legs round
    away from."""
    a, b, ma, mb, w, bias = (np.asarray(x, np.float64)
                             for x in (a, b, mask_a, mask_b, w, bias))
    bsz, _, i, c = a.shape
    j = b.shape[2]
    o = np.einsum("bsix,bsjy->bijxy", a, b).reshape(bsz, i, j, c * c)
    denom = np.einsum("bsi,bsj->bij", ma, mb) + 1e-3
    ov = o / denom[..., None]
    out = ov @ w + bias
    g = np.cos(out)
    dov = g @ w.T
    dden = -np.einsum("bijk,bijk->bij", ov, dov) / denom
    do = (dov / denom[..., None]).reshape(bsz, i, j, c, c)
    grads = (np.einsum("bijxy,bsjy->bsix", do, b),
             np.einsum("bijxy,bsix->bsjy", do, a),
             np.einsum("bij,bsj->bsi", dden, mb),
             np.einsum("bij,bsi->bsj", dden, ma),
             np.einsum("bijk,bijd->kd", ov, g),
             g.sum(axis=(0, 1, 2)))
    return out, grads


def _assert_within_ulps(got, want, ulps, what):
    scale = max(1.0, float(np.abs(want).max()))
    err = float(np.abs(np.asarray(got, np.float64) - want).max())
    assert err <= ulps * EPS32 * scale, (what, err, ulps * EPS32 * scale)


def test_opm_tile_invariance():
    """The tile is a pure execution knob: every tile lands within fp32
    rounding of the float64 result, and of each other. Not bit-identical —
    XLA blocks the j-slab dots differently per width, reordering the sums."""
    args = _opm_inputs(jnp.float32, "sparse", seed=3)
    want, _ = _opm_sin_grads_f64(*args)
    outs = [np.asarray(ops.fused_outer_product_mean(*args, tile=t))
            for t in (0, 2, 3, 8)]
    for t, o in zip((0, 2, 3, 8), outs):
        _assert_within_ulps(o, want, OPM_FWD_ULPS, f"tile {t}")
        _assert_within_ulps(o, outs[0], 2 * OPM_FWD_ULPS, f"tile {t} vs 0")


# ---------------------------------------------------------------------------
# gradient parity through the recompute custom_vjp
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mask_mode", ["ones", "sparse"])
@pytest.mark.parametrize("tile", [0, 3])
def test_triangle_grad_parity(mask_mode, tile):
    """jax.grad through the recompute custom_vjp (inputs + per-tile stats
    only) == autodiff of the materialized oracle, for every input."""
    args = _tri_inputs(jnp.float32, mask_mode, seed=5)
    n = len(args)

    def f1(*a):
        return jnp.sum(jnp.sin(ops.fused_triangle_mult(*a, tile=tile)))

    def f2(*a):
        return jnp.sum(jnp.sin(ref.triangle_mult_ref(*a)))

    g1 = jax.grad(f1, argnums=tuple(range(n)))(*args)
    g2 = jax.grad(f2, argnums=tuple(range(n)))(*args)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5,
                                   rtol=1e-3)


@pytest.mark.parametrize("mask_mode", ["ones", "sparse"])
@pytest.mark.parametrize("tile", [0, 3])
def test_opm_grad_parity(mask_mode, tile):
    """jax.grad through the recompute custom_vjp and autodiff of the
    materialized oracle both land within fp32 rounding of the float64
    gradient, for every input. The mask cotangents sum terms of ~1e2 into
    values near zero, so a bound relative to each element cannot hold."""
    args = _opm_inputs(jnp.float32, mask_mode, seed=5)
    n = len(args)

    def f1(*a):
        return jnp.sum(jnp.sin(ops.fused_outer_product_mean(*a, tile=tile)))

    def f2(*a):
        return jnp.sum(jnp.sin(ref.outer_product_mean_ref(*a)))

    _, want = _opm_sin_grads_f64(*args)
    g1 = jax.grad(f1, argnums=tuple(range(n)))(*args)
    g2 = jax.grad(f2, argnums=tuple(range(n)))(*args)
    for k, (a, b, w) in enumerate(zip(g1, g2, want)):
        _assert_within_ulps(a, w, OPM_GRAD_ULPS, f"fused d{k}")
        _assert_within_ulps(b, w, OPM_GRAD_ULPS, f"oracle d{k}")


def test_triangle_grad_parity_bf16():
    args = _tri_inputs(jnp.bfloat16, "sparse", seed=7)

    def loss(op):
        def f(a_lin, ga, b_full, g_lin):
            full = (a_lin, ga, args[2], b_full) + args[4:8] + (g_lin, args[9])
            return jnp.sum(op(*full).astype(jnp.float32) ** 2)
        return f

    g1 = jax.grad(loss(lambda *a: ops.fused_triangle_mult(*a, tile=3)),
                  argnums=(0, 1, 2, 3))(args[0], args[1], args[3], args[8])
    g2 = jax.grad(loss(ref.triangle_mult_ref),
                  argnums=(0, 1, 2, 3))(args[0], args[1], args[3], args[8])
    for a, b in zip(g1, g2):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        scale = max(1.0, float(np.abs(b).max()))
        assert float(np.abs(a - b).max()) <= 2e-2 * scale


# ---------------------------------------------------------------------------
# leg equivalence + envelopes
# ---------------------------------------------------------------------------


def test_triangle_xla_leg_matches_pallas_interpret(monkeypatch):
    """The XLA j-block scan (default off-TPU leg) and the Pallas kernel
    (REPRO_PALLAS_INTERPRET=1 validation leg) are the same computation."""
    args = _tri_inputs(jnp.float32, "sparse", seed=9)
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    y_xla = ops.fused_triangle_mult(*args, tile=4)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    y_pallas = ops.fused_triangle_mult(*args, tile=4)
    np.testing.assert_allclose(np.asarray(y_xla), np.asarray(y_pallas),
                               atol=2e-5)


def test_opm_xla_leg_matches_pallas_interpret(monkeypatch):
    args = _opm_inputs(jnp.float32, "sparse", seed=9)
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    y_xla = ops.fused_outer_product_mean(*args, tile=4)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    y_pallas = ops.fused_outer_product_mean(*args, tile=4)
    np.testing.assert_allclose(np.asarray(y_xla), np.asarray(y_pallas),
                               atol=2e-5)


@pytest.mark.parametrize("op", ["triangle", "opm"])
def test_pallas_multi_tile_parity(op):
    """Interpret-mode kernels at shapes spanning several tiles on every grid
    axis, with padding on each (triangle: 3 i x 2 j x 3 k tiles; OPM: 3 i x
    2 j x 3 s tiles) — the accumulator carry and edge tiles the small
    shapes above never reach."""
    with use_plan(preset("interpret")):
        if op == "triangle":
            args = _tri_inputs(jnp.float32, "sparse", B=1, I=40, J=200,
                               K=300, seed=4)
            got = ops.fused_triangle_mult(*args)
        else:
            args = _opm_inputs(jnp.float32, "sparse", B=1, S=300, I=20,
                               J=200, seed=4)
            got = ops.fused_outer_product_mean(*args)
    if op == "triangle":
        want = ref.triangle_mult_ref(*args)
    else:
        want = ref.outer_product_mean_ref(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=ATOL[jnp.float32], rtol=1e-2)


def test_triangle_oracle_forced_env(monkeypatch):
    """REPRO_FORCE_TRIANGLE_ORACLE=1 pins both ops to the jnp oracles (the
    ci.sh oracle leg) without touching the other kernels."""
    args = _tri_inputs(jnp.float32, "sparse")
    oargs = _opm_inputs(jnp.float32, "sparse")
    monkeypatch.setenv("REPRO_FORCE_TRIANGLE_ORACLE", "1")
    assert not ops.fused_triangle_supported(16, 12, jnp.float32)
    assert not ops.fused_opm_supported(8, 12, jnp.float32)
    y1 = ops.fused_triangle_mult(*args)
    y2 = ops.fused_outer_product_mean(*oargs)
    monkeypatch.delenv("REPRO_FORCE_TRIANGLE_ORACLE")
    np.testing.assert_allclose(np.asarray(y1),
                               np.asarray(ref.triangle_mult_ref(*args)),
                               atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(y2), np.asarray(ref.outer_product_mean_ref(*oargs)),
        atol=1e-6)


def test_kernels_disabled_falls_back_to_oracle():
    args = _tri_inputs(jnp.float32, "sparse")
    y_kern = ops.fused_triangle_mult(*args)
    with use_plan(preset("oracle")):
        y_ref = ops.fused_triangle_mult(*args)
    np.testing.assert_allclose(np.asarray(y_kern), np.asarray(y_ref),
                               atol=2e-5)


# ---------------------------------------------------------------------------
# evoformer-level A/B: fused pair-stack sites vs the materialized jnp path
# ---------------------------------------------------------------------------

CFG = EvoformerConfig(d_msa=32, d_pair=16, msa_heads=4, pair_heads=2,
                      head_dim=8, opm_dim=8, tri_mult_dim=16, n_blocks=2)


def _pair_inputs(seed=0):
    B, r = 2, 10
    pair = jax.random.normal(jax.random.PRNGKey(seed), (B, r, r, CFG.d_pair))
    seq_mask = jnp.ones((B, r)).at[:, -2:].set(0.0)
    pair_mask = seq_mask[:, :, None] * seq_mask[:, None, :]
    return pair, pair_mask


@pytest.mark.parametrize("site", ["outgoing", "incoming", "opm"])
def test_evoformer_pair_sites_fused_vs_materialized(site):
    """Each rewired pair-stack site: the fused path equals the materialized
    jnp path (REPRO_DISABLE_KERNELS A/B) on the same params/inputs."""
    params = init_evoformer_block(jax.random.PRNGKey(0), CFG)
    pair, pair_mask = _pair_inputs()
    dist = LocalDist()

    def run():
        if site == "outgoing":
            return triangle_mult_outgoing(params["tri_mult_out"], pair,
                                          pair_mask, dist, CFG)
        if site == "incoming":
            pair_t = pair.swapaxes(1, 2)
            return triangle_mult_incoming(params["tri_mult_in"], pair,
                                          pair_t, pair_mask.swapaxes(1, 2),
                                          dist, CFG)
        B, s, r = 2, 6, pair.shape[1]
        msa = jax.random.normal(jax.random.PRNGKey(3), (B, s, r, CFG.d_msa))
        msa_mask = jnp.ones((B, s, r)).at[:, :, -2:].set(0.0)
        return outer_product_mean(params["opm"], msa, msa_mask, dist, CFG)

    got = run()
    with use_plan(preset("oracle")):
        want = run()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=1e-4)


def test_evoformer_pair_sites_grad_parity():
    """Grad parity through the rewired triangle sites (fused custom_vjp vs
    the materialized autodiff path), including the transposed-coords output
    gate of the incoming update."""
    params = init_evoformer_block(jax.random.PRNGKey(0), CFG)
    pair, pair_mask = _pair_inputs(seed=1)
    dist = LocalDist()

    def loss(p, z):
        u1 = triangle_mult_outgoing(p["tri_mult_out"], z, pair_mask, dist,
                                    CFG)
        z = z + u1
        u2 = triangle_mult_incoming(p["tri_mult_in"], z, z.swapaxes(1, 2),
                                    pair_mask.swapaxes(1, 2), dist, CFG)
        return jnp.sum((z + u2) ** 2)

    g_fused = jax.grad(loss, argnums=(0, 1))(params, pair)
    with use_plan(preset("oracle")):
        g_ref = jax.grad(loss, argnums=(0, 1))(params, pair)
    for a, b in zip(jax.tree.leaves(g_fused), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5,
                                   rtol=1e-3)


def test_evoformer_tile_knobs_pure_execution(monkeypatch):
    """cfg.tri_k_tile / cfg.opm_s_tile are pure execution knobs through the
    evoformer sites."""
    params = init_evoformer_block(jax.random.PRNGKey(0), CFG)
    pair, pair_mask = _pair_inputs(seed=2)
    dist = LocalDist()
    cfg_t = dataclasses.replace(CFG, tri_k_tile=3, opm_s_tile=2)
    u0 = triangle_mult_outgoing(params["tri_mult_out"], pair, pair_mask,
                                dist, CFG)
    u1 = triangle_mult_outgoing(params["tri_mult_out"], pair, pair_mask,
                                dist, cfg_t)
    np.testing.assert_allclose(np.asarray(u0), np.asarray(u1), atol=1e-6)
