"""Fused flash-attention kernel: forward + gradient parity vs the
scores-materialized oracle, in both 4D and 5D forms, through a full
evoformer_block, and across dist modes."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dist import GspmdDist, LocalDist
from repro.core.evoformer import (
    EvoformerConfig,
    evoformer_block,
    init_evoformer_block,
)
from repro.exec.plan import current_plan, preset, use_plan
from repro.kernels import ops, ref

ATOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


def _mk(n, sq, skv, h, d, dtype, with_bias, with_mask, bias_b=1, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (n, sq, h, d), dtype)
    k = jax.random.normal(ks[1], (n, skv, h, d), dtype)
    v = jax.random.normal(ks[2], (n, skv, h, d), dtype)
    bias = (jax.random.normal(ks[3], (bias_b, h, sq, skv), dtype)
            if with_bias else None)
    mask = None
    if with_mask:
        mask = jnp.where(jax.random.bernoulli(ks[4], 0.85, (n, skv)), 0.0,
                         -1e9).astype(jnp.float32)
    return q, k, v, bias, mask


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("with_bias,with_mask", [
    (True, True), (True, False), (False, True), (False, False),
])
def test_fused_attention_fwd_4d(dtype, with_bias, with_mask):
    n, sq, skv, h, d = 4, 33, 33, 2, 16
    q, k, v, bias, mask = _mk(n, sq, skv, h, d, dtype, with_bias, with_mask,
                              bias_b=2)
    scale = 1.0 / (d ** 0.5)
    got = ops.fused_attention(q, k, v, bias=bias, mask=mask, scale=scale)
    want, _ = ref.attention_ref(q, k, v, bias, mask, scale)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=ATOL[dtype], rtol=1e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_attention_fwd_5d(dtype):
    b, g, s, h, d = 2, 5, 12, 3, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    q = jax.random.normal(ks[0], (b, g, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, g, s, h, d), dtype)
    v = jax.random.normal(ks[2], (b, g, s, h, d), dtype)
    bias = jax.random.normal(ks[3], (b, h, s, s), dtype)
    mask = jnp.where(jax.random.bernoulli(ks[4], 0.8, (b, g, s)), 0.0,
                     -1e9).astype(jnp.float32)
    got = ops.fused_attention(q, k, v, bias=bias, mask=mask)
    assert got.shape == q.shape
    want, _ = ref.attention_ref(
        q.reshape(b * g, s, h, d), k.reshape(b * g, s, h, d),
        v.reshape(b * g, s, h, d), bias, mask.reshape(b * g, s),
        1.0 / (d ** 0.5))
    np.testing.assert_allclose(
        np.asarray(got, np.float32).reshape(b * g, s, h, d),
        np.asarray(want, np.float32), atol=ATOL[dtype], rtol=1e-2)


@pytest.mark.parametrize("with_bias,with_mask", [(True, True), (False, False)])
def test_fused_attention_grad_parity(with_bias, with_mask):
    """jax.grad through the custom recompute VJP == autodiff of the oracle."""
    n, sq, skv, h, d = 3, 17, 23, 2, 8
    q, k, v, bias, mask = _mk(n, sq, skv, h, d, jnp.float32, with_bias,
                              with_mask, bias_b=3, seed=2)
    scale = 0.5
    args = [a for a in (q, k, v, bias, mask) if a is not None]
    nargs = len(args)

    def f1(*a):
        b_ = a[3] if with_bias else None
        m_ = a[-1] if with_mask else None
        return jnp.sum(jnp.sin(ops.fused_attention(
            a[0], a[1], a[2], bias=b_, mask=m_, scale=scale)))

    def f2(*a):
        b_ = a[3] if with_bias else None
        m_ = a[-1] if with_mask else None
        return jnp.sum(jnp.sin(ref.attention_ref(
            a[0], a[1], a[2], b_, m_, scale)[0]))

    g1 = jax.grad(f1, argnums=tuple(range(nargs)))(*args)
    g2 = jax.grad(f2, argnums=tuple(range(nargs)))(*args)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_fused_attention_kv_tile_invariance():
    """The KV tile is a pure execution knob — results must not depend on it."""
    q, k, v, bias, mask = _mk(2, 40, 40, 2, 16, jnp.float32, True, True)
    outs = [ops.fused_attention(q, k, v, bias=bias, mask=mask, kv_tile=t)
            for t in (0, 128, 256)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   atol=1e-6)


def test_fused_attention_xla_leg_matches_pallas_interpret(monkeypatch):
    """The XLA-native online-softmax forward (default off-TPU leg) and the
    Pallas kernel (REPRO_PALLAS_INTERPRET=1 validation leg) are the same
    computation."""
    q, k, v, bias, mask = _mk(3, 21, 29, 2, 16, jnp.float32, True, True,
                              bias_b=3, seed=5)
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    y_xla = ops.fused_attention(q, k, v, bias=bias, mask=mask, kv_tile=16)
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    y_pallas = ops.fused_attention(q, k, v, bias=bias, mask=mask, kv_tile=16)
    np.testing.assert_allclose(np.asarray(y_xla), np.asarray(y_pallas),
                               atol=1e-6)


@pytest.mark.parametrize("with_bias,with_mask", [
    (True, True), (True, False), (False, True), (False, False),
])
def test_fused_pallas_backward_matches_ref(monkeypatch, with_bias, with_mask):
    """flash_attention_bwd_pallas (interpret mode) == autodiff of the
    scores-materialized oracle, for every bias/mask combination — including
    the bias-group (rep > 1) reduction sweep."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    n, sq, skv, h, d = 4, 19, 27, 2, 8
    q, k, v, bias, mask = _mk(n, sq, skv, h, d, jnp.float32, with_bias,
                              with_mask, bias_b=2, seed=7)
    scale = 0.7
    args = [q, k, v] + ([bias] if with_bias else []) \
        + ([mask] if with_mask else [])

    def loss(*a):
        b_ = a[3] if with_bias else None
        m_ = a[3 + with_bias] if with_mask else None
        return jnp.sum(jnp.sin(ops.fused_attention(
            a[0], a[1], a[2], bias=b_, mask=m_, scale=scale, kv_tile=16)))

    got = jax.grad(loss, argnums=tuple(range(len(args))))(*args)
    out, _ = ref.attention_ref(q, k, v, bias if with_bias else None,
                               mask if with_mask else None, scale)
    want = ref.attention_bwd_ref(q, k, v, bias if with_bias else None,
                                 mask if with_mask else None,
                                 jnp.cos(out), scale)
    want = [w for w in want if w is not None]
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5,
                                   rtol=1e-3)


def test_fused_pallas_multi_tile_matches_ref():
    """Interpret-mode forward and backward kernels where every grid axis
    spans several tiles (3 q tiles x 3 KV tiles, both padded): the running
    softmax state, the lse/delta rows and the mask reduction carried across
    tiles, which the single-tile shapes above never reach."""
    n, s, h, d = 2, 300, 2, 8
    q, k, v, bias, mask = _mk(n, s, s, h, d, jnp.float32, True, True,
                              bias_b=1, seed=13)
    scale = 0.5

    def loss(fn):
        def f(q_, k_, v_, b_, m_):
            return jnp.sum(jnp.sin(fn(q_, k_, v_, b_, m_)))
        return f

    def fused(q_, k_, v_, b_, m_):
        return ops.fused_attention(q_, k_, v_, bias=b_, mask=m_, scale=scale,
                                   kv_tile=128)

    def oracle(q_, k_, v_, b_, m_):
        return ref.attention_ref(q_, k_, v_, b_, m_, scale)[0]

    with use_plan(preset("interpret")):
        got = fused(q, k, v, bias, mask)
        g_got = jax.grad(loss(fused), argnums=(0, 1, 2, 3, 4))(
            q, k, v, bias, mask)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(oracle(q, k, v, bias, mask)),
                               atol=2e-5, rtol=1e-4)
    g_want = jax.grad(loss(oracle), argnums=(0, 1, 2, 3, 4))(
        q, k, v, bias, mask)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5,
                                   rtol=1e-3)


def test_fused_pallas_backward_mesh_local_bias_two_sweeps(monkeypatch):
    """rep == 1 (bias batch == N, the mesh-local bias-group case): dbias is
    emitted from the dq sweep (two recompute sweeps instead of three) and
    must still match the autodiff oracle."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    n, sq, skv, h, d = 3, 19, 27, 2, 8
    q, k, v, bias, mask = _mk(n, sq, skv, h, d, jnp.float32, True, True,
                              bias_b=n, seed=11)
    scale = 0.6

    def loss(q_, k_, v_, b_, m_):
        return jnp.sum(jnp.sin(ops.fused_attention(
            q_, k_, v_, bias=b_, mask=m_, scale=scale, kv_tile=16)))

    got = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(q, k, v, bias, mask)
    out, _ = ref.attention_ref(q, k, v, bias, mask, scale)
    want = ref.attention_bwd_ref(q, k, v, bias, mask, jnp.cos(out), scale)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5,
                                   rtol=1e-3)


def test_fused_pallas_backward_matches_scan_bf16(monkeypatch):
    """bf16: the Pallas backward and the jnp KV-scan backward agree on the
    same residuals (the scan is the oracle leg of ops._attn_bwd)."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    q, k, v, bias, mask = _mk(2, 24, 24, 2, 16, jnp.bfloat16, True, True,
                              bias_b=2, seed=9)

    def loss(q_, k_, v_):
        return jnp.sum(ops.fused_attention(
            q_, k_, v_, bias=bias, mask=mask, kv_tile=16).astype(jnp.float32)
            ** 2)

    g_pallas = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    # Pin the scan backward via a plan scope (the old FORCE_SCAN_ATTN_BWD
    # module global): the leg bakes into the op call's trace, so scoping the
    # grad call is sufficient and nothing leaks to other tests.
    with use_plan(current_plan().with_kernels(attn_bwd="scan")):
        g_scan = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_pallas, g_scan):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        scale = max(1.0, float(np.abs(b).max()))
        assert float(np.abs(a - b).max()) <= 2e-2 * scale


# The forward kernel on the model's (N, S, H·D) layout, every head of a row
# in one grid step (interpret mode): (n, sq, skv, h, d, bias_b, kv_tile).
PACKED_FWD_CASES = {
    # H·D 256 (the MSA sites), bias shared by 2 rows each, Sq 33 padded to
    # the 48-row q tile.
    "hd256_shared_bias": (4, 33, 33, 8, 32, 2, 0),
    # H·D 128 (the pair sites), KV tile 128 < Skv 300: three KV tiles, the
    # last padded, swept innermost with the fp32 state in scratch.
    "hd128_kv_tiles": (2, 40, 300, 4, 32, 1, 128),
    # H·D 64 (the extra-MSA stack), one bias row per attention row.
    "hd64_row_bias": (3, 20, 20, 8, 8, 3, 0),
    # Sq 530 over five 128-row q tiles, the last padded; Skv 130 padded to
    # one 256-wide KV tile.
    "hd32_q_tiles": (2, 530, 130, 4, 8, 1, 0),
    # Sq 1100 over three 384-row q tiles; Skv 700 over two 512-wide KV
    # tiles, the second padded.
    "hd128_q_and_kv_tiles": (1, 1100, 700, 4, 32, 1, 0),
    # H·D 512 past the widest group: two groups of two 128-wide heads.
    "hd512_head_groups": (2, 24, 40, 4, 128, 1, 0),
}


@pytest.mark.parametrize("case", sorted(PACKED_FWD_CASES))
def test_packed_forward_matches_ref(case):
    n, sq, skv, h, d, bias_b, kv_tile = PACKED_FWD_CASES[case]
    q, k, v, bias, mask = _mk(n, sq, skv, h, d, jnp.float32, True, True,
                              bias_b=bias_b, seed=21)
    scale = d ** -0.5
    with use_plan(preset("interpret")):
        got = ops.fused_attention(q, k, v, bias=bias, mask=mask, scale=scale,
                                  kv_tile=kv_tile)
    want, _ = ref.attention_ref(q, k, v, bias, mask, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=1e-4)


@pytest.mark.parametrize("sq,q_tile", [
    (33, 48), (256, 256), (512, 512),       # one q tile covers all of Sq
    (530, 128), (600, 128), (768, 384),     # the least padding, 128-row
    (1024, 512), (1100, 384),               # multiples, then the largest
])
def test_forward_q_tile(sq, q_tile):
    assert ops._attn_fwd_tiles(sq, 256, 0)[0] == q_tile


@pytest.mark.parametrize("heads,head_dim,group", [
    (8, 32, 8), (4, 32, 4), (8, 8, 8),     # the Evoformer's sites: all heads
    (4, 128, 2), (16, 32, 8),              # 256-lane groups past the widest
    (3, 96, 3),                            # no 128-lane divisor: all heads
])
def test_head_group(heads, head_dim, group):
    from repro.kernels.flash_attention import head_group

    assert head_group(heads, head_dim) == group


def test_packed_forward_lse_and_grad_through_staged_backward():
    """The packed forward's lse residual, and the gradient through the
    backward kernel that still reads the staged (N, H, S, 128-lane) layout,
    against the oracle: H·D 64 over two KV tiles."""
    n, sq, skv, h, d = 2, 24, 200, 4, 16
    q, k, v, bias, mask = _mk(n, sq, skv, h, d, jnp.float32, True, True,
                              bias_b=1, seed=23)
    scale = 0.4
    out, lse = ops._attn_fwd_impl(scale, True, True, 128, "interpret", q, k,
                                  v, bias, mask)
    want_out, want_lse = ref.attention_ref(q, k, v, bias, mask, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                               atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               atol=2e-5, rtol=1e-5)

    def loss(q_, k_, v_, b_, m_):
        return jnp.sum(jnp.sin(ops.fused_attention(
            q_, k_, v_, bias=b_, mask=m_, scale=scale, kv_tile=128)))

    with use_plan(preset("interpret")):
        got = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(q, k, v, bias, mask)
    want = ref.attention_bwd_ref(q, k, v, bias, mask, jnp.cos(want_out),
                                 scale)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5,
                                   rtol=1e-3)


def test_fused_attention_disabled_matches_kernel():
    """The 'oracle' plan's fallback == the kernel path (A/B as a use_plan
    scope instead of the old KERNELS_ENABLED mutation)."""
    q, k, v, bias, mask = _mk(2, 16, 16, 2, 8, jnp.float32, True, True)
    y_kern = ops.fused_attention(q, k, v, bias=bias, mask=mask)
    with use_plan(preset("oracle")):
        y_ref = ops.fused_attention(q, k, v, bias=bias, mask=mask)
    np.testing.assert_allclose(np.asarray(y_kern), np.asarray(y_ref),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# Through a full evoformer_block (acceptance criterion) and across dist modes.
# ---------------------------------------------------------------------------

CFG = EvoformerConfig(d_msa=32, d_pair=16, msa_heads=4, pair_heads=2,
                      head_dim=8, opm_dim=8, tri_mult_dim=16, n_blocks=2)


@pytest.fixture
def block_inputs():
    B, s, r = 2, 6, 10
    msa = jax.random.normal(jax.random.PRNGKey(1), (B, s, r, CFG.d_msa))
    pair = jax.random.normal(jax.random.PRNGKey(2), (B, r, r, CFG.d_pair))
    return (msa, pair, jnp.ones((B, s, r)), jnp.ones((B, r)),
            jnp.ones((B, r, r)))


def _block_grads(params, inputs, cfg, dist):
    def loss(p):
        m, z = evoformer_block(p, *inputs, dist=dist, cfg=cfg)
        return jnp.sum(m ** 2) + jnp.sum(z ** 2)

    return jax.grad(loss)(params)


def test_evoformer_block_grad_parity_fused_vs_oracle(block_inputs):
    """Gradient parity between the fused-attention block and the
    scores-materialized oracle block, under jax.grad through the whole
    evoformer_block (fp32: 1e-5)."""
    params = init_evoformer_block(jax.random.PRNGKey(0), CFG)
    g_fused = _block_grads(params, block_inputs, CFG, LocalDist())
    with use_plan(preset("oracle")):
        g_ref = _block_grads(params, block_inputs, CFG, LocalDist())
    flat1, tree1 = jax.tree.flatten(g_fused)
    flat2, tree2 = jax.tree.flatten(g_ref)
    assert tree1 == tree2
    for a, b in zip(flat1, flat2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                                   rtol=1e-4)


@pytest.mark.parametrize("mode", ["local", "gspmd"])
def test_evoformer_block_fused_dist_modes(block_inputs, mode):
    """Fused path under LocalDist and GspmdDist (1-device mesh) agrees with
    the LocalDist oracle; the ShardMapDist mode runs in
    test_distributed.py subprocesses with real device counts."""
    params = init_evoformer_block(jax.random.PRNGKey(0), CFG)
    m_ref, z_ref = evoformer_block(params, *block_inputs, dist=LocalDist(),
                                   cfg=CFG)
    if mode == "local":
        dist = LocalDist()
    else:
        from repro.launch.mesh import make_host_mesh

        dist = GspmdDist(mesh=make_host_mesh(model=1, data=1), axis="model")
    with_jit = jax.jit(lambda p, *a: evoformer_block(p, *a, dist=dist,
                                                     cfg=CFG))
    m, z = with_jit(params, *block_inputs)
    np.testing.assert_allclose(np.asarray(m), np.asarray(m_ref), atol=2e-5)
    np.testing.assert_allclose(np.asarray(z), np.asarray(z_ref), atol=2e-5)


def test_evoformer_block_bf16_grad_parity(block_inputs):
    """bf16 parity between fused and oracle paths within 2e-2."""
    params = init_evoformer_block(jax.random.PRNGKey(0), CFG)
    cfg = dataclasses.replace(CFG, compute_dtype=jnp.bfloat16)
    inputs = tuple(x.astype(jnp.bfloat16) if x.ndim == 4 else x
                   for x in block_inputs)
    g_fused = _block_grads(params, inputs, cfg, LocalDist())
    with use_plan(preset("oracle")):
        g_ref = _block_grads(params, inputs, cfg, LocalDist())
    for a, b in zip(jax.tree.leaves(g_fused), jax.tree.leaves(g_ref)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        # Scale-normalized max-abs: 4e-2 relative to the gradient magnitude
        # (bf16 eps ~8e-3; absolute tolerances are unattainable for O(10)
        # grads). The fused pair-stack path keeps the triangle/OPM products
        # in fp32 while the materialized path rounds them to bf16, so the
        # A/B delta here is bf16 rounding noise, not a defect.
        scale = max(1.0, float(np.abs(b).max()))
        assert float(np.abs(a - b).max()) <= 4e-2 * scale


def test_attention_counter_one_block(block_inputs):
    """Tracing one Evoformer block on the kernel's leg records
    ``ops.attention.fwd`` once per attention site (MSA row and column,
    triangle start and end), each with every head in one grid step and one
    KV tile; nothing on the XLA and oracle legs, which run no flash
    kernel."""
    from repro.obs import use_tracer

    params = init_evoformer_block(jax.random.PRNGKey(0), CFG)

    def trace_block():
        with use_tracer() as tr:
            jax.eval_shape(lambda p, *a: evoformer_block(
                p, *a, dist=LocalDist(), cfg=CFG), params, *block_inputs)
        return [e["attrs"] for e in tr.events
                if e["kind"] == "counter" and e["name"] == "ops.attention.fwd"]

    with use_plan(preset("interpret")):
        got = trace_block()
    heads = [CFG.msa_heads] * 2 + [CFG.pair_heads] * 2
    assert sorted(a["heads"] for a in got) == sorted(heads)
    for a in got:
        assert a["heads_per_step"] == a["heads"] and a["kv_tiles"] == 1, a
    for leg in ("xla", "oracle"):
        with use_plan(current_plan().with_kernels(attention=leg)):
            assert trace_block() == []
