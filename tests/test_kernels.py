"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles,
hypothesis property tests, and custom-VJP correctness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.kernels import ops, ref

ATOL = {jnp.float32: 1e-5, jnp.bfloat16: 2.5e-2}


def tols(dt):
    return dict(atol=ATOL[dt], rtol=1e-2)


# ---------------------------------------------------------------------------
# fused softmax
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    (1, 1, 8, 128),    # aligned
    (2, 4, 16, 64),    # small lanes
    (3, 2, 5, 130),    # pad both dims
    (2, 8, 33, 256),   # row-tile edge
    (1, 4, 256, 384),  # alphafold-ish row size
])
def test_softmax_sweep(shape, dtype):
    n, h, r, c = shape
    x = jax.random.normal(jax.random.PRNGKey(0), shape, dtype) * 3
    bias = jax.random.normal(jax.random.PRNGKey(1), (h, r, c), dtype)
    mask = jnp.where(
        jax.random.bernoulli(jax.random.PRNGKey(2), 0.8, (n, c)), 0.0, -1e9
    ).astype(jnp.float32)
    got = ops.fused_softmax(x, bias, mask, scale=0.5)
    want = ref.softmax_ref(x, bias[None], mask, 0.5)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tols(dtype))


def test_softmax_bias_batch():
    n, h, r, c = 6, 2, 8, 96
    x = jax.random.normal(jax.random.PRNGKey(0), (n, h, r, c))
    bias = jax.random.normal(jax.random.PRNGKey(1), (3, h, r, c))
    got = ops.fused_softmax(x, bias)
    want = ref.softmax_ref(x, bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


@settings(max_examples=25, deadline=None)
@given(
    r=st.integers(1, 40), c=st.integers(2, 300),
    scale=st.floats(0.1, 4.0),
    seed=st.integers(0, 2**30),
)
def test_softmax_properties(r, c, scale, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, 1, r, c)) * 5
    y = np.asarray(ops.fused_softmax(x, scale=scale))
    # rows sum to 1, all entries in [0, 1]
    np.testing.assert_allclose(y.sum(-1), np.ones((1, 1, r)), atol=1e-5)
    assert (y >= 0).all() and (y <= 1.0 + 1e-6).all()
    # shift invariance
    y2 = np.asarray(ops.fused_softmax(x + 7.0 / scale, scale=scale))
    np.testing.assert_allclose(y, y2, atol=1e-5)


def test_softmax_fully_masked_row_no_nan():
    x = jnp.ones((1, 1, 4, 8))
    mask = jnp.full((1, 8), -1e9, jnp.float32)
    y = ops.fused_softmax(x, mask=mask)
    assert not bool(jnp.isnan(y).any())


def test_softmax_vjp_matches_autodiff():
    n, h, r, c = 4, 2, 8, 96
    x = jax.random.normal(jax.random.PRNGKey(0), (n, h, r, c))
    bias = jax.random.normal(jax.random.PRNGKey(1), (2, h, r, c))
    mask = jnp.where(jax.random.bernoulli(jax.random.PRNGKey(2), 0.9, (n, c)),
                     0.0, -1e9)
    f1 = lambda x, b, m: jnp.sum(jnp.sin(ops.fused_softmax(x, b, m, 0.7)))
    f2 = lambda x, b, m: jnp.sum(jnp.sin(ref.softmax_ref(x, b, m, 0.7)))
    g1 = jax.grad(f1, argnums=(0, 1, 2))(x, bias, mask)
    g2 = jax.grad(f2, argnums=(0, 1, 2))(x, bias, mask)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


# ---------------------------------------------------------------------------
# layer norm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(16, 64), (7, 130), (32, 256), (5, 8960),
                                   (1, 1)])
def test_layernorm_sweep(shape, dtype):
    r, c = shape
    x = jax.random.normal(jax.random.PRNGKey(r + c), shape, dtype) * 2 + 1
    g = jax.random.normal(jax.random.PRNGKey(1), (c,))
    b = jax.random.normal(jax.random.PRNGKey(2), (c,))
    got = ops.layer_norm(x, g, b)
    want = ref.layer_norm_ref(x, g, b)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tols(dtype))


@settings(max_examples=25, deadline=None)
@given(r=st.integers(1, 30), c=st.integers(2, 400), seed=st.integers(0, 2**30))
def test_layernorm_properties(r, c, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (r, c)) * 4 + 3
    y = np.asarray(ops.layer_norm(x, jnp.ones((c,)), jnp.zeros((c,))),
                   np.float64)
    np.testing.assert_allclose(y.mean(-1), np.zeros(r), atol=1e-4)
    # The output's std is sqrt(var / (var + eps)), not 1: a short row (c=2)
    # can have a variance near eps.
    var = np.asarray(x, np.float64).var(-1)
    np.testing.assert_allclose(y.std(-1), np.sqrt(var / (var + 1e-5)),
                               atol=2e-2)


def test_layernorm_vjp_matches_autodiff():
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 96))
    g = jax.random.normal(jax.random.PRNGKey(3), (96,))
    b = jax.random.normal(jax.random.PRNGKey(4), (96,))
    f1 = lambda *a: jnp.sum(jnp.cos(ops.layer_norm(*a)))
    f2 = lambda *a: jnp.sum(jnp.cos(ref.layer_norm_ref(*a)))
    g1 = jax.grad(f1, argnums=(0, 1, 2))(x, g, b)
    g2 = jax.grad(f2, argnums=(0, 1, 2))(x, g, b)
    for a, bb in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb), atol=1e-4)


# ---------------------------------------------------------------------------
# fused element-wise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bias_sigmoid_mul(dtype):
    g = jax.random.normal(jax.random.PRNGKey(0), (4, 6, 96), dtype)
    v = jax.random.normal(jax.random.PRNGKey(1), (4, 6, 96), dtype)
    bg = jax.random.normal(jax.random.PRNGKey(2), (96,))
    got = ops.bias_sigmoid_mul(g, bg, v)
    want = ref.bias_sigmoid_mul_ref(g, bg, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tols(dtype))


@pytest.mark.parametrize("shape", [(16, 64), (3, 7, 130), (2, 5, 9, 96),
                                   (2, 3, 4, 5, 32)])
def test_layernorm_rank_polymorphic(shape):
    """2D-4D inputs run the kernel WITHOUT a row-flatten (grid over leading
    dims — mesh-sharded dims stay unmerged under GSPMD); 5D+ falls back to
    the flattened layout. Values and VJP reductions must be rank-agnostic."""
    c = shape[-1]
    x = jax.random.normal(jax.random.PRNGKey(1), shape) * 2 + 1
    g = jax.random.normal(jax.random.PRNGKey(2), (c,))
    b = jax.random.normal(jax.random.PRNGKey(3), (c,))
    np.testing.assert_allclose(np.asarray(ops.layer_norm(x, g, b)),
                               np.asarray(ref.layer_norm_ref(x, g, b)),
                               atol=1e-5)
    g1 = jax.grad(lambda *a: jnp.sum(jnp.sin(ops.layer_norm(*a))),
                  argnums=(0, 1, 2))(x, g, b)
    g2 = jax.grad(lambda *a: jnp.sum(jnp.sin(ref.layer_norm_ref(*a))),
                  argnums=(0, 1, 2))(x, g, b)
    for a, bb in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb), atol=1e-4)


@pytest.mark.parametrize("shape", [(16, 64), (3, 7, 130), (2, 5, 9, 96),
                                   (2, 3, 4, 5, 32)])
def test_bias_sigmoid_mul_rank_polymorphic(shape):
    c = shape[-1]
    g = jax.random.normal(jax.random.PRNGKey(1), shape)
    v = jax.random.normal(jax.random.PRNGKey(2), shape)
    bg = jax.random.normal(jax.random.PRNGKey(3), (c,))
    np.testing.assert_allclose(np.asarray(ops.bias_sigmoid_mul(g, bg, v)),
                               np.asarray(ref.bias_sigmoid_mul_ref(g, bg, v)),
                               atol=1e-6)
    g1 = jax.grad(lambda *a: jnp.sum(jnp.sin(ops.bias_sigmoid_mul(*a))),
                  argnums=(0, 1, 2))(g, bg, v)
    g2 = jax.grad(lambda *a: jnp.sum(jnp.sin(ref.bias_sigmoid_mul_ref(*a))),
                  argnums=(0, 1, 2))(g, bg, v)
    for a, bb in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb), atol=1e-5)


def test_bias_dropout_add_deterministic():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 96))
    r = jax.random.normal(jax.random.PRNGKey(1), (4, 96))
    b = jax.random.normal(jax.random.PRNGKey(2), (96,))
    got = ops.bias_dropout_add(x, b, r)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x + b + r),
                               atol=1e-5)


def test_bias_dropout_add_rate():
    x = jnp.ones((64, 128))
    r = jnp.zeros((64, 128))
    b = jnp.zeros((128,))
    out = np.asarray(ops.bias_dropout_add(x, b, r, rate=0.5,
                                          rng=jax.random.PRNGKey(7)))
    zero_frac = (out == 0).mean()
    assert 0.35 < zero_frac < 0.65
    kept = out[out != 0]
    np.testing.assert_allclose(kept, 2.0, atol=1e-6)  # 1/(1-rate) scaling


def test_kernels_disable_flag():
    from repro.exec.plan import preset, use_plan
    from repro.kernels import ops as ops_mod
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
    g = jnp.ones((64,))
    b = jnp.zeros((64,))
    with use_plan(preset("oracle")):
        y_ref = ops_mod.layer_norm(x, g, b)
    y_kern = ops_mod.layer_norm(x, g, b)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_kern),
                               atol=1e-6)
