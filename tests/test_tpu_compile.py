"""AOT compiles of the Pallas kernels for a described TPU v5e: the main
path's (attention, triangle, OPM) and the row-wise kernels (softmax, layer
norm, element-wise) that a plan naming their ``pallas`` leg runs.

Interpret mode cannot see the TPU lowering's block-shape (8, 128) rule,
scoped-VMEM limits or Mosaic's layout support; these compiles can, at the
FULL config's widths and at the shapes ``kernels/ops.py`` stages for the
kernels. Nothing runs: the topology is described, not attached. The kernels
are called directly with ``interpret=False`` because ``ops`` interprets
them off-TPU.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.kernels.flash_attention import (
    flash_attention_bwd_pallas,
    flash_attention_pallas,
)
from repro.kernels.fused_elementwise import (
    bias_dropout_add_pallas,
    bias_sigmoid_mul_pallas,
)
from repro.kernels.fused_softmax import fused_softmax_pallas
from repro.kernels.layer_norm import layer_norm_pallas
from repro.kernels.triangle import fused_opm_pallas, fused_triangle_pallas

BF16, F32 = jnp.bfloat16, jnp.float32

# (N, S, H, D, bias, mask) of the Evoformer's attention sites at FULL
# widths, n_res 256, n_seq 128: N is batch x group, S the attended length;
# and the extra-MSA stack's row attention (model_3: 5120 rows, 8 heads of
# 8); and triangle attention at n_res 600, whose forward takes several q
# and KV tiles. The forward reads them in the model's (N, S, H·D) layout;
# the backward stages them at 128 lanes a head.
ATTENTION_SITES = {
    "msa_row": (128, 256, 8, 32, True, True),
    "msa_col": (256, 128, 8, 32, False, True),
    "tri_attn": (256, 256, 4, 32, True, True),
    "extra_msa_row": (5120, 256, 8, 8, True, True),
    "tri_attn_r600": (600, 600, 4, 32, True, True),
}
HEAD_DIM = 32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        # libtpu would otherwise log under /tmp.
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu, or it cannot describe a v5e
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip cannot be read back from the
        # persistent cache without the chip; keep it out of the cache.
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield jax.sharding.SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, sharding, *shapes):
    """AOT-compile ``fn`` for the described chip; return the HLO text."""
    args = [None if s is None else
            jax.ShapeDtypeStruct(s[0], s[1], sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernels(hlo: str, n: int):
    assert hlo.count('custom_call_target="tpu_custom_call"') == n


def _attention_shapes(site):
    """The backward kernel's staged shapes: (N, H, S, 128-lane D)."""
    n, s, h, d, has_bias, has_mask = ATTENTION_SITES[site]
    q_tile, kv_tile, d_pad = ops._attn_tiles(s, s, d, 0)
    sq, skv = -(-s // q_tile) * q_tile, -(-s // kv_tile) * kv_tile
    q = ((n, h, sq, d_pad), BF16)
    kv = ((n, h, skv, d_pad), BF16)
    bias = ((1, h, sq, skv), BF16) if has_bias else None
    mask = ((n, 1, skv), F32) if has_mask else None
    kw = dict(scale=d ** -0.5, kv_len=s, q_tile=q_tile,
              kv_tile=kv_tile, has_bias=has_bias, has_mask=has_mask,
              interpret=False)
    return q, kv, ((n, h, sq), F32), bias, mask, kw


@pytest.mark.parametrize("site", sorted(ATTENTION_SITES))
def test_flash_attention_fwd_compiles(one_chip, site):
    """The forward on the model's layout: q, k, v (N, S, H·D), every head
    of a row in one grid step."""
    n, s, h, d, has_bias, has_mask = ATTENTION_SITES[site]
    q_tile, kv_tile = ops._attn_fwd_tiles(s, s, 0)
    sq, skv = -(-s // q_tile) * q_tile, -(-s // kv_tile) * kv_tile
    kw = dict(scale=d ** -0.5, kv_len=s, heads=h, q_tile=q_tile,
              kv_tile=kv_tile, has_bias=has_bias, has_mask=has_mask,
              interpret=False)
    hlo = _compile(functools.partial(flash_attention_pallas, **kw), one_chip,
                   ((n, sq, h * d), BF16), ((n, skv, h * d), BF16),
                   ((n, skv, h * d), BF16),
                   ((1, h, sq, skv), BF16) if has_bias else None,
                   ((n, 1, skv), F32) if has_mask else None)
    _assert_kernels(hlo, 1)


def test_msa_row_attention_layer_reads_model_layout(one_chip, monkeypatch):
    """The Evoformer's gated attention at the ``msa_row`` site, from the QKV
    projection through ``ops.fused_attention`` to the gated output
    projection, compiled on the Pallas leg: one kernel, and no transpose or
    pad of q, k or v between the projection and it, nor a slice of one
    merged projection output."""
    from repro.core.evoformer import _gated_attention
    from repro.exec.plan import current_plan, use_plan
    from repro.layers.attention import AttnDims, init_attention

    n, s, h, d, _, _ = ATTENTION_SITES["msa_row"]
    c = h * d
    dims = AttnDims(h, h, d)
    # ``ops`` interprets the kernels off-TPU; this compile is for the chip.
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    params = jax.eval_shape(lambda: init_attention(
        jax.random.PRNGKey(0), c, h, h, d, gating=True, out_bias=True))

    def layer(p, x, bias, mask):
        return _gated_attention(p, x, bias, mask, dims)

    with use_plan(current_plan().with_kernels(attention="pallas")):
        lowered = jax.jit(layer).lower(
            jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=one_chip), params),
            jax.ShapeDtypeStruct((1, n, s, c), BF16, sharding=one_chip),
            jax.ShapeDtypeStruct((1, h, s, s), BF16, sharding=one_chip),
            jax.ShapeDtypeStruct((1, n, s), F32, sharding=one_chip))
    hlo = lowered.compile().as_text()
    _assert_kernels(hlo, 1)
    for op in ("transpose", "pad"):
        assert f" {op}(" not in hlo, op
    # The projection's outputs reach the kernel whole: no sliced (N, S,
    # 3·H·D) output of a merged GEMM.
    assert f",{s},{3 * c}]" not in hlo


@pytest.mark.parametrize("site", sorted(ATTENTION_SITES))
def test_flash_attention_bwd_compiles(one_chip, site):
    q, kv, row, bias, mask, kw = _attention_shapes(site)
    hlo = _compile(functools.partial(flash_attention_bwd_pallas, **kw),
                   one_chip, q, kv, kv, q, row, row, bias, mask)
    # dq and dk/dv sweeps, plus the bias-reduction sweep when the pair bias
    # is shared by several rows (every site here: B = 1 < N).
    _assert_kernels(hlo, 3 if bias is not None else 2)


def test_triangle_mult_compiles(one_chip):
    r, c, d = 256, 128, 128
    hlo = _compile(
        functools.partial(fused_triangle_pallas, interpret=False), one_chip,
        ((1, r, r, c), BF16), ((1, r, r, c), BF16), ((1, r, r), BF16),
        ((1, r, r, c), BF16), ((c,), F32), ((c,), F32), ((c, d), F32),
        ((d,), F32), ((1, r, r, d), BF16), ((d,), F32))
    _assert_kernels(hlo, 1)


def test_outer_product_mean_compiles(one_chip):
    s, r, c, d = 128, 256, 32, 128
    hlo = _compile(
        functools.partial(fused_opm_pallas, interpret=False), one_chip,
        ((1, s, r, c), BF16), ((1, s, r, c), BF16), ((1, s, r), F32),
        ((1, s, r), F32), ((c * c, d), F32), ((d,), F32))
    _assert_kernels(hlo, 1)


def test_fused_softmax_compiles(one_chip):
    # The scores-materialized MSA-row site: (B·G, H, R, C) with pair bias.
    hlo = _compile(
        functools.partial(fused_softmax_pallas, scale=HEAD_DIM ** -0.5,
                          has_bias=True, has_mask=True, interpret=False),
        one_chip, ((128, 8, 256, 256), BF16), ((1, 8, 256, 256), BF16),
        ((128, 256), F32))
    _assert_kernels(hlo, 1)


@pytest.mark.parametrize("c", [128, 256])
def test_layer_norm_compiles(one_chip, c):
    # The MSA (c 256) and pair (c 128) representations, 4D as the Evoformer
    # hands them over.
    shape = (1, 128, 256, c) if c == 256 else (1, 256, 256, c)
    hlo = _compile(functools.partial(layer_norm_pallas, interpret=False),
                   one_chip, (shape, BF16), ((c,), F32), ((c,), F32))
    _assert_kernels(hlo, 1)


def test_bias_sigmoid_mul_compiles(one_chip):
    shape = (1, 256, 256, 128)
    hlo = _compile(functools.partial(bias_sigmoid_mul_pallas,
                                     interpret=False),
                   one_chip, (shape, BF16), ((128,), F32), (shape, BF16))
    _assert_kernels(hlo, 1)


def test_bias_dropout_add_compiles(one_chip):
    rows = 256 * 256        # the pair representation, row-flattened
    hlo = _compile(functools.partial(bias_dropout_add_pallas, rate=0.25,
                                     interpret=False),
                   one_chip, ((rows, 128), BF16), ((128,), BF16),
                   ((rows, 128), BF16), ((rows, 128), F32))
    _assert_kernels(hlo, 1)
