"""Fused LayerNorm Pallas TPU kernel (paper §IV.A.3, Fig. 9).

GPU→TPU adaptation: the paper uses a warp per row with the *Welford* merge
update so partial (mean, M2) streams held by different threads can be combined
numerically stably in one pass. On TPU the whole row lives in one VMEM tile, so
no cross-thread merging exists; we keep the one-pass property by accumulating
``sum(x)`` and ``sum(x^2)`` in fp32 inside the tile. At the row lengths in this
framework (<= ~27k, bf16 inputs) fp32 E[x^2]-E[x]^2 matches the two-pass oracle
to within bf16 resolution — asserted by the kernel test sweep.

Fusion, as in the paper: load x once from HBM, write y once, with statistics +
affine applied in-register.

On TPU this kernel is an explicit leg (``KernelPolicy(layer_norm="pallas")``,
for A/B runs and microbenchmarks), not the default: ``auto`` takes the XLA leg
(``kernels/ops.py`` ``kernel_leg``). Its 8-row tiles cost one grid step per
2-4 KB of bf16, so a fixed cost per step, not HBM bandwidth, sets its time,
while XLA fuses the same float32 math into the neighbouring ops. In the
full-width train step on one v5e it took 1.88 s of 7.46 s; on the XLA leg the
whole step ran 1.44x faster (PERF.md section 5).

Rank-polymorphic: 2D–4D inputs run under a grid over the leading dims — the
kernel never row-flattens its input, so mesh-sharded (B, G, ...) leading dims
stay unmerged under GSPMD (a reshape merging two sharded dims would force an
all-gather of the whole representation; same contract as the shard-mapped
fused attention).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROW_TILE = 8
LANE = 128


def _pad_to(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def row_grid_specs(shape, row_tile: int, c_pad: int):
    """Grid + x-block spec for an (..., R, C) tensor WITHOUT flattening the
    leading dims: one grid axis per leading dim, blocks of (1, ..., row_tile,
    c_pad). Returns (grid, block_shape, index_map)."""
    lead = tuple(shape[:-2])
    nl = len(lead)
    grid = lead + (pl.cdiv(shape[-2], row_tile),)
    block = (1,) * nl + (row_tile, c_pad)

    def ix(*g):
        return g[:nl] + (g[nl], 0)

    return grid, block, ix


def _layer_norm_kernel(x_ref, g_ref, b_ref, o_ref, *, eps: float, c_actual: int):
    x = x_ref[...].astype(jnp.float32)
    x = x.reshape(x.shape[-2:])         # drop leading (1,)*nl block dims
    if c_actual != x.shape[-1]:
        lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
        valid = lane < c_actual
        x = jnp.where(valid, x, 0.0)
    count = jnp.float32(c_actual)
    s1 = jnp.sum(x, axis=-1, keepdims=True)
    s2 = jnp.sum(x * x, axis=-1, keepdims=True)
    mean = s1 / count
    var = jnp.maximum(s2 / count - mean * mean, 0.0)
    inv = jax.lax.rsqrt(var + eps)
    y = (x - mean) * inv
    y = y * g_ref[...].astype(jnp.float32)[0] + b_ref[...].astype(jnp.float32)[0]
    o_ref[...] = y.reshape(o_ref.shape).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def layer_norm_pallas(
    x: jax.Array,
    gamma: jax.Array,
    beta: jax.Array,
    *,
    eps: float = 1e-5,
    interpret: bool = False,
) -> jax.Array:
    """x: (..., R, C) (2D–4D) normalized over C; gamma/beta: (C,)."""
    r, c = x.shape[-2], x.shape[-1]
    c_pad = _pad_to(c, LANE)
    row_tile = ROW_TILE if r >= ROW_TILE else r
    grid, block, ix = row_grid_specs(x.shape, row_tile, c_pad)
    kernel = functools.partial(_layer_norm_kernel, eps=eps, c_actual=c)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(block, ix),
            pl.BlockSpec((1, c_pad), lambda *g: (0, 0)),
            pl.BlockSpec((1, c_pad), lambda *g: (0, 0)),
        ],
        out_specs=pl.BlockSpec(block, ix),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, gamma.reshape(1, c), beta.reshape(1, c))
