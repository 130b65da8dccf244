"""Fused element-wise chains (paper §IV.A.1 "JIT Fusion") as Pallas kernels.

The paper fuses ``bias + sigmoid + element-wise product`` (Evoformer gating)
and ``bias + dropout + add`` (residual path) with TorchScript. Under XLA these
chains fuse anyway, and into their neighbours too. The Pallas kernels here make
the fusion explicit and serve as the unit the paper's Figure-8/9-style
microbenchmarks exercise; on TPU they are an explicit leg
(``KernelPolicy(elementwise="pallas")``), not the default, which is the XLA leg
(``kernels/ops.py`` ``kernel_leg``). Their 8-row tiles cost one grid step per
2-4 KB of bf16, so a fixed cost per step, not HBM bandwidth, sets their time,
and ``bias_dropout_add_pallas`` also streams a full-size float32 keep mask. In
the full-width train step on one v5e they took 0.75 s of 7.46 s; with them and
layer norm on the XLA leg the whole step ran 1.44x faster (PERF.md section 5).

Dropout randomness: the kernel consumes pre-generated uint32 random bits
(threshold compare in-register) rather than an in-kernel PRNG, keeping the
kernel deterministic and identical between interpret (CPU) and TPU modes.

``bias_sigmoid_mul_pallas`` is rank-polymorphic (2D–4D): a grid axis per
leading dim instead of a row-flatten, so mesh-sharded (B, G, ...) leading
dims stay unmerged under GSPMD (a reshape merging two sharded dims would
force an all-gather of the whole representation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.layer_norm import row_grid_specs

ROW_TILE = 8
LANE = 128


def _pad_to(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _bias_sigmoid_mul_kernel(g_ref, bg_ref, v_ref, o_ref):
    g = g_ref[...].astype(jnp.float32) + bg_ref[...].astype(jnp.float32)[0]
    o = jax.nn.sigmoid(g) * v_ref[...].astype(jnp.float32)
    o_ref[...] = o.reshape(o_ref.shape).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bias_sigmoid_mul_pallas(
    g: jax.Array, bg: jax.Array, v: jax.Array, *, interpret: bool = False
) -> jax.Array:
    """g, v: (..., R, C) (2D-4D); bg: (C,). sigmoid(g + bg) * v in v.dtype."""
    r, c = g.shape[-2], g.shape[-1]
    c_pad = _pad_to(c, LANE)
    row_tile = ROW_TILE if r >= ROW_TILE else r
    grid, block, ix = row_grid_specs(g.shape, row_tile, c_pad)
    return pl.pallas_call(
        _bias_sigmoid_mul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(block, ix),
            pl.BlockSpec((1, c_pad), lambda *gi: (0, 0)),
            pl.BlockSpec(block, ix),
        ],
        out_specs=pl.BlockSpec(block, ix),
        out_shape=jax.ShapeDtypeStruct(v.shape, v.dtype),
        interpret=interpret,
    )(g, bg.reshape(1, c), v)


def _bias_dropout_add_kernel(x_ref, b_ref, res_ref, keep_ref, o_ref, *, rate: float):
    y = x_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)[0]
    if rate > 0.0:
        y = y * keep_ref[...] / (1.0 - rate)
    o_ref[...] = (res_ref[...].astype(jnp.float32) + y).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rate", "interpret"))
def bias_dropout_add_pallas(
    x: jax.Array,
    b: jax.Array,
    residual: jax.Array,
    keep: jax.Array,
    *,
    rate: float,
    interpret: bool = False,
) -> jax.Array:
    """x, residual: (R, C); keep: (R, C) float32 0/1 mask; b: (C,).
    residual + dropout(x + b, rate)."""
    r, c = x.shape
    c_pad = _pad_to(c, LANE)
    row_tile = ROW_TILE if r >= ROW_TILE else r
    grid = (pl.cdiv(r, row_tile),)
    kernel = functools.partial(_bias_dropout_add_kernel, rate=rate)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((row_tile, c_pad), lambda i: (i, 0)),
            pl.BlockSpec((1, c_pad), lambda i: (0, 0)),
            pl.BlockSpec((row_tile, c_pad), lambda i: (i, 0)),
            pl.BlockSpec((row_tile, c_pad), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((row_tile, c_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(residual.shape, residual.dtype),
        interpret=interpret,
    )(x, b.reshape(1, c), residual, keep)
