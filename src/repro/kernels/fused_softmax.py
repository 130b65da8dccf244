"""Fused scale+bias+mask+softmax Pallas TPU kernel (paper §IV.A.2, Fig. 5).

GPU→TPU adaptation: the paper assigns one *warp* per (short) softmax row and
reduces with ``__shfl_xor_sync``. TPUs have no warps; the equivalent strategy is
to pack a tile of rows into VMEM — block shape ``(1, 1, ROW_TILE, C_pad)``,
8x128-aligned — and let the VPU do the lane reduction over the last axis. The
fusion benefit is identical to the paper's: scale, pair-bias add, mask add,
max-subtract, exp, and normalize all happen in a single HBM round trip instead
of five.

Numerical behaviour matches ref.softmax_ref: fp32 accumulation, max-shifted exp.
Out-of-envelope shapes fall back to the oracle in ops.py.
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


def _softmax_kernel(*refs, scale: float, c_actual: int, has_bias: bool, has_mask: bool):
    idx = 0
    x_ref = refs[idx]; idx += 1
    b_ref = refs[idx] if has_bias else None
    idx += int(has_bias)
    m_ref = refs[idx] if has_mask else None
    idx += int(has_mask)
    o_ref = refs[idx]

    x = x_ref[0, 0].astype(jnp.float32) * scale  # (ROW_TILE, C_pad)
    if b_ref is not None:
        x = x + b_ref[0, 0].astype(jnp.float32)
    if m_ref is not None:
        x = x + m_ref[0].astype(jnp.float32)      # (1, C_pad) row
    # Neutralize lane padding (C_pad > C): padded lanes must not win the max
    # nor contribute to the sum.
    if c_actual != x.shape[-1]:
        lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
        x = jnp.where(lane < c_actual, x, -jnp.inf)
    x_max = jnp.max(x, axis=-1, keepdims=True)
    # Guard fully-masked rows (all -inf): exp(-inf - -inf) would be NaN.
    x_max = jnp.where(jnp.isfinite(x_max), x_max, 0.0)
    ex = jnp.exp(x - x_max)
    denom = jnp.sum(ex, axis=-1, keepdims=True)
    o_ref[0, 0] = (ex / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "has_bias", "has_mask", "interpret")
)
def fused_softmax_pallas(
    x: jax.Array,
    bias: jax.Array | None = None,
    mask: jax.Array | None = None,
    *,
    scale: float = 1.0,
    has_bias: bool = False,
    has_mask: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """x: (N, H, R, C); bias: (H, R, C) | None; mask: (N, C) | None."""
    n, h, r, c = x.shape
    c_pad = _pad_to(c, LANE)
    row_tile = ROW_TILE if r >= ROW_TILE else r
    grid = (n, h, pl.cdiv(r, row_tile))

    in_specs = [
        pl.BlockSpec((1, 1, row_tile, c_pad), lambda i, j, k: (i, j, k, 0)),
    ]
    operands = [x]
    if has_bias:
        assert bias is not None and bias.ndim == 4 and bias.shape[1:] == (h, r, c)
        rep = n // bias.shape[0]  # rows of x sharing one bias batch element
        in_specs.append(
            pl.BlockSpec((1, 1, row_tile, c_pad),
                         lambda i, j, k: (i // rep, j, k, 0))
        )
        operands.append(bias)
    if has_mask:
        assert mask is not None and mask.shape == (n, c)
        # (N, 1, C): a (1, 1, C_pad) block keeps its second-minor dim equal
        # to the array's — the TPU (8, 128) block rule refuses (1, C_pad).
        in_specs.append(pl.BlockSpec((1, 1, c_pad),
                                     lambda i, j, k: (i, 0, 0)))
        operands.append(mask.reshape(n, 1, c))

    kernel = functools.partial(
        _softmax_kernel,
        scale=scale,
        c_actual=c,
        has_bias=has_bias,
        has_mask=has_mask,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, row_tile, c_pad), lambda i, j, k: (i, j, k, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(*operands)
