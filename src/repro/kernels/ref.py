"""Pure-jnp oracles for every Pallas kernel in this package.

These are the correctness references: each kernel test sweeps shapes/dtypes and
asserts allclose against these functions. They are also the fallback path used
by ops.py when a shape is outside the kernel's supported envelope.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def softmax_ref(
    x: jax.Array,
    bias: jax.Array | None = None,
    mask: jax.Array | None = None,
    scale: float = 1.0,
) -> jax.Array:
    """softmax(scale*x + bias + mask) over the last axis, fp32 accumulation.

    x:    (N, H, R, C)
    bias: (B, H, R, C) with N % B == 0 — each bias batch element is shared by
          N/B consecutive rows of x (pair bias in Evoformer: B batch elements,
          N = B*s attention groups). (H, R, C) is accepted as B=1.
    mask: (N, C)     additive, broadcast over H, R
    """
    acc = x.astype(jnp.float32) * scale
    if bias is not None:
        if bias.ndim == 3:
            bias = bias[None]
        b = bias.shape[0]
        n = x.shape[0]
        acc = acc.reshape((b, n // b) + acc.shape[1:])
        acc = acc + bias.astype(jnp.float32)[:, None]
        acc = acc.reshape((n,) + acc.shape[2:])
    if mask is not None:
        acc = acc + mask.astype(jnp.float32)[:, None, None, :]
    out = jax.nn.softmax(acc, axis=-1)
    return out.astype(x.dtype)


def attention_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    bias: jax.Array | None = None,
    mask: jax.Array | None = None,
    scale: float = 1.0,
) -> tuple[jax.Array, jax.Array]:
    """Scores-materialized oracle for ops.fused_attention.

    q: (N, Sq, H, D); k, v: (N, Skv, H, D)
    bias: (B, H, Sq, Skv), N % B == 0 (each bias batch element shared by N/B
          consecutive rows — Evoformer pair bias), or (H, Sq, Skv) as B=1.
    mask: (N, Skv) additive fp32, broadcast over H and Sq.

    Returns (out (N, Sq, H, D) in q.dtype, lse (N, H, Sq) fp32). This is the
    exact computation the fused kernel performs tile-wise; it materializes the
    full (N, H, Sq, Skv) scores tensor and is the A/B baseline + fallback.
    """
    n, sq, h, d = q.shape
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        if bias.ndim == 3:
            bias = bias[None]
        b = bias.shape[0]
        s = s.reshape((b, n // b) + s.shape[1:])
        s = s + bias.astype(jnp.float32)[:, None]
        s = s.reshape((n,) + s.shape[2:])
    if mask is not None:
        s = s + mask.astype(jnp.float32)[:, None, None, :]
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    ex = jnp.exp(s - m)
    l = jnp.sum(ex, axis=-1, keepdims=True)
    probs = (ex / jnp.maximum(l, 1e-30)).astype(q.dtype)
    out = jnp.einsum("nhqk,nkhd->nqhd", probs, v,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    lse = (m + jnp.log(jnp.maximum(l, 1e-30)))[..., 0]
    return out, lse


def attention_bwd_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    bias: jax.Array | None,
    mask: jax.Array | None,
    g: jax.Array,
    scale: float = 1.0,
):
    """Autodiff gradients of attention_ref's output under cotangent ``g`` —
    the oracle for both legs of ops._attn_bwd (the jnp KV-scan and the fused
    flash_attention_bwd_pallas kernel). Returns (dq, dk, dv, dbias | None,
    dmask | None)."""
    diff = [q, k, v]
    if bias is not None:
        diff.append(bias)
    if mask is not None:
        diff.append(mask)

    def f(*args):
        b_ = args[3] if bias is not None else None
        m_ = args[3 + (bias is not None)] if mask is not None else None
        return attention_ref(args[0], args[1], args[2], b_, m_, scale)[0]

    _, vjp = jax.vjp(f, *diff)
    grads = list(vjp(g))
    if bias is None:
        grads.insert(3, None)
    if mask is None:
        grads.append(None)
    return tuple(grads)


def global_attention_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: jax.Array | None = None,
    scale: float = 1.0,
) -> jax.Array:
    """Oracle for ops.global_attention, in float32 throughout.

    q: (..., H, D) one query per head; k, v: (..., S, D) one key and one
    value head shared by all heads; mask: (..., S) additive, broadcast over
    H. Returns (..., H, D) in q.dtype."""
    f32 = jnp.float32
    s = jnp.einsum("...hd,...sd->...hs", q.astype(f32), k.astype(f32)) * scale
    if mask is not None:
        s = s + mask.astype(f32)[..., None, :]
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("...hs,...sd->...hd", p, v.astype(f32)).astype(q.dtype)


def triangle_mult_ref(
    a_lin: jax.Array,
    ga: jax.Array,
    mask: jax.Array,
    b_full: jax.Array,
    gamma: jax.Array,
    beta: jax.Array,
    w_out: jax.Array,
    b_out: jax.Array,
    g_lin: jax.Array,
    g_bias: jax.Array,
    eps: float = 1e-5,
) -> jax.Array:
    """Materialized oracle for ops.fused_triangle_mult (the full fused
    triangular multiplicative update chain).

    a_lin, ga: (B, I, K, C) left projection / gate logits; mask: (B, I, K);
    b_full: (B, J, K, C) gated+masked right operand (gathered under DAP);
    gamma/beta: (C,) output LN; w_out: (C, D), b_out: (D,) output projection;
    g_lin: (B, I, J, D) output-gate logits (pre-bias), g_bias: (D,).

    out = sigmoid(g_lin + g_bias) * (LN_c(sum_k a·b) @ w_out + b_out) with
    a = (a_lin * sigmoid(ga)) * mask — fp32 accumulation/statistics, GEMM
    operands in the compute dtype. Materializes the full (B, I, J, C) fp32
    product; the fused legs keep it tile-bounded.
    """
    f32 = jnp.float32
    a = (a_lin.astype(f32) * jax.nn.sigmoid(ga.astype(f32))
         ).astype(a_lin.dtype) * mask.astype(a_lin.dtype)[..., None]
    o = jnp.einsum("bikc,bjkc->bijc", a, b_full, preferred_element_type=f32)
    mean = jnp.mean(o, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(o - mean), axis=-1, keepdims=True)
    y = ((o - mean) * jax.lax.rsqrt(var + eps) * gamma.astype(f32)
         + beta.astype(f32)).astype(a.dtype)
    z = jnp.einsum("bijc,cd->bijd", y, w_out.astype(a.dtype),
                   preferred_element_type=f32) + b_out.astype(f32)
    s = jax.nn.sigmoid(g_lin.astype(f32) + g_bias.astype(f32))
    return (s * z).astype(g_lin.dtype)


def outer_product_mean_ref(
    a: jax.Array,
    b_full: jax.Array,
    mask_a: jax.Array,
    mask_b: jax.Array,
    w: jax.Array,
    bias: jax.Array,
) -> jax.Array:
    """Materialized oracle for ops.fused_outer_product_mean.

    a: (B, S, I, C), b_full: (B, S, J, C) masked projections (b gathered
    under DAP); mask_a: (B, S, I), mask_b: (B, S, J); w: (C*C, D), bias (D,).

    out[b,i,j] = (vec(sum_s a_si ⊗ b_sj) / (norm_ij + 1e-3)) @ w + bias with
    norm = sum_s mask_a·mask_b — fp32 outer product and normalization.
    Materializes the full (B, I, J, C, C) transient; the fused legs keep it
    tile-bounded.
    """
    f32 = jnp.float32
    o = jnp.einsum("bsic,bsjd->bijcd", a, b_full, preferred_element_type=f32)
    norm = jnp.einsum("bsi,bsj->bij", mask_a.astype(f32), mask_b.astype(f32))
    ov = (o / (norm[..., None, None] + 1e-3)).astype(a.dtype)
    out = jnp.einsum("bijx,xd->bijd", ov.reshape(ov.shape[:3] + (-1,)),
                     w.astype(a.dtype), preferred_element_type=f32)
    return (out + bias.astype(f32)).astype(a.dtype)


def layer_norm_ref(
    x: jax.Array,
    gamma: jax.Array,
    beta: jax.Array,
    eps: float = 1e-5,
) -> jax.Array:
    """LayerNorm over the last axis with affine, fp32 statistics."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    y = y * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
    return y.astype(x.dtype)


def bias_sigmoid_mul_ref(g: jax.Array, bg: jax.Array, v: jax.Array) -> jax.Array:
    """sigmoid(g + bg) * v — the Evoformer gating fusion (paper §IV.A JIT fusion)."""
    gf = g.astype(jnp.float32) + bg.astype(jnp.float32)
    return (jax.nn.sigmoid(gf) * v.astype(jnp.float32)).astype(v.dtype)


def bias_dropout_add_ref(
    x: jax.Array,
    b: jax.Array,
    residual: jax.Array,
    keep: jax.Array | None,
    rate: float,
) -> jax.Array:
    """residual + dropout(x + b, rate). `keep` is a float 0/1 mask (same shape
    as x); keep=None => no dropout."""
    y = x.astype(jnp.float32) + b.astype(jnp.float32)
    if keep is not None and rate > 0.0:
        y = y * keep.astype(jnp.float32) / (1.0 - rate)
    return (residual.astype(jnp.float32) + y).astype(residual.dtype)
