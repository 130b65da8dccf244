"""Tensor-Parallel Evoformer — the paper's baseline (§IV.B.1, Table III).

Megatron-style column/row parallelism applied to Evoformer, exactly as the
paper describes for its comparison: QKV+gate projections column-parallel
(heads split across the `model` axis), output projection row-parallel with an
AllReduce; transitions column/row-parallel with an AllReduce. Outer-Product-
Mean and the Triangular Updates are NOT parallelizable under TP (paper Table
III) and run replicated.

Uses the *same parameter pytree* as the DAP/local Evoformer, slicing weights
per device inside shard_map — so the comparison is apples-to-apples, and the
equivalence test (TP output == local output) certifies correctness.

Scaling limit reproduced: the pair stack has 4 heads, so TP cannot exceed 4
devices there (the paper's core argument for DAP).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import evoformer as evo
from repro.core.dist import LocalDist, batch_spec, unchecked_shard_map
from repro.kernels import ops
from repro.layers.attention import evoformer_attention
from repro.layers.norms import layer_norm
from repro.layers.params import dense

NEG_INF = -1e9


def _slice_cols(w, idx, n, groups: int = 1):
    """Column-slice a (d_in, groups*h*hd) weight into its per-device block,
    slicing each of `groups` equal segments (q|k|v merged layout)."""
    d_in, d_out = w.shape
    seg = d_out // groups
    loc = seg // n
    parts = [
        jax.lax.dynamic_slice_in_dim(w, g * seg + idx * loc, loc, axis=1)
        for g in range(groups)
    ]
    return jnp.concatenate(parts, axis=1)


def _slice_vec(b, idx, n, groups: int = 1):
    seg = b.shape[0] // groups
    loc = seg // n
    parts = [
        jax.lax.dynamic_slice_in_dim(b, g * seg + idx * loc, loc, axis=0)
        for g in range(groups)
    ]
    return jnp.concatenate(parts, axis=0)


def tp_gated_attention(p_attn, x_n, bias, key_mask, heads, head_dim, axis):
    """Column-parallel QKV/gate, row-parallel output + AllReduce."""
    idx = jax.lax.axis_index(axis)
    n = jax.lax.axis_size(axis)
    h_loc = heads // n
    dt = x_n.dtype

    wqkv = _slice_cols(p_attn["wqkv"]["w"], idx, n, groups=3).astype(dt)
    y = jnp.einsum("nsd,de->nse", x_n, wqkv)
    if "b" in p_attn["wqkv"]:
        y = y + _slice_vec(p_attn["wqkv"]["b"], idx, n, groups=3).astype(dt)
    q, k, v = jnp.split(y, 3, axis=-1)
    q = q.reshape(q.shape[:-1] + (h_loc, head_dim))
    k = k.reshape(k.shape[:-1] + (h_loc, head_dim))
    v = v.reshape(v.shape[:-1] + (h_loc, head_dim))

    bias_loc = None
    if bias is not None:  # (B, H, R, C) -> local heads
        bias_loc = jax.lax.dynamic_slice_in_dim(bias, idx * h_loc, h_loc, axis=1)
    mask = None
    if key_mask is not None:
        mask = jnp.where(key_mask > 0, 0.0, NEG_INF).astype(jnp.float32)
    ctx = evoformer_attention(q, k, v, bias=bias_loc, mask=mask)
    flat = ctx.reshape(ctx.shape[:-2] + (-1,))

    if "wg" in p_attn:
        wg = _slice_cols(p_attn["wg"]["w"], idx, n).astype(dt)
        g = jnp.einsum("nsd,de->nse", x_n, wg)
        flat = ops.bias_sigmoid_mul(g, _slice_vec(p_attn["wg"]["b"], idx, n), flat)

    wo_loc = jax.lax.dynamic_slice_in_dim(
        p_attn["wo"]["w"], idx * h_loc * head_dim, h_loc * head_dim, axis=0
    ).astype(dt)
    out = jnp.einsum("nse,eo->nso", flat, wo_loc)
    out = jax.lax.psum(out, axis)  # the TP AllReduce (paper Table III)
    if "b" in p_attn["wo"]:
        out = out + p_attn["wo"]["b"].astype(dt)
    return out


def tp_transition(p, x, axis):
    """Column-parallel first linear, row-parallel second + AllReduce."""
    idx = jax.lax.axis_index(axis)
    n = jax.lax.axis_size(axis)
    x_n = layer_norm(p["ln"], x)
    dt = x_n.dtype
    wi = _slice_cols(p["mlp"]["wi"]["w"], idx, n).astype(dt)
    bi = _slice_vec(p["mlp"]["wi"]["b"], idx, n).astype(dt)
    h = jax.nn.relu(jnp.einsum("...d,de->...e", x_n, wi) + bi)
    d_ff = p["mlp"]["wo"]["w"].shape[0]
    loc = d_ff // n
    wo = jax.lax.dynamic_slice_in_dim(p["mlp"]["wo"]["w"], idx * loc, loc,
                                      axis=0).astype(dt)
    out = jax.lax.psum(jnp.einsum("...e,eo->...o", h, wo), axis)
    return out + p["mlp"]["wo"]["b"].astype(dt)


def tp_evoformer_block(params, msa, pair, msa_mask, seq_mask, pair_mask, *,
                       cfg: evo.EvoformerConfig, axis="model"):
    """TP block: tensors replicated across `axis`, weights logically split."""
    b, s, r, _ = msa.shape
    local = LocalDist()

    # --- MSA row attention (TP over heads) ---
    p = params["msa_row"]
    z_n = layer_norm(p["ln_z"], pair)
    bias = dense(p["bias"], z_n).transpose(0, 3, 1, 2)  # (B, H, r, r)
    m_n = layer_norm(p["ln_m"], msa)
    x = m_n.reshape(b * s, r, cfg.d_msa)
    key_mask = jnp.broadcast_to(seq_mask[:, None, :], (b, s, r)).reshape(b * s, r)
    upd = tp_gated_attention(p["attn"], x, bias, key_mask, cfg.msa_heads,
                             cfg.head_dim, axis)
    msa = msa + upd.reshape(b, s, r, cfg.d_msa)

    # --- MSA column attention ---
    p = params["msa_col"]
    m_n = layer_norm(p["ln"], msa)
    x = m_n.transpose(0, 2, 1, 3).reshape(b * r, s, cfg.d_msa)
    key_mask = msa_mask.transpose(0, 2, 1).reshape(b * r, s)
    upd = tp_gated_attention(p["attn"], x, None, key_mask, cfg.msa_heads,
                             cfg.head_dim, axis)
    msa = msa + upd.reshape(b, r, s, cfg.d_msa).transpose(0, 2, 1, 3)

    msa = msa + tp_transition(params["msa_trans"], msa, axis)

    # --- OPM + triangular updates: NOT TP-parallelizable (replicated) ---
    pair = pair + evo.outer_product_mean(params["opm"], msa, msa_mask, local, cfg)
    pair = pair + evo.triangle_mult_outgoing(params["tri_mult_out"], pair,
                                             pair_mask, local, cfg)
    pair_t = pair.swapaxes(1, 2)
    pair_mask_t = pair_mask.swapaxes(1, 2)
    pair = pair + evo.triangle_mult_incoming(params["tri_mult_in"], pair,
                                             pair_t, pair_mask_t, local, cfg)

    # --- Triangular attentions (TP over the 4 pair heads) ---
    for name, transpose in (("tri_attn_start", False), ("tri_attn_end", True)):
        p = params[name]
        src = pair.swapaxes(1, 2) if transpose else pair
        z_n = layer_norm(p["ln"], src)
        bias = dense(p["bias"], z_n).transpose(0, 3, 1, 2)
        x = z_n.reshape(b * r, r, cfg.d_pair)
        key_mask = jnp.broadcast_to(seq_mask[:, None, :], (b, r, r)).reshape(b * r, r)
        upd = tp_gated_attention(p["attn"], x, bias, key_mask, cfg.pair_heads,
                                 cfg.head_dim, axis)
        upd = upd.reshape(b, r, r, cfg.d_pair)
        pair = pair + (upd.swapaxes(1, 2) if transpose else upd)

    pair = pair + tp_transition(params["pair_trans"], pair, axis)
    return msa, pair


def tp_evoformer_stack(mesh, cfg: evo.EvoformerConfig, *, remat: bool = True):
    """jit-able TP stack: activations replicated over 'model', batch over data
    axes. Scaling limit: model axis size must divide pair_heads (=4)."""
    bspec = P(batch_spec(mesh))

    def local_fn(params, msa, pair, msa_mask, seq_mask, pair_mask):
        def body(carry, p):
            m, z = carry
            m, z = tp_evoformer_block(p, m, z, msa_mask, seq_mask, pair_mask,
                                      cfg=cfg)
            return (m, z), None

        if remat:
            body = jax.checkpoint(body,
                                  policy=jax.checkpoint_policies.nothing_saveable)
        (m, z), _ = jax.lax.scan(body, (msa, pair), params)
        return m, z

    b4 = P(batch_spec(mesh), None, None, None)
    b3 = P(batch_spec(mesh), None, None)
    b2 = P(batch_spec(mesh), None)
    return unchecked_shard_map(local_fn, mesh, (P(), b4, b4, b3, b2, b3),
                               (b4, b4))
