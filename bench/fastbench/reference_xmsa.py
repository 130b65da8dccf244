"""Plain reference of AlphaFold-2 ``model_3``: the benchmarked trunk of
``reference.py`` with the extra-MSA stack in its place (Jumper et al. 2021,
Supplementary Information, Algorithm 2 lines 13-14, Algorithms 18 and 19),
in ``jax.numpy`` and float32.

It imports ``reference.py``'s layers and nothing of the system under test.
Every matrix product goes through ``Numerics.mm``, so the float8 control of
``reference.py`` applies unchanged. Departures from the published model,
beside those ``reference.py`` lists:

- the structure module is ``reference.py``'s CA-only variant;
- the clustered MSA enters as the 23-token one-hot of ``reference.py``, not
  as ``msa_feat``'s 49 channels (no cluster profile, no deletion features);
  the extra MSA's 25 channels are the published ones (23-token one-hot,
  ``has_deletion``, ``deletion_value``);
- as in the trunk, row attention masks keys by the residue mask, and the
  extra stack's dropout draws from ``fold_in(key, 1)`` (a fold has none).

Memory: the extra stack's row attention runs ``reference.gated_attention``
in chunks of 64 rows and its outer product mean in chunks of 64 residues,
so the reference fits one chip at 5120 extra rows.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from fastbench import reference as R
from fastbench.reference import F32, FP32, NEG_INF, Numerics, dense, \
    layer_norm

N_EXTRA_FEAT = R.N_MSA_TOK + 2


@dataclasses.dataclass(frozen=True)
class XDims(R.Dims):
    """``reference.Dims`` and the extra stack's sizes."""

    extra_msa_channel: int
    extra_msa_heads: int
    extra_msa_head_dim: int
    extra_msa_stack_num_block: int


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def _global_attn(c, heads, hd):
    return {"wq": R._dense(c, heads * hd, bias=False),
            "wkv": R._dense(c, 2 * hd, bias=False),
            "wg": {"w": ("fan_in", (c, heads * hd)),
                   "b": ("ones", (heads * hd,))},
            "wo": R._dense(heads * hd, c, zero=True)}


def extra_param_spec(d: XDims) -> dict:
    """The leaves that ``model_3`` adds to ``reference.param_spec``: the
    extra-MSA embedding and the stack's blocks, stacked on a leading axis.
    A block is the trunk's with 8 heads of 8 on the 64-wide MSA and global
    column attention; its pair side is the trunk's."""
    c, h, hd = d.extra_msa_channel, d.extra_msa_heads, d.extra_msa_head_dim
    block = R._block_spec(dataclasses.replace(d, d_msa=c))
    block["msa_row"]["attn"] = R._attn(c, h, hd, c)
    block["msa_row"]["bias"] = R._dense(d.d_pair, h, bias=False)
    block["msa_col"]["attn"] = _global_attn(c, h, hd)
    n = d.extra_msa_stack_num_block
    return {"extra_msa_embed": R._dense(N_EXTRA_FEAT, c),
            "extra_msa_stack": jax.tree.map(
                lambda leaf: (leaf[0], (n,) + leaf[1], 1), block,
                is_leaf=R._is_spec)}


def _draw(spec, key):
    """``reference.init_params``'s rule for each leaf of ``spec``."""
    leaves, tree = jax.tree.flatten(spec, is_leaf=R._is_spec)
    out = []
    for (kind, shape, *stacked), k in zip(leaves,
                                          jax.random.split(key, len(leaves))):
        own = shape[len(stacked):]
        fan_in = own[-2] if len(own) >= 2 else own[-1]
        if kind == "ones":
            out.append(jnp.ones(shape, F32))
        elif kind == "fan_in":
            out.append(jax.random.truncated_normal(k, -2.0, 2.0, shape, F32)
                       / math.sqrt(fan_in))
        else:
            out.append(R.SMALL_SCALE / math.sqrt(fan_in)
                       * jax.random.normal(k, shape, F32))
    return jax.tree.unflatten(tree, out)


def init_params(key, d: XDims):
    """The trunk's weights as ``reference.init_params`` draws them, and the
    extra leaves by the same rule from a second key. Run it under
    ``jax.jit``."""
    k_trunk, k_extra = jax.random.split(key)
    return {**R.init_params(k_trunk, d),
            **_draw(extra_param_spec(d), k_extra)}


# ---------------------------------------------------------------------------
# the extra-MSA stack
# ---------------------------------------------------------------------------


def embed_extra(params, batch, nx: Numerics):
    feat = jnp.concatenate([
        jax.nn.one_hot(batch["extra_msa"], R.N_MSA_TOK, dtype=F32),
        batch["extra_has_deletion"][..., None].astype(F32),
        batch["extra_deletion_value"][..., None].astype(F32)], axis=-1)
    return dense(params["extra_msa_embed"], feat, nx)


def global_column_attention(p, msa, msa_mask, d: XDims, nx: Numerics):
    """Algorithm 19 on msa (B, s, r, c): per column, the query is the mean
    of its unmasked rows, keys and values are one head shared by all heads,
    and each row's gate scales the column's one context."""
    h, hd = d.extra_msa_heads, d.extra_msa_head_dim
    x = layer_norm(p["ln"], msa).transpose(0, 2, 1, 3)        # (B, r, s, c)
    mask = msa_mask.transpose(0, 2, 1)                        # (B, r, s)
    b, r, s, _ = x.shape
    mean = (jnp.sum(x * mask[..., None], axis=2)
            / (jnp.sum(mask, axis=2)[..., None] + 1e-10))
    q = dense(p["attn"]["wq"], mean, nx).reshape(b, r, h, hd) / math.sqrt(hd)
    kv = dense(p["attn"]["wkv"], x, nx)
    k, v = kv[..., :hd], kv[..., hd:]
    logits = (nx.mm("brhd,brsd->brhs", q, k)
              + jnp.where(mask > 0, 0.0, NEG_INF)[:, :, None, :])
    ctx = nx.mm("brhs,brsd->brhd", jax.nn.softmax(logits, axis=-1), v)
    gate = jax.nn.sigmoid(dense(p["attn"]["wg"], x, nx))  # (B, r, s, h·hd)
    out = dense(p["attn"]["wo"], gate * ctx.reshape(b, r, 1, h * hd), nx)
    return out.transpose(0, 2, 1, 3)


def extra_block(p, msa, pair, msa_mask, seq_mask, d: XDims, nx, key):
    """Algorithm 18's block: row attention with pair bias at 8 heads of 8,
    global column attention, the MSA transition, the outer product mean
    from the extra MSA and the trunk's pair side."""
    keys = (list(jax.random.split(key, 8)) if key is not None
            else [None] * 8)
    pair_mask = seq_mask[:, :, None] * seq_mask[:, None, :]
    t = lambda x: x.swapaxes(1, 2)  # noqa: E731  (pair transpose i <-> j)
    pr = p["msa_row"]
    bias = dense(pr["bias"], layer_norm(pr["ln_z"], pair), nx)
    b, s, r, _ = msa.shape
    upd = R.gated_attention(pr["attn"], layer_norm(pr["ln_m"], msa),
                            bias.transpose(0, 3, 1, 2),
                            jnp.broadcast_to(seq_mask[:, None, :], (b, s, r)),
                            d.extra_msa_heads, d.extra_msa_head_dim, nx)
    msa = R._dropout_add(upd, msa, d.dropout_msa, keys[0], 2)
    msa = msa + global_column_attention(p["msa_col"], msa, msa_mask, d, nx)
    msa = msa + R.transition(p["msa_trans"], msa, nx)
    pair = R._dropout_add(R.outer_product_mean(p["opm"], msa, msa_mask, d,
                                               nx),
                          pair, d.dropout_pair, keys[1], 1)
    pair = R._dropout_add(R.triangle_mult(p["tri_mult_out"], pair, pair_mask,
                                          d, nx),
                          pair, d.dropout_pair, keys[2], 1)
    pair = R._dropout_add(t(R.triangle_mult(p["tri_mult_in"], t(pair),
                                            t(pair_mask), d, nx)),
                          pair, d.dropout_pair, keys[3], 1)
    pair = R._dropout_add(R.triangle_attention(p["tri_attn_start"], pair,
                                               seq_mask, d, nx),
                          pair, d.dropout_pair, keys[4], 1)
    pair = R._dropout_add(t(R.triangle_attention(p["tri_attn_end"], t(pair),
                                                 seq_mask, d, nx)),
                          pair, d.dropout_pair, keys[5], 2)
    pair = pair + R.transition(p["pair_trans"], pair, nx)
    return msa, pair


def extra_stack(params, batch, pair, d: XDims, nx, key):
    """The extra MSA embedded and run through the stack's blocks; only the
    pair comes out."""
    msa = embed_extra(params, batch, nx)
    n = d.extra_msa_stack_num_block
    keys = (jax.random.split(key, n) if key is not None
            else jnp.zeros((n, 2), jnp.uint32))

    def body(carry, xs):
        p, k = xs
        return extra_block(p, *carry, batch["extra_msa_mask"],
                           batch["seq_mask"], d, nx,
                           k if key is not None else None), None

    (_, pair), _ = jax.lax.scan(body, (msa, pair),
                                (params["extra_msa_stack"], keys))
    return pair


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------


def _iteration(params, batch, prev, d: XDims, nx, key):
    """``reference._iteration`` with the extra stack between the recycling
    embedding and the trunk (Algorithm 2)."""
    oh = lambda x, n: jax.nn.one_hot(x, n, dtype=F32)  # noqa: E731
    aa = oh(batch["aatype"], R.N_AA)
    msa = (dense(params["msa_embed"], oh(batch["msa"], R.N_MSA_TOK), nx)
           + dense(params["target_embed_m"], aa, nx)[:, None])
    pair = (dense(params["left_embed"], aa, nx)[:, :, None]
            + dense(params["right_embed"], aa, nx)[:, None])
    ri = batch["residue_index"]
    rel = jnp.clip(ri[:, :, None] - ri[:, None, :], -R.RELPOS_K,
                   R.RELPOS_K) + R.RELPOS_K
    pair = pair + dense(params["relpos_embed"], oh(rel, 2 * R.RELPOS_K + 1),
                        nx)

    prev_m, prev_z, prev_x = prev
    rp = params["recycle"]
    msa = msa.at[:, 0].add(layer_norm(rp["ln_m"], prev_m))
    pair = pair + layer_norm(rp["ln_z"], prev_z)
    dist = jnp.linalg.norm(prev_x[:, :, None] - prev_x[:, None] + 1e-8,
                           axis=-1)
    edges = jnp.linspace(3.375, 21.375, d.recycle_bins - 1)
    bins = jnp.sum(dist[..., None] > edges, axis=-1)
    pair = pair + dense(rp["dist_embed"], oh(bins, d.recycle_bins), nx)

    pair = extra_stack(params, batch, pair, d, nx,
                       None if key is None else jax.random.fold_in(key, 1))

    msa_mask, seq_mask = batch["msa_mask"], batch["seq_mask"]
    keys = (jax.random.split(key, d.n_blocks) if key is not None
            else jnp.zeros((d.n_blocks, 2), jnp.uint32))

    def body(carry, xs):
        p, k = xs
        return R.evoformer_block(p, *carry, msa_mask, seq_mask, d, nx,
                                 k if key is not None else None), None

    (msa, pair), _ = jax.lax.scan(body, (msa, pair),
                                  (params["evoformer"], keys))
    single = dense(params["single_proj"], msa[:, 0], nx)
    coords, frames, traj = R.structure_module(params["structure"], single,
                                              pair, seq_mask, d, nx)
    return {"msa_first_row": msa[:, 0], "pair": pair, "coords": coords,
            "frames": frames, "traj": traj,
            "msa_logits": dense(params["msa_head"], msa, nx),
            "distogram_logits": dense(params["dist_head"], pair, nx)}


def forward(params, batch, d: XDims, nx: Numerics = FP32, key=None):
    """The fold: ``n_recycle`` passes, then the last one."""
    b, _, r = batch["msa"].shape
    prev = (jnp.zeros((b, r, d.d_msa), F32),
            jnp.zeros((b, r, r, d.d_pair), F32), jnp.zeros((b, r, 3), F32))

    def body(_, prev):
        out = _iteration(params, batch, prev, d, nx, key)
        return out["msa_first_row"], out["pair"], out["coords"]

    prev = jax.lax.stop_gradient(jax.lax.fori_loop(0, d.n_recycle, body,
                                                   prev))
    return _iteration(params, batch, prev, d, nx, key)
