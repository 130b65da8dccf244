"""Evoformer (AlphaFold 2 trunk) with Dynamic Axial Parallelism.

One implementation, three execution modes via the ``dist`` backend
(core/dist.py). Sharding state machine (shard_map local view), following
paper Fig. 6:

  MSA rep   (B, s, r, Hm): sharded on **s** during row ops, swapped to **r**
            by all_to_all for column attention + Outer-Product-Mean, swapped
            back *after* OPM consumes it — the swap-back is launched before
            the pair stack and consumed at the next block's row attention,
            which is exactly the paper's Duality-Async overlap window.
  pair rep  (B, i, j, Hz): sharded on **i**; "incoming"/"ending-node" ops run
            on the all_to_all-transposed tensor (an axis swap, 1/N^2 volume).
  AllGather materializes cross-axis operands: OPM right projection, triangular
  left/right projections, and the (H, r, r) attention bias tensors.

Kernel usage (paper §IV.A + ScaleFold's fused-attention extension): all four
attention sites (MSA row, MSA col, triangle start/end) go through the
flash-style fused gated-attention Pallas kernel (``ops.fused_attention``) —
online softmax over KV tiles, so the (B, G, H, R, R) scores tensor never
reaches HBM. The pair stack's remaining hot paths go through the fused
triangle/OPM kernels (kernels/triangle.py): both triangular multiplicative
updates route ``dist.sharded_triangle`` (k-tiled product with the input
gating, pair mask, output LayerNorm and output gate fused into one sweep —
the (B, i, j, c) fp32 product never hits HBM at full size) and the
Outer-Product-Mean routes ``dist.sharded_opm`` (s-tiled outer product with
the fp32 mask-normalization and c²→d projection fused — no (B, i, j, c, c)
transient). Leg selection rides the context-local ExecutionPlan
(repro.exec.plan): ``KernelPolicy(enabled=False)`` (or out-of-envelope
shapes) sends every site to its materialized jnp path, kept for A/B and
diagnosis; ``KernelPolicy(triangle='oracle', opm='oracle')`` pins just the
triangle/OPM ops. All LayerNorms go through the fused LN kernel; gating
through bias+sigmoid+mul; residual adds through bias+dropout+add with the
AlphaFold shared-axis dropout mask. Left/right projections use merged
GEMMs; QKV keeps one merged weight and runs one GEMM per output
(``_project_heads``), the flash kernel's layout.

Chunk knobs (``inference_chunk``, ``opm_chunk``, ``attn_kv_tile``,
``tri_k_tile``, ``opm_s_tile``) default to 0 = off/kernel-default; the
AutoChunk planner (repro.memory.autochunk) fills them from the HBM budget at
the alphafold_forward level instead of hand-set constants.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import duality
from repro.core.dist import LocalDist
from repro.exec.plan import current_plan
from repro.kernels import ops
from repro.layers.attention import evoformer_attention, init_attention, AttnDims, \
    output_proj
from repro.layers.mlp import init_transition, transition
from repro.layers.norms import init_layer_norm, layer_norm
from repro.layers.params import Params, dense, init_dense

NEG_INF = -1e9


@dataclass(frozen=True)
class EvoformerConfig:
    d_msa: int = 256
    d_pair: int = 128
    msa_heads: int = 8
    pair_heads: int = 4
    head_dim: int = 32
    # The extra-MSA block variant: MSA column attention is global attention
    # (SI Alg. 19), one mean query per column against one key and one value
    # head shared by all heads (``msa_col_global_attention``).
    global_column: bool = False
    opm_dim: int = 32
    tri_mult_dim: int = 128
    transition_factor: int = 4
    dropout_msa: float = 0.15
    dropout_pair: float = 0.25
    n_blocks: int = 48
    compute_dtype: Any = jnp.bfloat16
    # remat policy for the block scan: "nothing" (recompute all, min memory)
    # or "dots" (save GEMM outputs: less recompute, more activation memory).
    remat_policy: str = "nothing"
    # OPM j-chunking: compute the (i, j, 32, 32) outer-product intermediate
    # in j-chunks of this size (0 = whole row at once). Shrinks the dominant
    # (B, i/N, r, 1024) intermediate by r/chunk (§Perf alphafold iter 2).
    opm_chunk: int = 0
    # Inference "chunking technique" (paper §V.C): the single-device fallback
    # AlphaFold/OpenFold use for long sequences — attention rows processed in
    # sequential chunks, capping the (G, H, r, r) transient. 0 = off. The
    # paper's point (Figs 12-13, Table V) is that DAP beats this; we implement
    # both so the comparison is ours to measure.
    inference_chunk: int = 0
    # KV tile for the fused flash-attention kernel (and its backward
    # recompute block). 0 = kernel default (512). Bounds the per-tile
    # attention transient at (B, G, H, r, kv_tile) instead of r^2.
    attn_kv_tile: int = 0
    # Tile of the fused triangle-multiplication kernel: the Pallas grid's k
    # accumulation tile and the XLA leg's / backward recompute's j output
    # block. 0 = leg default (Pallas 128, a multiple of the TPU lane width;
    # XLA/backward j block 128 — the HBM-visible transient the planner
    # models). Bounds the fp32 product transient at (B, i_loc, tile, c)
    # instead of (B, i_loc, r, c).
    tri_k_tile: int = 0
    # Tile of the fused outer-product-mean kernel: Pallas s accumulation
    # tile / XLA-leg j output block / backward recompute block. 0 = leg
    # default (Pallas 128, XLA/backward 128). Bounds the fp32 outer-product
    # transient at (B, i_loc, tile, c_opm^2).
    opm_s_tile: int = 0
    # Let the AutoChunk planner (repro.memory.autochunk) fill any chunk knob
    # left at 0 from the HBM budget — resolved once per forward at the
    # alphafold_forward level (trace-time, static shapes). Hand-set nonzero
    # knobs are always respected.
    auto_chunk: bool = True

    @property
    def msa_head_dim(self) -> int:
        """Head width of the two MSA attention sites, ``d_msa / msa_heads``
        as in AlphaFold-2: 32 in the trunk (256 / 8), 8 in the extra-MSA
        stack (64 / 8, SI Alg. 18), whose pair side keeps ``head_dim``."""
        return self.d_msa // self.msa_heads


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_evoformer_block(key, cfg: EvoformerConfig) -> Params:
    ks = iter(jax.random.split(key, 24))
    d_m, d_z = cfg.d_msa, cfg.d_pair
    hm, hz, hd = cfg.msa_heads, cfg.pair_heads, cfg.head_dim
    hd_m = cfg.msa_head_dim
    c_mult = cfg.tri_mult_dim

    def attn(d_in, heads, d_out, head_dim=hd):
        return init_attention(
            next(ks), d_in, heads, heads, head_dim, gating=True,
            out_bias=True, d_out=d_out,
        )

    def tri_mult():
        return {
            "ln_in": init_layer_norm(d_z),
            # Merge GEMM (paper §IV.A.1): left+right projections and
            # left+right gates each fused into one weight.
            "proj": init_dense(next(ks), d_z, 2 * c_mult, bias=True),
            "gate": init_dense(next(ks), d_z, 2 * c_mult, bias=True),
            "ln_out": init_layer_norm(c_mult),
            "out": init_dense(next(ks), c_mult, d_z, bias=True, zero_init=True),
            "gate_out": init_dense(next(ks), d_z, d_z, bias=True),
        }

    def tri_attn():
        return {
            "ln": init_layer_norm(d_z),
            "bias": init_dense(next(ks), d_z, hz, bias=False),
            "attn": attn(d_z, hz, d_z),
        }

    return {
        "msa_row": {
            "ln_m": init_layer_norm(d_m),
            "ln_z": init_layer_norm(d_z),
            "bias": init_dense(next(ks), d_z, hm, bias=False),
            "attn": attn(d_m, hm, d_m, hd_m),
        },
        "msa_col": {"ln": init_layer_norm(d_m),
                    "attn": (init_global_attention(next(ks), d_m, hm, hd_m)
                             if cfg.global_column
                             else attn(d_m, hm, d_m, hd_m))},
        "msa_trans": {"ln": init_layer_norm(d_m),
                      "mlp": init_transition(next(ks), d_m, cfg.transition_factor)},
        "opm": {
            "ln": init_layer_norm(d_m),
            "proj": init_dense(next(ks), d_m, 2 * cfg.opm_dim, bias=True),
            "out": init_dense(next(ks), cfg.opm_dim * cfg.opm_dim, d_z,
                              bias=True, zero_init=True),
        },
        "tri_mult_out": tri_mult(),
        "tri_mult_in": tri_mult(),
        "tri_attn_start": tri_attn(),
        "tri_attn_end": tri_attn(),
        "pair_trans": {"ln": init_layer_norm(d_z),
                       "mlp": init_transition(next(ks), d_z, cfg.transition_factor)},
    }


def init_global_attention(key, d_in: int, heads: int, head_dim: int) -> Params:
    """Global column attention (SI Alg. 19): a per-head query projection of
    the column's mean, one key and one value head shared by all heads
    (merged), the per-element gate (bias 1: gates start open) and the output
    projection. The gate and output keys are ``init_attention``'s, so
    ``output_proj`` applies them."""
    kq, kkv, kg, ko = jax.random.split(key, 4)
    gate = init_dense(kg, d_in, heads * head_dim, bias=True)
    gate["b"] = jnp.ones_like(gate["b"])
    return {
        "wq": init_dense(kq, d_in, heads * head_dim, bias=False),
        "wkv": init_dense(kkv, d_in, 2 * head_dim, bias=False),
        "wg": gate,
        "wo": init_dense(ko, heads * head_dim, d_in, bias=True,
                         zero_init=True),
    }


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------

def _residual_add(upd, residual, rate: float, rng, shared_axis: int,
                  train: bool):
    """AlphaFold shared-axis residual add: residual + dropout(upd) with one
    Bernoulli draw broadcast along ``shared_axis`` (row/column dropout),
    fused into the bias+dropout+add kernel in one HBM pass (paper §IV.A.1
    "JIT Fusion" residual chain). Under shard_map the mask is shared within
    the local shard when the shared axis is the sharded one
    (stochastic-regularization-equivalent; exact equivalence across dist
    modes is tested with dropout disabled)."""
    use_dropout = train and rate > 0.0 and rng is not None
    return ops.bias_dropout_add(
        upd, None, residual,
        rate=rate if use_dropout else 0.0,
        rng=rng if use_dropout else None,
        shared_axes=(shared_axis,),
    )


def _project_heads(p_attn, x, dims: AttnDims):
    """x (..., S, d) -> q (..., S, H, hd), k/v (..., S, KV, hd): one GEMM per
    output on the merged ``wqkv`` weight's columns. The flash kernel reads
    each of q, k and v whole as (..., S, H·hd), a free reshape of its own
    GEMM's output; a slice of one merged output would be a copy pass over
    all three (the weight's column slices are a few hundred KB here)."""
    h, kv, hd = dims
    cols = {name: jnp.split(a, [h * hd, (h + kv) * hd], axis=-1)
            for name, a in p_attn["wqkv"].items()}          # w, and b if any
    return tuple(
        dense({name: a[i] for name, a in cols.items()}, x).reshape(
            x.shape[:-1] + (heads, hd))
        for i, heads in enumerate((h, kv, kv)))


def _gated_attention(p_attn, x_n, bias, key_mask, dims: AttnDims,
                     dist=LocalDist(), chunk: int = 0, kv_tile: int = 0):
    """Group attention, kept 5D so (batch, group) dims never merge — merging
    two mesh-sharded dims would force an all-gather under GSPMD.

    x_n: (B, G, S, d); bias (B, H, S, S) shared across G, or None;
    key_mask (B, G, S) in {0,1}, or None. The G (group) dim carries the DAP
    shard; the q/ctx (fused path) or scores/probs (fallback) constraints pin
    it through the backward recompute regions, where plain propagation loses
    it.

    Fused path (default): ``dist.sharded_attention`` — the kernel-side
    sharding hook (core/dist.py). LocalDist/ShardMapDist call
    ops.fused_attention on the (already local) block; GspmdDist shard_maps
    the kernel over (batch_axes, 'model') so each device runs it on its
    local (B_loc, G_loc, S, H, D) shard with the gathered bias replicated —
    the production path executes the fused kernel instead of falling back.
    With kernels disabled on the plan (KernelPolicy(enabled=False) /
    attention='oracle'), out-of-envelope shapes, or a group dim that doesn't
    divide the mesh, the scores-materialized path below runs instead (A/B
    baseline; it never merges the (B, G) dims either).

    chunk > 0: the paper-§V.C chunking technique — G processed in sequential
    chunks, capping the attention transient at (B, chunk, H, S, *). Inference
    fallback only (trades latency for memory; DAP is the scalable answer).
    """
    def attend(x_c, mask_c):
        q, k, v = _project_heads(p_attn, x_c, dims)
        hd = q.shape[-1]
        scale = 1.0 / (hd**0.5)
        mask = None
        bias_w = bias
        if bias_w is not None:
            # Duality-Async window: fence the gathered pair bias with the QKV
            # projection so the gather cannot sink past the independent GEMMs
            # to its consumer below (core/duality.py). q passes the fence
            # with its heads merged, (..., S, H·D): the flash kernel's
            # layout, so the barrier hands it on without a relayout copy.
            bias_w, q_m = duality.overlap_window(
                bias_w, q.reshape(q.shape[:-2] + (-1,)))
            q = q_m.reshape(q.shape)
        if mask_c is not None:
            mask = jnp.where(mask_c > 0, 0.0, NEG_INF).astype(jnp.float32)
        if (ops.fused_attention_supported(q.shape, kv_len=k.shape[2],
                                          dtype=q.dtype)
                and dist.sharded_attention_supported(q.shape)):
            spec = ("b", "m", None, None, None)
            q = dist.constrain(q, spec)
            k = dist.constrain(k, spec)
            v = dist.constrain(v, spec)
            ctx = dist.sharded_attention(q, k, v, bias=bias_w, mask=mask,
                                         scale=scale, kv_tile=kv_tile)
            ctx = dist.constrain(ctx, spec)
        else:
            # Sanctioned scores-materialized A/B fallback (oracle leg /
            # out-of-envelope shapes); the fused path above is production.
            # repro-lint: disable=R004
            scores = jnp.einsum("bgihd,bgjhd->bghij", q, k)
            scores = dist.constrain(scores, ("b", "m", None, None, None))
            # allow_flatten: under GspmdDist the (B, G) dims are mesh-sharded
            # GLOBAL dims — the softmax must not merge them even on TPU.
            probs = ops.fused_softmax(scores, bias=bias_w, mask=mask,
                                      scale=scale,
                                      allow_flatten=dist.local_tensors)
            probs = dist.constrain(probs, ("b", "m", None, None, None))
            ctx = jnp.einsum("bghij,bgjhd->bgihd", probs,
                             v)  # repro-lint: disable=R004 -- same fallback
        return output_proj(p_attn, ctx, x_for_gate=x_c)

    g = x_n.shape[1]
    if not chunk or g % chunk != 0 or chunk >= g:
        return attend(x_n, key_mask)
    nc = g // chunk

    def split(t):
        return t.reshape(t.shape[0], nc, chunk, *t.shape[2:]).swapaxes(0, 1)

    if key_mask is None:
        out = jax.lax.map(lambda x: attend(x, None), split(x_n))
    else:
        out = jax.lax.map(lambda xm: attend(xm[0], xm[1]),
                          (split(x_n), split(key_mask)))
    return out.swapaxes(0, 1).reshape(x_n.shape[0], g, *out.shape[3:])


# ---------------------------------------------------------------------------
# Sub-modules (all take *local* tensors per the sharding state machine)
# ---------------------------------------------------------------------------

def msa_row_attention(p, msa, pair, seq_mask, dist, cfg: EvoformerConfig):
    """msa (B, s/N, r, Hm) [s-shard]; pair (B, i/N, j, Hz) [i-shard];
    seq_mask (B, r) replicated."""
    b, s_loc, r, _ = msa.shape
    dims = AttnDims(cfg.msa_heads, cfg.msa_heads, cfg.msa_head_dim)
    # Pair bias: project local pair rows -> (B, i/N, j, H) -> gather rows.
    z_n = layer_norm(p["ln_z"], pair)
    bias_loc = dense(p["bias"], z_n)                      # (B, i/N, j, H)
    bias_loc = bias_loc.transpose(0, 3, 1, 2)             # (B, H, i/N, j)
    bias = dist.all_gather(bias_loc, axis=2)              # (B, H, r, r)
    bias = dist.constrain(bias, ("b", None, None, None))
    # Duality-async window: the gather result is first consumed *after* the
    # QKV projection below — independent compute the scheduler can overlap.
    m_n = layer_norm(p["ln_m"], msa)
    key_mask = jnp.broadcast_to(seq_mask[:, None, :], (b, s_loc, r))
    return _gated_attention(p["attn"], m_n, bias, key_mask, dims,
                            dist=dist, chunk=cfg.inference_chunk,
                            kv_tile=cfg.attn_kv_tile)


def msa_col_attention(p, msa, msa_mask, dist, cfg: EvoformerConfig):
    """msa (B, s, r/N, Hm) [r-shard]; msa_mask (B, s, r/N)."""
    b, s, r_loc, _ = msa.shape
    dims = AttnDims(cfg.msa_heads, cfg.msa_heads, cfg.msa_head_dim)
    m_n = layer_norm(p["ln"], msa)
    x = m_n.transpose(0, 2, 1, 3)                  # (B, r/N, s, d)
    key_mask = msa_mask.transpose(0, 2, 1)         # (B, r/N, s)
    out = _gated_attention(p["attn"], x, None, key_mask, dims,
                           dist=dist, chunk=cfg.inference_chunk,
                           kv_tile=cfg.attn_kv_tile)
    return out.transpose(0, 2, 1, 3)


def msa_col_global_attention(p, msa, msa_mask, dist, cfg: EvoformerConfig):
    """Global column attention (SI Alg. 19) on msa (B, s, r/N, c) [r-shard];
    msa_mask (B, s, r/N). Each column's query is the masked mean of its
    LN'ed rows, projected per head; keys and values are one head shared by
    all heads, so a column costs O(s), not the O(s²) of column attention
    (``ops.global_attention``). The sigmoid gate is per element, so the
    column's one context is gated row by row before the output projection.
    Columns are independent: under DAP's r-shard nothing is exchanged."""
    del dist
    h, hd = cfg.msa_heads, cfg.msa_head_dim
    x = layer_norm(p["ln"], msa).transpose(0, 2, 1, 3)      # (B, r/N, s, c)
    mask = msa_mask.transpose(0, 2, 1).astype(jnp.float32)  # (B, r/N, s)
    pa = p["attn"]
    mean = (jnp.sum(x.astype(jnp.float32) * mask[..., None], axis=2)
            / (jnp.sum(mask, axis=-1, keepdims=True) + 1e-10))
    q = dense(pa["wq"], mean.astype(x.dtype))
    q = q.reshape(q.shape[:-1] + (h, hd))                   # (B, r/N, H, hd)
    k, v = jnp.split(dense(pa["wkv"], x), 2, axis=-1)       # (B, r/N, s, hd)
    ctx = ops.global_attention(q, k, v,
                               mask=jnp.where(mask > 0, 0.0, NEG_INF),
                               scale=1.0 / (hd ** 0.5))
    ctx = jnp.broadcast_to(ctx[:, :, None], x.shape[:3] + (h, hd))
    return output_proj(pa, ctx, x_for_gate=x).transpose(0, 2, 1, 3)


def msa_transition(p, msa):
    return transition(p["mlp"], layer_norm(p["ln"], msa))


def outer_product_mean(p, msa, msa_mask, dist, cfg: EvoformerConfig):
    """msa (B, s, r/N, Hm) [r-shard] -> pair update (B, i/N, j, Hz) [i-shard].

    Paper Fig. 6(b): the cross-axis operand is AllGathered; we gather the
    *right* projection so the output lands i-sharded, matching the pair rep.
    """
    c = cfg.opm_dim
    m_n = layer_norm(p["ln"], msa)
    ab = dense(p["proj"], m_n)                    # merged GEMM (B, s, r/N, 2c)
    a, bproj = jnp.split(ab, 2, axis=-1)
    mask = msa_mask[..., None].astype(a.dtype)
    a = a * mask
    bproj = bproj * mask
    b_full = dist.all_gather(bproj, axis=2)       # (B, s, r, c)
    b_full = dist.constrain(b_full, ("b", None, None, None))
    mask_full = dist.all_gather(msa_mask, axis=2)  # (B, s, r)
    # Duality-Async window: keep the left-projection operand inside the
    # gather's launch->use window (it is independent of the gather).
    b_full, a = duality.overlap_window(b_full, a)

    # Fused path (default): dist.sharded_opm — s-tiled accumulation of the
    # outer product with the fp32 mask-normalization and c²→Hz projection
    # fused, so the (B, i/N, r, c, c) transient never hits HBM at full size.
    # GspmdDist shard_maps the op over (batch_axes, 'model') with b_full
    # replicated. The j-chunked jnp path below stays as the A/B baseline
    # (plan legs: KernelPolicy(enabled=False) or opm='oracle').
    if (ops.fused_opm_supported(c, p["out"]["w"].shape[1], a.dtype)
            and dist.sharded_opm_supported(a.shape[2])):
        return dist.sharded_opm(a, b_full, msa_mask, mask_full,
                                p["out"]["w"], p["out"]["b"],
                                tile=cfg.opm_s_tile)

    def opm_block(b_blk, mask_blk):
        # repro-lint: disable=R004 -- sanctioned j-chunked OPM baseline
        o = jnp.einsum("bsic,bsjd->bijcd", a, b_blk)  # (B, r/N, jc, c, c)
        norm = jnp.einsum("bsi,bsj->bij", msa_mask,
                          mask_blk)  # repro-lint: disable=R004
        o = (o.astype(jnp.float32)
             / (norm[..., None, None] + 1e-3)).astype(a.dtype)
        o = o.reshape(o.shape[:3] + (c * c,))
        return dense(p["out"], o)                  # (B, i/N, jc, Hz)

    jc = cfg.opm_chunk
    r_full = b_full.shape[2]
    if not jc or r_full % jc != 0 or jc >= r_full:
        return opm_block(b_full, mask_full)
    # j-chunked: scan keeps the (i, jc, c*c) intermediate bounded.
    nb = r_full // jc
    bsz, s = b_full.shape[:2]
    b_c = b_full.reshape(bsz, s, nb, jc, c).transpose(2, 0, 1, 3, 4)
    m_c = mask_full.reshape(bsz, s, nb, jc).transpose(2, 0, 1, 3)
    _, outs = jax.lax.scan(
        lambda _, bm: (None, opm_block(bm[0], bm[1])), None, (b_c, m_c))
    # outs: (nb, B, i/N, jc, Hz) -> (B, i/N, r, Hz)
    return outs.transpose(1, 2, 0, 3, 4).reshape(bsz, a.shape[2], r_full, -1)


def triangle_mult_core(p, z_src, pair_mask_loc, dist,
                       cfg: EvoformerConfig):
    """Shared core of the two Triangular Multiplicative Updates: the full
    gated update (including the output gate) in ``z_src`` coords.

    z_src: tensor the a/b projections AND the output gate read (already
    LN'ed); for the "outgoing" update this is LN(z) (i-shard); for
    "incoming" it is the transposed LN(z) (row-sharded, transposed coords —
    the sigmoid output gate commutes elementwise with the transpose).

    Fused path (default): ``dist.sharded_triangle`` — k-tiled accumulation
    of the triangular product with the a-side input gating, pair mask,
    output LayerNorm and bias_sigmoid_mul output gate fused into the same
    sweep (ops.fused_triangle_mult); the b half is gated+masked *before*
    the row gather (elementwise commutes with the gather, and gathering the
    gated half keeps the collective at (B, r, k, c)). GspmdDist shard_maps
    the op over (batch_axes, 'model') with b_full replicated, so the
    kernel's tiling only ever sees local (B_loc, i_loc, ...) blocks. The
    materialized jnp path below stays behind the plan's oracle legs
    (KernelPolicy(enabled=False) / triangle='oracle') and out-of-envelope
    shapes for A/B.
    """
    c = cfg.tri_mult_dim
    ab = dense(p["proj"], z_src)                   # (B, p/N, k, 2c) merged
    g = dense(p["gate"], z_src)
    # Fused output gate operand: sigmoid(z @ Wg + bg) * upd, computed in the
    # same coords as the update (the gate bias rides into the fused op, so
    # dense() — which would apply it — cannot be used here).
    # repro-lint: disable=R004 -- d-scale GEMM, not an r²-scale contraction
    g_lin = jnp.einsum("...d,de->...e", z_src,
                       p["gate_out"]["w"].astype(z_src.dtype))
    if (ops.fused_triangle_supported(c, p["out"]["w"].shape[1], ab.dtype)
            and dist.sharded_triangle_supported(ab.shape[1])):
        a_lin, b_lin = jnp.split(ab, 2, axis=-1)
        ga, gb = jnp.split(g, 2, axis=-1)
        bm = (b_lin.astype(jnp.float32)
              * jax.nn.sigmoid(gb.astype(jnp.float32))).astype(ab.dtype)
        bm = bm * pair_mask_loc[..., None].astype(ab.dtype)
        b_full = dist.all_gather(bm, axis=1)       # (B, r, k, c) gather rows
        b_full = dist.constrain(b_full, ("b", None, None, None))
        # Duality-Async window: fence the a-side operand with the gather so
        # the triangular gather cannot sink to the fused product below.
        b_full, a_lin = duality.overlap_window(b_full, a_lin)
        return dist.sharded_triangle(
            a_lin, ga, pair_mask_loc, b_full,
            p["ln_out"]["gamma"], p["ln_out"]["beta"],
            p["out"]["w"], p["out"]["b"], g_lin, p["gate_out"]["b"],
            tile=cfg.tri_k_tile)
    # Materialized A/B path: gated projections and the (B, p/N, r, c)
    # product as standalone tensors, then LN -> projection -> gate.
    ab = ab * jax.nn.sigmoid(g.astype(jnp.float32)).astype(ab.dtype)
    ab = ab * pair_mask_loc[..., None].astype(ab.dtype)
    a, bm = jnp.split(ab, 2, axis=-1)
    b_full = dist.all_gather(bm, axis=1)           # (B, r, k, c) gather rows
    b_full = dist.constrain(b_full, ("b", None, None, None))
    b_full, a = duality.overlap_window(b_full, a)
    # repro-lint: disable=R004 -- sanctioned materialized triangle A/B path
    o = jnp.einsum("bikc,bjkc->bijc", a, b_full)   # (B, p/N, r, c)
    upd = dense(p["out"], layer_norm(p["ln_out"], o))
    # Fused gating kernel: sigmoid(z @ Wg + bg) * upd in one HBM pass.
    return ops.bias_sigmoid_mul(g_lin, p["gate_out"]["b"], upd)


def triangle_mult_outgoing(p, pair, pair_mask_loc, dist, cfg):
    z_n = layer_norm(p["ln_in"], pair)
    return triangle_mult_core(p, z_n, pair_mask_loc, dist, cfg)


def triangle_mult_incoming(p, pair, pair_t, pair_mask_loc_t, dist, cfg):
    """incoming(z)_ij = sum_k a_ki b_kj == outgoing_core(z^T)_ij.

    pair:   (B, i/N, j, Hz) — kept for signature compatibility (TP mode);
            the gate now reads the transposed coords directly.
    pair_t: (B, j/N, i, Hz) — transposed tensor (from all_to_all axis swap).

    The whole gated update is computed in transposed coords (gate(z^T) =
    gate(z)^T elementwise) and axis-swapped back to i-shard coords.
    """
    del pair
    z_n_t = layer_norm(p["ln_in"], pair_t)
    upd_t = triangle_mult_core(p, z_n_t, pair_mask_loc_t, dist, cfg)
    return transpose_pair(upd_t, dist)


def triangle_attention(p, pair, seq_mask, dist, cfg: EvoformerConfig):
    """Around starting node on (B, i/N, j, Hz): per-row attention over k with
    bias b(j,k); bias rows are local -> AllGather."""
    b, i_loc, r, _ = pair.shape
    dims = AttnDims(cfg.pair_heads, cfg.pair_heads, cfg.head_dim)
    z_n = layer_norm(p["ln"], pair)
    bias_loc = dense(p["bias"], z_n).transpose(0, 3, 1, 2)  # (B, H, i/N, k)
    bias = dist.all_gather(bias_loc, axis=2)                # (B, H, r, r)
    bias = dist.constrain(bias, ("b", None, None, None))
    key_mask = jnp.broadcast_to(seq_mask[:, None, :], (b, i_loc, r))
    return _gated_attention(p["attn"], z_n, bias, key_mask, dims,
                            dist=dist, chunk=cfg.inference_chunk,
                            kv_tile=cfg.attn_kv_tile)


def transpose_pair(x, dist):
    """Axis-swap a pair-like tensor: (B, i/N, j, c) -> (B, j/N, i, c).

    all_to_all moves the shard (1/N^2 volume, paper Table III), local swap
    finishes the transpose."""
    y = dist.all_to_all(x, split_axis=2, concat_axis=1)  # (B, i, j/N, c)
    y = y.swapaxes(1, 2)
    return dist.constrain(y, ("b", "m") + (None,) * (y.ndim - 2))


# ---------------------------------------------------------------------------
# Full block
# ---------------------------------------------------------------------------

def evoformer_block(
    params: Params,
    msa: jax.Array,        # (B, s/N, r, Hm)  s-shard
    pair: jax.Array,       # (B, i/N, j, Hz)  i-shard
    msa_mask: jax.Array,   # (B, s/N, r)
    seq_mask: jax.Array,   # (B, r) replicated
    pair_mask_loc: jax.Array,  # (B, i/N, j)
    *,
    dist=None,
    cfg: EvoformerConfig,
    rng=None,
    train: bool = False,
):
    """One Evoformer block under the DAP sharding state machine.
    ``dist=None`` resolves the current ExecutionPlan's ParallelPolicy."""
    if dist is None:
        dist = current_plan().parallel.make_dist()
    rngs = list(jax.random.split(rng, 8)) if rng is not None else [None] * 8

    # ----- MSA stack (s-shard phase) -----
    # Each sub-module runs under jax.named_scope("evoformer.<name>"), its
    # residual add and layout swaps included, so the device trace attributes
    # the block's time (and, under DAP, its collectives) by sub-module.
    msa = dist.constrain(msa, ("b", "m", None, None))
    pair = dist.constrain(pair, ("b", "m", None, None))
    with jax.named_scope("evoformer.msa_row_attention"):
        upd = msa_row_attention(params["msa_row"], msa, pair, seq_mask, dist,
                                cfg)
        msa = _residual_add(upd, msa, cfg.dropout_msa, rngs[0], 2, train)

    col = (msa_col_global_attention if cfg.global_column
           else msa_col_attention)
    with jax.named_scope("evoformer." + col.__name__):
        # all_to_all #1: s-shard -> r-shard.
        msa = dist.all_to_all(msa, split_axis=2, concat_axis=1)
        msa = dist.constrain(msa, ("b", None, "m", None))
        msa_mask_r = dist.all_to_all(msa_mask, split_axis=2, concat_axis=1)

        upd = col(params["msa_col"], msa, msa_mask_r, dist, cfg)
        msa = _residual_add(upd, msa, 0.0, None, 0, train)
    with jax.named_scope("evoformer.msa_transition"):
        msa = _residual_add(msa_transition(params["msa_trans"], msa), msa,
                            0.0, None, 0, train)

    # ----- Communication: OPM consumes the r-shard MSA -----
    with jax.named_scope("evoformer.outer_product_mean"):
        pair_upd = outer_product_mean(params["opm"], msa, msa_mask_r, dist,
                                      cfg)

        # all_to_all #2 (the Duality-Async window): swap MSA back to s-shard
        # now; its result is consumed only at the *next block's* row
        # attention, so the entire pair stack below is overlap-eligible
        # compute.
        msa = dist.all_to_all(msa, split_axis=1, concat_axis=2)
        msa = dist.constrain(msa, ("b", "m", None, None))

        pair = _residual_add(pair_upd, pair, cfg.dropout_pair, rngs[1], 1,
                             train)

    # ----- Pair stack (i-shard phase) -----
    with jax.named_scope("evoformer.triangle_mult_outgoing"):
        upd = triangle_mult_outgoing(params["tri_mult_out"], pair,
                                     pair_mask_loc, dist, cfg)
        pair = _residual_add(upd, pair, cfg.dropout_pair, rngs[2], 1, train)

    with jax.named_scope("evoformer.triangle_mult_incoming"):
        pair_t = transpose_pair(pair, dist)
        pair_mask_t = transpose_pair(pair_mask_loc[..., None], dist)[..., 0]
        upd = triangle_mult_incoming(params["tri_mult_in"], pair, pair_t,
                                     pair_mask_t, dist, cfg)
        pair = _residual_add(upd, pair, cfg.dropout_pair, rngs[3], 1, train)

    with jax.named_scope("evoformer.triangle_attention_starting"):
        upd = triangle_attention(params["tri_attn_start"], pair, seq_mask,
                                 dist, cfg)
        pair = _residual_add(upd, pair, cfg.dropout_pair, rngs[4], 1, train)

    # Ending-node attention == starting-node attention on the transpose.
    with jax.named_scope("evoformer.triangle_attention_ending"):
        pair_t = transpose_pair(pair, dist)
        upd_t = triangle_attention(params["tri_attn_end"], pair_t, seq_mask,
                                   dist, cfg)
        upd = transpose_pair(upd_t, dist)
        pair = _residual_add(upd, pair, cfg.dropout_pair, rngs[5], 2, train)

    with jax.named_scope("evoformer.pair_transition"):
        pair = _residual_add(
            transition(params["pair_trans"]["mlp"],
                       layer_norm(params["pair_trans"]["ln"], pair)),
            pair, 0.0, None, 0, train)
    # Duality-Async window (paper §IV.C): the swap-back all_to_all above is
    # consumed only at the *next* block's row attention. Fencing its result
    # with the finished pair stack pins the collective inside this block —
    # the scheduler may start it as early as OPM allows but cannot sink it
    # into the next block's body past the overlap-eligible pair compute.
    msa, pair = duality.overlap_window(msa, pair)
    return msa, pair


def init_evoformer_stack(key, cfg: EvoformerConfig) -> Params:
    """Stacked block params with leading layer axis (scan-compatible)."""
    keys = jax.random.split(key, cfg.n_blocks)
    return jax.vmap(lambda k: init_evoformer_block(k, cfg))(keys)


def evoformer_stack(
    params_stacked: Params,
    msa: jax.Array,
    pair: jax.Array,
    msa_mask: jax.Array,
    seq_mask: jax.Array,
    pair_mask_loc: jax.Array,
    *,
    dist=None,
    cfg: EvoformerConfig,
    rng=None,
    train: bool = False,
    remat: bool = True,
):
    """scan over n_blocks Evoformer blocks (activation checkpointing per block,
    as AlphaFold/the paper do — §III.B "gradient checkpointing").
    ``dist=None`` resolves the current ExecutionPlan's ParallelPolicy."""
    if dist is None:
        dist = current_plan().parallel.make_dist()
    rngs = (jax.random.split(rng, cfg.n_blocks) if rng is not None
            else jnp.zeros((cfg.n_blocks, 2), jnp.uint32))

    def body(carry, xs):
        m, z = carry
        p, r_key = xs
        r = r_key if rng is not None else None
        m, z = evoformer_block(p, m, z, msa_mask, seq_mask, pair_mask_loc,
                               dist=dist, cfg=cfg, rng=r, train=train)
        return (m, z), None

    if remat:
        policy = (jax.checkpoint_policies.dots_saveable
                  if cfg.remat_policy == "dots"
                  else jax.checkpoint_policies.nothing_saveable)
        body = jax.checkpoint(body, policy=policy)
    (msa, pair), _ = jax.lax.scan(body, (msa, pair), (params_stacked, rngs))
    return msa, pair


def extra_msa_stack(
    params_stacked: Params,
    extra_msa: jax.Array,
    pair: jax.Array,
    extra_mask: jax.Array,
    seq_mask: jax.Array,
    pair_mask_loc: jax.Array,
    *,
    dist=None,
    cfg: EvoformerConfig,
    rng=None,
    train: bool = False,
):
    """The extra-MSA stack (SI Alg. 18): ``cfg.n_blocks`` blocks of the
    global-column variant (``cfg.global_column``) over the embedded extra
    MSA (B, s_extra, r, c). Only the pair leaves it; the extra MSA is
    dropped."""
    if not cfg.global_column:
        raise ValueError("the extra-MSA stack runs the global-column block "
                         "variant: set EvoformerConfig.global_column")
    _, pair = evoformer_stack(params_stacked, extra_msa, pair, extra_mask,
                              seq_mask, pair_mask_loc, dist=dist, cfg=cfg,
                              rng=rng, train=train)
    return pair
