"""Fused flash-style gated-attention Pallas TPU kernels (forward + backward).

Forward: ``out = softmax(scale * q @ k^T + bias + mask) @ v`` with an online
softmax over KV tiles: the scores tile lives only in VMEM, so the
``(N, H, R, R)`` scores tensor the paper's §III.B identifies as the cubic
``N_r^3 * H`` memory transient never reaches HBM. HBM traffic per q tile is
linear in the KV tile size instead of quadratic in sequence length — the
fused-attention gap ScaleFold (arXiv 2404.11068) closes on top of FastFold's
kernel set.

Backward (``flash_attention_bwd_pallas``): recompute-style flash backward
from the saved ``(q, k, v, out->delta, lse)`` residuals — the probs/ds tiles
are rebuilt per (q_tile, kv_tile) cell in VMEM, so the fp32
``(N, H, Sq, kv_block)`` recompute transient the jnp KV-scan backward streams
through HBM never materializes. Three sweeps (dq; dk/dv + the mask
reduction; the bias reduction), each a separate grid ordered so its
accumulator lives in VMEM scratch across the innermost dimension — except
when the bias group is mesh-local (rep == 1, the shard-mapped DAP layout):
then the dq sweep's recomputed ds tiles ARE dbias, so they are emitted as a
second output of sweep 1 and the bias-reduction sweep is skipped (two sweeps
total, one fewer full recompute pass over the tiles).

An XLA-native forward with identical semantics (``flash_attention_xla``,
lax.scan over KV tiles) serves as the non-TPU leg: interpret-mode Pallas is a
per-grid-cell loop, ~2x the jnp online-softmax path on CPU smoke shapes.

Kernel contract (enforced/prepared by ops.fused_attention):

Forward (``flash_attention_pallas``), on the model's own layout:

  q, k, v : (N, S, H·D), the (N, S, H, D) the projection emits with its
            heads merged (a free reshape: no transpose, no lane padding), S
            padded to the q/kv tile only where it is not a multiple (zero
            rows — harmless: they attend over the real KV range and are
            sliced off by the caller).
  bias    : (B, H, Sq, Skv) additive, ``N % B == 0`` (each bias batch element
            is shared by N/B consecutive rows of q — the Evoformer pair bias
            shared across the MSA/group axis), or None.
  mask    : (N, 1, Skv) additive fp32 (0 / NEG_INF-style), or None — the
            unit axis keeps the block's second-minor dim equal to the
            array's (the TPU (8, 128) block rule). Mask values must be
            finite (use ~-1e9, not -inf).
  kv_len  : true KV length before padding; padded columns are masked to
            ``NEG_INF`` in-kernel so they never win the max nor add to the sum.

Returns ``out (N, Sq, H·D)`` in the input dtype and the fp32 log-sum-exp
``lse (N, H, Sq)`` that the recompute backward needs. One grid step computes
every head of a ``head_group`` (all heads at the Evoformer's widths) for one
(row, q tile): static lane slices ``[h·D, (h+1)·D)`` of the blocks, the
outputs concatenated along lanes, lse written as a (heads, q_tile) block of
``(N, H/group, group, Sq)``. Grid ``(H/group, N, Sq/q_tile, Skv/kv_tile)``:
with one KV tile (every site at n_res 256, n_seq 512) a shared pair bias
stays resident across consecutive rows and k/v across a row's q tiles, and
the softmax is taken in one pass; with several the fp32 (m, l, acc) state
lives in VMEM scratch across the innermost KV sweep and the output is
written on its last step.

Backward (``flash_attention_bwd_pallas``), on the staged layout ops.py
builds from the saved residuals: q, k, v, dO (N, H, S, D) with D zero-padded
to a 128-lane multiple and S padded to the q/kv tile, one head per grid
step. The per-row statistics (lse, delta) and the mask reduction travel as
``(N, H, 1, S)`` rows: a ``(1, 1, 1, tile)`` block obeys the (8, 128) rule
where a ``(1, 1, tile)`` block of ``(N, H, S)`` does not, and a row costs at
most an 8-sublane pad in HBM where a lane-broadcast column would cost 128x.

Both: fp32 statistics, MXU GEMMs with fp32 accumulation
(``preferred_element_type``) at ``KERNEL_PRECISION``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
NEG_INF = -1e30  # finite: keeps exp(s - m) NaN-free even for all-masked rows
# In-kernel MXU precision, fixed so a process-wide
# ``jax_default_matmul_precision`` cannot reach the kernels: Mosaic refuses
# bf16 operands at fp32 contract precision ("Bad lhs type").
KERNEL_PRECISION = jax.lax.Precision.DEFAULT
# Widest head group (H·D lanes of q, k and v) one forward grid step reads:
# every head of the Evoformer's sites (256 lanes on the MSA side, 128 on the
# pair side, 64 in the extra-MSA stack).
MAX_GROUP_LANES = 256
# Scoped VMEM of the forward: a whole-Sq q tile holds a (heads, Sq, Skv)
# bias block and one head's (Sq, Skv) fp32 scores at a time; a v5e core has
# 128 MiB of VMEM.
VMEM_LIMIT_BYTES = 64 << 20


def _pad_to(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def head_group(heads: int, head_dim: int) -> int:
    """Heads one forward grid step computes: all of them when their lanes
    fit ``MAX_GROUP_LANES``, else the largest divisor of ``heads`` whose
    lane width is a 128-lane multiple within it (a legal lane block), else
    all of them."""
    if heads * head_dim <= MAX_GROUP_LANES:
        return heads
    for g in range(heads - 1, 0, -1):
        width = g * head_dim
        if heads % g == 0 and width % LANE == 0 and width <= MAX_GROUP_LANES:
            return g
    return heads


def _flash_kernel(*refs, scale: float, kv_len: int, kv_tile: int, n_kv: int,
                  heads: int, head_dim: int, has_bias: bool,
                  has_mask: bool):
    """One (head group, row, q tile, kv tile) grid step over every head of
    the group: static lane slices of the (q_tile | kv_tile, heads·D)
    blocks. A single KV tile (``n_kv == 1``) takes the softmax in one pass;
    several carry fp32 (m, l, acc) scratch across the innermost KV axis."""
    idx = 0
    q_ref = refs[idx]; idx += 1
    k_ref = refs[idx]; idx += 1
    v_ref = refs[idx]; idx += 1
    b_ref = refs[idx] if has_bias else None
    idx += int(has_bias)
    mk_ref = refs[idx] if has_mask else None
    idx += int(has_mask)
    o_ref, lse_ref = refs[idx], refs[idx + 1]
    acc_ref, m_ref, l_ref = refs[idx + 2:idx + 5] if n_kv > 1 else (None,) * 3

    jk = pl.program_id(3)
    q, k, v = q_ref[0], k_ref[0], v_ref[0]     # (q_tile | kv_tile, heads·D)
    mask = mk_ref[0].astype(jnp.float32) if has_mask else None  # (1, kv_tile)

    def scores(h):
        lanes = slice(h * head_dim, (h + 1) * head_dim)
        s = jax.lax.dot_general(
            q[:, lanes], k[:, lanes], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=KERNEL_PRECISION,
        ) * scale                                     # (q_tile, kv_tile)
        if b_ref is not None:
            s = s + b_ref[0, h].astype(jnp.float32)
        if mask is not None:
            s = s + mask
        if kv_len < n_kv * kv_tile:
            # Padded KV columns must not win the max nor add to the sum.
            col = jk * kv_tile + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(col < kv_len, s, NEG_INF)
        return s

    def pv(p, h):
        return jax.lax.dot_general(
            p.astype(v.dtype), v[:, h * head_dim:(h + 1) * head_dim],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=KERNEL_PRECISION,
        )                                             # (q_tile, D)

    def finish(accs, ms, ls):
        outs, lses = [], []
        for acc, m, l in zip(accs, ms, ls):
            l = jnp.maximum(l, 1e-30)
            outs.append(acc / l)
            lses.append((m + jnp.log(l)).reshape(1, -1))
        o_ref[0] = jnp.concatenate(outs, axis=-1).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.concatenate(lses, axis=0)  # (heads, q_tile)

    if n_kv == 1:
        # The same softmax with nothing carried: no scratch round trips of
        # (m, l, acc) nor alpha rescale. On a TPU v5e this runs the
        # Evoformer's sites 1.76-2.04x faster than the scratch path below
        # at one KV tile (MSA row, 512 rows: 2.78 against 4.89 ms a call).
        accs, ms, ls = [], [], []
        for h in range(heads):
            s = scores(h)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            ls.append(jnp.sum(p, axis=-1, keepdims=True))
            ms.append(m)
            accs.append(pv(p, h))
        finish(accs, ms, ls)
        return

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for h in range(heads):
        s = scores(h)
        m_prev = m_ref[h, :, :1]                      # (q_tile, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_ref[h, :, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + pv(p, h)
        m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
        l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(jk == n_kv - 1)
    def _epilogue():
        finish([acc_ref[h] for h in range(heads)],
               [m_ref[h, :, :1] for h in range(heads)],
               [l_ref[h, :, :1] for h in range(heads)])


@functools.partial(
    jax.jit,
    static_argnames=("scale", "kv_len", "heads", "q_tile", "kv_tile",
                     "has_bias", "has_mask", "interpret"),
)
def flash_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    bias: jax.Array | None = None,
    mask: jax.Array | None = None,
    *,
    scale: float,
    kv_len: int,
    heads: int,
    q_tile: int,
    kv_tile: int,
    has_bias: bool = False,
    has_mask: bool = False,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Forward on the model's layout — see the module docstring; use
    ops.fused_attention. Returns (out (N, Sq, H·D) in q's dtype, lse
    (N, H, Sq) fp32)."""
    n, sq, width = q.shape
    skv = k.shape[1]
    assert sq % q_tile == 0 and skv % kv_tile == 0 and width % heads == 0, \
        (q.shape, k.shape, heads, q_tile, kv_tile)
    d = width // heads
    g = head_group(heads, d)
    n_kv = skv // kv_tile
    # Head groups outermost, then rows: a pair bias shared by consecutive
    # rows stays resident across them, and with one KV tile k and v stay
    # resident across a row's q tiles; KV innermost carries the scratch.
    grid = (heads // g, n, sq // q_tile, n_kv)

    in_specs = [
        pl.BlockSpec((1, q_tile, g * d), lambda j, i, iq, jk: (i, iq, j)),
        pl.BlockSpec((1, kv_tile, g * d), lambda j, i, iq, jk: (i, jk, j)),
        pl.BlockSpec((1, kv_tile, g * d), lambda j, i, iq, jk: (i, jk, j)),
    ]
    operands = [q, k, v]
    if has_bias:
        assert bias is not None and bias.ndim == 4 and n % bias.shape[0] == 0
        rep = n // bias.shape[0]
        in_specs.append(
            pl.BlockSpec((1, g, q_tile, kv_tile),
                         lambda j, i, iq, jk: (i // rep, j, iq, jk))
        )
        operands.append(bias)
    if has_mask:
        assert mask is not None and mask.shape == (n, 1, skv)
        in_specs.append(
            pl.BlockSpec((1, 1, kv_tile), lambda j, i, iq, jk: (i, 0, jk))
        )
        operands.append(mask)

    scratch = []
    if n_kv > 1:
        scratch = [
            pltpu.VMEM((g, q_tile, d), jnp.float32),      # acc
            pltpu.VMEM((g, q_tile, LANE), jnp.float32),   # running max m
            pltpu.VMEM((g, q_tile, LANE), jnp.float32),   # running sum l
        ]
    kernel = functools.partial(
        _flash_kernel, scale=scale, kv_len=kv_len, kv_tile=kv_tile,
        n_kv=n_kv, heads=g, head_dim=d, has_bias=has_bias,
        has_mask=has_mask,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, q_tile, g * d), lambda j, i, iq, jk: (i, iq, j)),
            pl.BlockSpec((1, 1, g, q_tile), lambda j, i, iq, jk: (i, j, 0, iq)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((n, heads // g, g, sq), jnp.float32),
        ],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*operands)
    return out, lse.reshape(n, heads, sq)


# ---------------------------------------------------------------------------
# XLA-native forward (non-TPU leg). Same math, same residuals.
# ---------------------------------------------------------------------------


def stage_kv_blocks(k, v, bias, mask, kv_tile: int) -> dict:
    """Shared KV-tile staging for the lax.scan legs (XLA-native forward and
    the jnp recompute backward in ops._attn_bwd): pad Skv to a kv_tile
    multiple and reshape into per-tile scan blocks. Padded columns carry a
    NEG_INF additive mask so recomputed probs are exactly zero there.

    k, v (N, Skv, H, D); bias (B, H, Sq, Skv) or None; mask (N, Skv) fp32 or
    None. Returns xs with leading dim nkv: 'k'/'v' (nkv, N, kvb, H, D),
    'b' (nkv, B, H, Sq, kvb) if bias, 'm' (nkv, N, kvb) if mask or padding.
    """
    n, skv, h, d = k.shape
    nkv = -(-skv // kv_tile)
    skv_pad = nkv * kv_tile
    kp = jnp.pad(k, ((0, 0), (0, skv_pad - skv), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, skv_pad - skv), (0, 0), (0, 0)))
    mcomb = None
    if mask is not None:
        mcomb = jnp.pad(mask.astype(jnp.float32),
                        ((0, 0), (0, skv_pad - skv)),
                        constant_values=NEG_INF)
    elif skv_pad != skv:
        col = jnp.arange(skv_pad)
        mcomb = jnp.broadcast_to(
            jnp.where(col < skv, 0.0, NEG_INF)[None, :], (n, skv_pad))
    xs = {
        "k": kp.reshape(n, nkv, kv_tile, h, d).swapaxes(0, 1),
        "v": vp.reshape(n, nkv, kv_tile, h, v.shape[-1]).swapaxes(0, 1),
    }
    if bias is not None:
        nb, _, sq, _ = bias.shape
        bp = jnp.pad(bias, ((0, 0), (0, 0), (0, 0), (0, skv_pad - skv)))
        xs["b"] = bp.reshape(nb, h, sq, nkv, kv_tile).transpose(3, 0, 1, 2, 4)
    if mcomb is not None:
        xs["m"] = mcomb.reshape(n, nkv, kv_tile).swapaxes(0, 1)
    return xs


def apply_block_bias_mask(s, blk, n: int):
    """Add a staged bias/mask block to a scores block s (N, H, Sq, kvb): the
    bias is shared by N/B consecutive rows (Evoformer bias-group contract)."""
    if "b" in blk:
        nb = blk["b"].shape[0]
        s = s.reshape((nb, n // nb) + s.shape[1:])
        s = s + blk["b"].astype(jnp.float32)[:, None]
        s = s.reshape((n,) + s.shape[2:])
    if "m" in blk:
        s = s + blk["m"][:, None, None, :]
    return s


def flash_attention_xla(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    bias: jax.Array | None = None,
    mask: jax.Array | None = None,
    *,
    scale: float,
    kv_tile: int,
) -> tuple[jax.Array, jax.Array]:
    """Online-softmax attention as a lax.scan over KV tiles — no Pallas.

    Layout matches ops.fused_attention (NOT the kernel): q (N, Sq, H, D);
    k, v (N, Skv, H, D); bias (B, H, Sq, Skv) with N % B == 0; mask (N, Skv)
    additive fp32. Returns (out (N, Sq, H, D) in q.dtype, lse (N, H, Sq) fp32)
    — the same residual contract as the Pallas kernel, so the recompute
    backward is shared. Used when ``jax.default_backend() != "tpu"``: the
    memory behavior (peak transient = one fp32 (N, H, Sq, kv_tile) block) is
    the same; XLA owns the fusion instead of Mosaic.
    """
    n, sq, h, d = q.shape
    skv = k.shape[1]
    kvb = min(kv_tile, skv)
    xs = stage_kv_blocks(k, v, bias, mask, kvb)

    def kv_step(carry, blk):
        m, l, acc = carry
        s = jnp.einsum("nqhd,nkhd->nhqk", q, blk["k"],
                       preferred_element_type=jnp.float32) * scale
        s = apply_block_bias_mask(s, blk, n)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "nhqk,nkhd->nhqd", p, blk["v"].astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((n, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((n, h, sq), jnp.float32)
    a0 = jnp.zeros((n, h, sq, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), xs)
    l = jnp.maximum(l, 1e-30)
    out = (acc / l[..., None]).swapaxes(1, 2).astype(q.dtype)
    lse = m + jnp.log(l)
    return out, lse


# ---------------------------------------------------------------------------
# Fused backward kernels
# ---------------------------------------------------------------------------
#
# ds recompute shared by all three sweeps: rebuild the scores tile from
# (q, k, bias, mask), the probs tile from lse, and d(logits) from
# (do, v, delta) — all in VMEM, fp32.


def _recompute_ds(q, k, v, do, lse, delta, b_blk, m_blk, *, scale, kv_len,
                  kv_tile, jk):
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=KERNEL_PRECISION,
    ) * scale                                          # (q_tile, kv_tile)
    if b_blk is not None:
        s = s + b_blk.astype(jnp.float32)
    if m_blk is not None:
        s = s + m_blk.astype(jnp.float32)              # (1, kv_tile) row
    col = jk * kv_tile + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < kv_len, s, NEG_INF)
    # lse/delta arrive as (1, q_tile) rows; the tile needs them as columns.
    p = jnp.exp(s - lse.reshape(-1, 1))                # (q_tile, kv_tile)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=KERNEL_PRECISION,
    )                                                  # (q_tile, kv_tile)
    ds = p * (dp - delta.reshape(-1, 1))
    return p, ds


def _bwd_dq_kernel(*refs, scale, kv_len, kv_tile, has_bias, has_mask,
                   emit_dbias=False):
    idx = 0
    q_ref = refs[idx]; idx += 1
    k_ref = refs[idx]; idx += 1
    v_ref = refs[idx]; idx += 1
    do_ref = refs[idx]; idx += 1
    lse_ref = refs[idx]; idx += 1
    dl_ref = refs[idx]; idx += 1
    b_ref = refs[idx] if has_bias else None
    idx += int(has_bias)
    mk_ref = refs[idx] if has_mask else None
    idx += int(has_mask)
    dq_ref = refs[idx]; idx += 1
    db_ref = refs[idx] if emit_dbias else None
    idx += int(emit_dbias)
    dq_acc = refs[idx]

    jk = pl.program_id(3)
    n_kv = pl.num_programs(3)

    @pl.when(jk == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    _, ds = _recompute_ds(
        q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0],
        lse_ref[0, 0], dl_ref[0, 0],
        b_ref[0, 0] if b_ref is not None else None,
        mk_ref[0] if mk_ref is not None else None,
        scale=scale, kv_len=kv_len, kv_tile=kv_tile, jk=jk)
    dq_acc[...] += jax.lax.dot_general(
        ds, k_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=KERNEL_PRECISION,
    ) * scale
    if db_ref is not None:
        # Mesh-local bias group (rep == 1): dbias IS the ds tile — each
        # (iq, jk) grid cell owns its output block, so the separate
        # bias-reduction sweep collapses into this one.
        db_ref[0, 0] = ds

    @pl.when(jk == n_kv - 1)
    def _epilogue():
        dq_ref[0, 0] = dq_acc[...]


def _bwd_dkv_kernel(*refs, scale, kv_len, kv_tile, has_bias, has_mask):
    idx = 0
    q_ref = refs[idx]; idx += 1
    k_ref = refs[idx]; idx += 1
    v_ref = refs[idx]; idx += 1
    do_ref = refs[idx]; idx += 1
    lse_ref = refs[idx]; idx += 1
    dl_ref = refs[idx]; idx += 1
    b_ref = refs[idx] if has_bias else None
    idx += int(has_bias)
    mk_ref = refs[idx] if has_mask else None
    idx += int(has_mask)
    dk_ref, dv_ref = refs[idx], refs[idx + 1]
    idx += 2
    dm_ref = refs[idx] if has_mask else None
    idx += int(has_mask)
    dk_acc, dv_acc = refs[idx], refs[idx + 1]
    dm_acc = refs[idx + 2] if has_mask else None

    iq = pl.program_id(3)
    n_q = pl.num_programs(3)
    jk = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        if dm_acc is not None:
            dm_acc[...] = jnp.zeros_like(dm_acc)

    p, ds = _recompute_ds(
        q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0],
        lse_ref[0, 0], dl_ref[0, 0],
        b_ref[0, 0] if b_ref is not None else None,
        mk_ref[0] if mk_ref is not None else None,
        scale=scale, kv_len=kv_len, kv_tile=kv_tile, jk=jk)
    dv_acc[...] += jax.lax.dot_general(
        p, do_ref[0, 0].astype(jnp.float32), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=KERNEL_PRECISION,
    )                                                  # (kv_tile, d)
    dk_acc[...] += jax.lax.dot_general(
        ds, q_ref[0, 0].astype(jnp.float32), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=KERNEL_PRECISION,
    ) * scale
    if dm_acc is not None:
        dm_acc[...] += jnp.broadcast_to(
            jnp.sum(ds, axis=0, keepdims=True), dm_acc.shape)

    @pl.when(iq == n_q - 1)
    def _epilogue():
        dk_ref[0, 0] = dk_acc[...]
        dv_ref[0, 0] = dv_acc[...]
        if dm_ref is not None:
            dm_ref[0, 0] = dm_acc[0:1, :]


def _bwd_dbias_kernel(*refs, scale, kv_len, kv_tile, has_mask):
    idx = 0
    q_ref = refs[idx]; idx += 1
    k_ref = refs[idx]; idx += 1
    v_ref = refs[idx]; idx += 1
    do_ref = refs[idx]; idx += 1
    lse_ref = refs[idx]; idx += 1
    dl_ref = refs[idx]; idx += 1
    b_ref = refs[idx]; idx += 1
    mk_ref = refs[idx] if has_mask else None
    idx += int(has_mask)
    db_ref, db_acc = refs[idx], refs[idx + 1]

    r = pl.program_id(4)
    rep = pl.num_programs(4)
    jk = pl.program_id(3)

    @pl.when(r == 0)
    def _init():
        db_acc[...] = jnp.zeros_like(db_acc)

    _, ds = _recompute_ds(
        q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0],
        lse_ref[0, 0], dl_ref[0, 0], b_ref[0, 0],
        mk_ref[0] if mk_ref is not None else None,
        scale=scale, kv_len=kv_len, kv_tile=kv_tile, jk=jk)
    db_acc[...] += ds

    @pl.when(r == rep - 1)
    def _epilogue():
        db_ref[0, 0] = db_acc[...]


@functools.partial(
    jax.jit,
    static_argnames=("scale", "kv_len", "q_tile", "kv_tile", "has_bias",
                     "has_mask", "interpret"),
)
def flash_attention_bwd_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    do: jax.Array,
    lse: jax.Array,
    delta: jax.Array,
    bias: jax.Array | None = None,
    mask: jax.Array | None = None,
    *,
    scale: float,
    kv_len: int,
    q_tile: int,
    kv_tile: int,
    has_bias: bool = False,
    has_mask: bool = False,
    interpret: bool = False,
):
    """Fused flash-attention backward. Pre-padded kernel layout, like the
    forward: q/k/v/do (N, H, S, D) with D a 128-lane multiple and S padded to
    the q/kv tile (zero rows/cols); lse and delta ( = rowsum(dO * O), fp32 )
    are (N, H, Sq) padded with zeros; mask is (N, 1, Skv) like the
    forward's. Zero-padded dO rows make every padded
    contribution vanish (ds = p * (dp - delta) = 0), and padded KV columns
    are re-masked to NEG_INF in-kernel exactly as in the forward.

    Returns fp32 (dq (N, H, Sq, D), dk, dv (N, H, Skv, D),
    dbias (B, H, Sq, Skv) | None, dmask_h (N, H, Skv) | None). dmask_h is the
    per-head mask reduction (sum over q of ds) — callers sum over H. Three
    grid sweeps recompute the ds tile in VMEM (dq: KV-innermost; dk/dv + mask
    reduction: q-innermost; bias reduction: bias-group-innermost so the
    (q_tile, kv_tile) accumulator can live in scratch) — or TWO sweeps when
    the bias group is mesh-local (rep == 1): dbias is emitted directly from
    the dq sweep's ds tiles and the bias-reduction sweep is skipped.
    """
    n, h, sq, d = q.shape
    skv = k.shape[2]
    assert sq % q_tile == 0 and skv % kv_tile == 0 and d % LANE == 0, \
        (q.shape, k.shape, q_tile, kv_tile)
    nq, nkv = sq // q_tile, skv // kv_tile

    def specs4(ixmap):
        return pl.BlockSpec((1, 1, q_tile, d), ixmap)

    def qkv_specs(iq_of, jk_of):
        # q/do + lse/delta blocks at the q-tile index, k/v at the kv index.
        return [
            pl.BlockSpec((1, 1, q_tile, d),
                         lambda *g: (g[0], g[1], iq_of(g), 0)),
            pl.BlockSpec((1, 1, kv_tile, d),
                         lambda *g: (g[0], g[1], jk_of(g), 0)),
            pl.BlockSpec((1, 1, kv_tile, d),
                         lambda *g: (g[0], g[1], jk_of(g), 0)),
            pl.BlockSpec((1, 1, q_tile, d),
                         lambda *g: (g[0], g[1], iq_of(g), 0)),
            pl.BlockSpec((1, 1, 1, q_tile),
                         lambda *g: (g[0], g[1], 0, iq_of(g))),
            pl.BlockSpec((1, 1, 1, q_tile),
                         lambda *g: (g[0], g[1], 0, iq_of(g))),
        ]

    rep = 1
    if has_bias:
        assert bias is not None and bias.ndim == 4 and n % bias.shape[0] == 0
        rep = n // bias.shape[0]
    # Mesh-local bias group (rep == 1, e.g. the shard-mapped DAP layout with
    # one bias row per attention row): the dq sweep's ds tiles ARE dbias —
    # emit them as a second output and skip the bias-reduction sweep
    # entirely (3 recompute sweeps -> 2).
    fuse_dbias = has_bias and rep == 1

    base_ops = [q, k, v, do, lse.reshape(n, h, 1, sq),
                delta.reshape(n, h, 1, sq)]

    # --- sweep 1: dq (+ dbias when the bias group is mesh-local),
    #     grid (N, H, nq, nkv), KV innermost ---
    in_specs = qkv_specs(lambda g: g[2], lambda g: g[3])
    operands = list(base_ops)
    if has_bias:
        in_specs.append(pl.BlockSpec(
            (1, 1, q_tile, kv_tile),
            lambda i, j, iq, jk: (i // rep, j, iq, jk)))
        operands.append(bias)
    if has_mask:
        assert mask is not None and mask.shape == (n, 1, skv)
        in_specs.append(pl.BlockSpec((1, 1, kv_tile),
                                     lambda i, j, iq, jk: (i, 0, jk)))
        operands.append(mask)
    out_specs = [pl.BlockSpec((1, 1, q_tile, d),
                              lambda i, j, iq, jk: (i, j, iq, 0))]
    out_shape = [jax.ShapeDtypeStruct((n, h, sq, d), jnp.float32)]
    if fuse_dbias:
        out_specs.append(pl.BlockSpec((1, 1, q_tile, kv_tile),
                                      lambda i, j, iq, jk: (i, j, iq, jk)))
        out_shape.append(jax.ShapeDtypeStruct((n, h, sq, skv), jnp.float32))
    outs1 = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, kv_len=kv_len,
                          kv_tile=kv_tile, has_bias=has_bias,
                          has_mask=has_mask, emit_dbias=fuse_dbias),
        grid=(n, h, nq, nkv),
        in_specs=in_specs,
        out_specs=out_specs if fuse_dbias else out_specs[0],
        out_shape=out_shape if fuse_dbias else out_shape[0],
        scratch_shapes=[pltpu.VMEM((q_tile, d), jnp.float32)],
        interpret=interpret,
    )(*operands)
    dq = outs1[0] if fuse_dbias else outs1
    dbias_fused = outs1[1] if fuse_dbias else None

    # --- sweep 2: dk/dv (+ mask reduction), grid (N, H, nkv, nq), q inner ---
    in_specs = qkv_specs(lambda g: g[3], lambda g: g[2])
    operands = list(base_ops)
    if has_bias:
        in_specs.append(pl.BlockSpec(
            (1, 1, q_tile, kv_tile),
            lambda i, j, jk, iq: (i // rep, j, iq, jk)))
        operands.append(bias)
    if has_mask:
        in_specs.append(pl.BlockSpec((1, 1, kv_tile),
                                     lambda i, j, jk, iq: (i, 0, jk)))
        operands.append(mask)
    kv_spec = pl.BlockSpec((1, 1, kv_tile, d),
                           lambda i, j, jk, iq: (i, j, jk, 0))
    out_specs = [kv_spec, kv_spec]
    out_shape = [jax.ShapeDtypeStruct((n, h, skv, d), jnp.float32),
                 jax.ShapeDtypeStruct((n, h, skv, d), jnp.float32)]
    scratch = [pltpu.VMEM((kv_tile, d), jnp.float32),
               pltpu.VMEM((kv_tile, d), jnp.float32)]
    if has_mask:
        out_specs.append(pl.BlockSpec((1, 1, 1, kv_tile),
                                      lambda i, j, jk, iq: (i, j, 0, jk)))
        out_shape.append(jax.ShapeDtypeStruct((n, h, 1, skv), jnp.float32))
        scratch.append(pltpu.VMEM((8, kv_tile), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, kv_len=kv_len,
                          kv_tile=kv_tile, has_bias=has_bias,
                          has_mask=has_mask),
        grid=(n, h, nkv, nq),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(*operands)
    dk, dv = outs[0], outs[1]
    dmask_h = outs[2].reshape(n, h, skv) if has_mask else None

    # --- sweep 3: dbias, grid (B, H, nq, nkv, rep), bias group innermost.
    #     Skipped when the dq sweep already emitted dbias (rep == 1). ---
    dbias = None
    if fuse_dbias:
        dbias = dbias_fused
    elif has_bias:
        nb = bias.shape[0]
        in_specs = [
            pl.BlockSpec((1, 1, q_tile, d),
                         lambda b, j, iq, jk, r: (b * rep + r, j, iq, 0)),
            pl.BlockSpec((1, 1, kv_tile, d),
                         lambda b, j, iq, jk, r: (b * rep + r, j, jk, 0)),
            pl.BlockSpec((1, 1, kv_tile, d),
                         lambda b, j, iq, jk, r: (b * rep + r, j, jk, 0)),
            pl.BlockSpec((1, 1, q_tile, d),
                         lambda b, j, iq, jk, r: (b * rep + r, j, iq, 0)),
            pl.BlockSpec((1, 1, 1, q_tile),
                         lambda b, j, iq, jk, r: (b * rep + r, j, 0, iq)),
            pl.BlockSpec((1, 1, 1, q_tile),
                         lambda b, j, iq, jk, r: (b * rep + r, j, 0, iq)),
            pl.BlockSpec((1, 1, q_tile, kv_tile),
                         lambda b, j, iq, jk, r: (b, j, iq, jk)),
        ]
        operands = list(base_ops) + [bias]
        if has_mask:
            in_specs.append(pl.BlockSpec(
                (1, 1, kv_tile),
                lambda b, j, iq, jk, r: (b * rep + r, 0, jk)))
            operands.append(mask)
        dbias = pl.pallas_call(
            functools.partial(_bwd_dbias_kernel, scale=scale, kv_len=kv_len,
                              kv_tile=kv_tile, has_mask=has_mask),
            grid=(nb, h, nq, nkv, rep),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, q_tile, kv_tile),
                                   lambda b, j, iq, jk, r: (b, j, iq, jk)),
            out_shape=jax.ShapeDtypeStruct((nb, h, sq, skv), jnp.float32),
            scratch_shapes=[pltpu.VMEM((q_tile, kv_tile), jnp.float32)],
            interpret=interpret,
        )(*operands)

    return dq, dk, dv, dbias, dmask_h
