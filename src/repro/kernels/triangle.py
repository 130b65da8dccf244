"""Fused tiled triangle-multiplication + outer-product-mean kernels.

FastFold's kernel profiling (§V) and ScaleFold's post-attention breakdown both
point at the pair stack's einsum+gate+norm chains once attention is fused:
the triangular multiplicative updates materialize a full ``(B, i, j, c)``
fp32 product of the gathered ``(B, r, k, c)`` operand before the output
LayerNorm/projection/gate consume it, and the outer-product-mean materializes
a ``(B, i, j, c, c)`` fp32 outer-product transient before the
mask-normalization and c²→d projection collapse it. Both transients dominate
pair-stack HBM traffic at long sequence length. This module fuses each chain
into one sweep so the transient never hits HBM at full size.

Three legs per op (selected by ``ops.fused_triangle_mult`` /
``ops.fused_outer_product_mean``):

* **Pallas TPU kernel** (``fused_triangle_pallas`` / ``fused_opm_pallas``) —
  the target. Triangle: grid ``(B, I/i_t, J/j_t, K/k_t)`` with the
  contraction (k) innermost; each cell loads raw ``a``/gate/mask tiles,
  applies the input gating + pair mask in VMEM (the gated left operand never
  round-trips to HBM), and accumulates the ``(C, i_t, j_t)`` fp32 product in
  scratch; the epilogue at the last k step runs the output LayerNorm (fp32,
  one-pass E[x²]−E[x]² stats, lane-masked for padded C), the c→d output
  GEMM, and the ``bias_sigmoid_mul`` output gate before the single HBM write
  of the ``(i_t, j_t, D)`` result — plus the per-tile (mean, inv) stats the
  recompute backward reuses. OPM: grid ``(B, I/i_t, J/j_t, S/s_t)`` with the
  sequence (s) innermost, accumulating the fp32 outer product in scratch as
  C slabs of ``(i_t·C, j_t)`` (one per right channel, so every VMEM access
  is row-aligned); the epilogue contracts c² → d in VMEM and divides by the
  fp32 mask normalization, so the ``(B, i, j, c, c)`` transient exists only
  as one tile.

* **XLA-native leg** (``fused_triangle_xla`` / ``fused_opm_xla``) — non-TPU
  backends (mirrors ``flash_attention_xla``): a ``lax.scan`` over j output
  blocks with the same epilogue math fused into each block, bounding the
  fp32 transient at ``(B, I, j_block, C)`` (triangle) /
  ``(B, I, j_block, C²)`` (OPM) instead of the full ``(B, I, J, ·)``.

* **jnp oracle** (``ref.triangle_mult_ref`` / ``ref.outer_product_mean_ref``)
  — the materialized baseline used for parity tests, for the plan's oracle
  legs (``KernelPolicy(enabled=False)`` / ``triangle='oracle'`` /
  ``opm='oracle'`` — the old env toggles, see repro/exec/envcompat.py), and
  for out-of-envelope dtypes.

Backward: a recompute ``custom_vjp`` (defined in ops.py over
``triangle_mult_bwd`` / ``opm_bwd`` below) saves only the inputs plus the
per-tile LayerNorm stats (mean, inv) — the backward rebuilds the product
tile-by-tile over j blocks in one ``lax.scan``, so the fp32 transient of the
backward matches the forward's bound instead of storing ``(B, I, J, C)`` /
``(B, I, J, C²)`` residuals.

Tiling knobs: the triangle op's ``tile`` is the k accumulation tile of the
Pallas grid and the j output block of the XLA leg + backward recompute; the
OPM op's ``tile`` is the s accumulation tile of the Pallas grid and the j
output block of the XLA leg + backward. The AutoChunk planner
(repro.memory.autochunk) picks both (``tri_k_tile`` / ``opm_s_tile``)
jointly with the attention/chunk knobs against the HBM budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import KERNEL_PRECISION

LANE = 128
OPM_NORM_EPS = 1e-3  # AlphaFold's outer-product-mean mask-norm epsilon
# Default k/s accumulation tile of the Pallas grids when the knob is 0. The
# triangle kernel's k tile sits in the lane (minor) position of its mask
# block, so on TPU it is a multiple of 128 unless one tile covers all of K
# (``_lane_tile``).
DEFAULT_PALLAS_TILE = 128
# Scoped-VMEM limit for the pair-stack kernels. Their double-buffered
# (j_t, k_t, C) operand tiles exceed the 16 MiB default at C = 128; a v5e
# core has 128 MiB of VMEM.
VMEM_LIMIT_BYTES = 64 << 20


def _pad_to(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _lane_tile(n: int, tile: int) -> int:
    """Tile of a dim that is the minor (lane) dim of some block: the TPU
    lowering needs it to be a multiple of 128 or the whole (padded) extent."""
    full = _pad_to(n, 8)
    if tile >= full:
        return full
    return min(_pad_to(tile, LANE), _pad_to(n, LANE))


def triangle_gate_a(a_lin, ga, mask):
    """Input gating + pair mask of the left triangle operand:
    ``(a_lin * sigmoid(ga)).astype(dt) * mask`` with fp32 sigmoid. On the
    Pallas leg this runs in VMEM per tile; here it is the shared jnp form
    for the XLA leg and the backward recompute (XLA fuses it into the
    consumer einsum — the gated copy is never a standalone HBM tensor)."""
    af = a_lin.astype(jnp.float32) * jax.nn.sigmoid(ga.astype(jnp.float32))
    return af.astype(a_lin.dtype) * mask.astype(a_lin.dtype)[..., None]


# ---------------------------------------------------------------------------
# Triangle multiplicative update — Pallas forward
# ---------------------------------------------------------------------------


def _tri_kernel(a_ref, ga_ref, mk_ref, b_ref, gam_ref, bet_ref, w_ref,
                bo_ref, gl_ref, gb_ref, o_ref, mean_ref, inv_ref, acc_ref,
                *, eps: float, c_actual: int):
    kk = pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Input gating + pair mask fused in VMEM (the gated a never hits HBM).
    a = (a_ref[0].astype(jnp.float32)
         * jax.nn.sigmoid(ga_ref[0].astype(jnp.float32))).astype(a_ref.dtype)
    # Channel-major (C, i_t, k_t): the (i_t, k_t) mask tile then broadcasts
    # over the leading dim, with no lane-to-sublane relayout of the mask.
    a = a.transpose(2, 0, 1) * mk_ref[0].astype(a_ref.dtype)[None]
    b = b_ref[0]                                   # (j_t, k_t, C)
    # o[c, i, j] += sum_k a[i, k, c] * b[j, k, c]: batch over c, contract k.
    acc_ref[...] += jax.lax.dot_general(
        a, b.transpose(2, 0, 1),
        (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
        precision=KERNEL_PRECISION,
    )

    @pl.when(kk == n_k - 1)
    def _epilogue():
        o = acc_ref[...].transpose(1, 2, 0)        # (i_t, j_t, C)
        i_t, j_t, cp = o.shape
        o2 = o.reshape(i_t * j_t, cp)
        if c_actual != cp:
            lane = jax.lax.broadcasted_iota(jnp.int32, o2.shape, 1)
            o2 = jnp.where(lane < c_actual, o2, 0.0)
        cnt = jnp.float32(c_actual)
        mean = jnp.sum(o2, axis=-1, keepdims=True) / cnt
        var = jnp.maximum(jnp.sum(o2 * o2, axis=-1, keepdims=True) / cnt
                          - mean * mean, 0.0)
        inv = jax.lax.rsqrt(var + eps)
        # Padded-C lanes: gamma/beta are zero-padded, so y vanishes there.
        y = ((o2 - mean) * inv * gam_ref[...][0].astype(jnp.float32)
             + bet_ref[...][0].astype(jnp.float32)).astype(o_ref.dtype)
        z = jax.lax.dot_general(
            y, w_ref[...].astype(y.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=KERNEL_PRECISION,
        ) + bo_ref[...][0].astype(jnp.float32)
        gl = (gl_ref[0].reshape(i_t * j_t, -1).astype(jnp.float32)
              + gb_ref[...][0].astype(jnp.float32))
        outv = jax.nn.sigmoid(gl) * z
        o_ref[0] = outv.reshape(i_t, j_t, -1).astype(o_ref.dtype)
        mean_ref[0] = mean.reshape(i_t, j_t)
        inv_ref[0] = inv.reshape(i_t, j_t)


@functools.partial(jax.jit, static_argnames=("eps", "k_tile", "interpret"))
def fused_triangle_pallas(
    a_lin: jax.Array,     # (B, I, K, C) left projection, pre-gate
    ga: jax.Array,        # (B, I, K, C) left gate logits
    mask: jax.Array,      # (B, I, K) pair mask
    b: jax.Array,         # (B, J, K, C) right operand (gated+masked, gathered)
    gamma: jax.Array,     # (C,) output LN
    beta: jax.Array,
    w_out: jax.Array,     # (C, D) output projection
    b_out: jax.Array,     # (D,)
    g_lin: jax.Array,     # (B, I, J, D) output gate logits, pre-bias
    g_bias: jax.Array,    # (D,)
    *,
    eps: float = 1e-5,
    k_tile: int = 0,
    interpret: bool = False,
):
    """Fused triangle multiplicative update (see module docstring).

    Returns (out (B, I, J, D) in g_lin.dtype, mean (B, I, J) fp32,
    inv (B, I, J) fp32) — the stats feed the recompute backward."""
    bsz, i_len, k_len, c = a_lin.shape
    j_len = b.shape[1]
    d = w_out.shape[1]
    dt = a_lin.dtype

    i_t = min(16, _pad_to(i_len, 8))
    j_t = min(128, _pad_to(j_len, 8))
    k_t = _lane_tile(k_len, k_tile or DEFAULT_PALLAS_TILE)
    ip, jp, kp = _pad_to(i_len, i_t), _pad_to(j_len, j_t), _pad_to(k_len, k_t)
    cp, dp = _pad_to(c, LANE), _pad_to(d, LANE)

    def pad4(x, n1, n2, n3):
        return jnp.pad(x, ((0, 0), (0, n1 - x.shape[1]),
                           (0, n2 - x.shape[2]), (0, n3 - x.shape[3])))

    a_p = pad4(a_lin, ip, kp, cp)
    ga_p = pad4(ga, ip, kp, cp)
    mk_p = jnp.pad(mask, ((0, 0), (0, ip - i_len), (0, kp - k_len)))
    b_p = pad4(b, jp, kp, cp)
    gl_p = pad4(g_lin, ip, jp, dp)
    gam_p = jnp.pad(gamma, (0, cp - c)).reshape(1, cp)
    bet_p = jnp.pad(beta, (0, cp - c)).reshape(1, cp)
    w_p = jnp.pad(w_out, ((0, cp - c), (0, dp - d)))
    bo_p = jnp.pad(b_out, (0, dp - d)).reshape(1, dp)
    gb_p = jnp.pad(g_bias, (0, dp - d)).reshape(1, dp)

    grid = (bsz, ip // i_t, jp // j_t, kp // k_t)
    out, mean, inv = pl.pallas_call(
        functools.partial(_tri_kernel, eps=eps, c_actual=c),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, i_t, k_t, cp), lambda b_, i, j, k: (b_, i, k, 0)),
            pl.BlockSpec((1, i_t, k_t, cp), lambda b_, i, j, k: (b_, i, k, 0)),
            pl.BlockSpec((1, i_t, k_t), lambda b_, i, j, k: (b_, i, k)),
            pl.BlockSpec((1, j_t, k_t, cp), lambda b_, i, j, k: (b_, j, k, 0)),
            pl.BlockSpec((1, cp), lambda b_, i, j, k: (0, 0)),
            pl.BlockSpec((1, cp), lambda b_, i, j, k: (0, 0)),
            pl.BlockSpec((cp, dp), lambda b_, i, j, k: (0, 0)),
            pl.BlockSpec((1, dp), lambda b_, i, j, k: (0, 0)),
            pl.BlockSpec((1, i_t, j_t, dp), lambda b_, i, j, k: (b_, i, j, 0)),
            pl.BlockSpec((1, dp), lambda b_, i, j, k: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, i_t, j_t, dp), lambda b_, i, j, k: (b_, i, j, 0)),
            pl.BlockSpec((1, i_t, j_t), lambda b_, i, j, k: (b_, i, j)),
            pl.BlockSpec((1, i_t, j_t), lambda b_, i, j, k: (b_, i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, ip, jp, dp), dt),
            jax.ShapeDtypeStruct((bsz, ip, jp), jnp.float32),
            jax.ShapeDtypeStruct((bsz, ip, jp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((cp, i_t, j_t), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(a_p, ga_p, mk_p, b_p, gam_p, bet_p, w_p, bo_p, gl_p, gb_p)
    return (out[:, :i_len, :j_len, :d], mean[:, :i_len, :j_len],
            inv[:, :i_len, :j_len])


# ---------------------------------------------------------------------------
# Triangle — XLA-native leg (non-TPU backends) + recompute backward
# ---------------------------------------------------------------------------


def _tri_block(a, b_blk, gl_blk, gamma, beta, w_out, b_out, g_bias, *, eps):
    """One fused j-block: k-contraction, output LN (fp32 two-pass stats),
    c→d projection, sigmoid output gate. Returns (out, mean, inv)."""
    o = jnp.einsum("bikc,bjkc->bijc", a, b_blk,
                   preferred_element_type=jnp.float32)
    mean = jnp.mean(o, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(o - mean), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    y = ((o - mean) * inv * gamma.astype(jnp.float32)
         + beta.astype(jnp.float32)).astype(a.dtype)
    z = jnp.einsum("bijc,cd->bijd", y, w_out.astype(a.dtype),
                   preferred_element_type=jnp.float32)
    z = z + b_out.astype(jnp.float32)
    s = jax.nn.sigmoid(gl_blk.astype(jnp.float32)
                       + g_bias.astype(jnp.float32))
    return (s * z).astype(gl_blk.dtype), mean[..., 0], inv[..., 0]


def _split_j(x, axis: int, nb: int, jb: int):
    """Pad axis to nb*jb and move the block axis to the front for lax.scan."""
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, nb * jb - x.shape[axis])
    xp = jnp.pad(x, pad)
    shape = xp.shape[:axis] + (nb, jb) + xp.shape[axis + 1:]
    return jnp.moveaxis(xp.reshape(shape), axis, 0)


def _merge_j(x, axis: int, j_len: int):
    """Inverse of _split_j on the stacked scan output (nb leading)."""
    y = jnp.moveaxis(x, 0, axis)
    shape = y.shape[:axis] + (-1,) + y.shape[axis + 2:]
    y = y.reshape(shape)
    return jax.lax.slice_in_dim(y, 0, j_len, axis=axis)


def fused_triangle_xla(a, b_full, g_lin, gamma, beta, w_out, b_out, g_bias,
                       *, eps: float = 1e-5, j_block: int = 0):
    """XLA-native fused triangle update: lax.scan over j output blocks, the
    LN/projection/gate epilogue fused into each block — the fp32 product
    transient is bounded at (B, I, j_block, C). ``a`` is the gated+masked
    left operand (triangle_gate_a). Returns (out, mean, inv) like the
    kernel."""
    j_len = b_full.shape[1]
    jb = min(j_block or j_len, j_len)
    nb = _ceil_div(j_len, jb)
    if nb <= 1:
        return _tri_block(a, b_full, g_lin, gamma, beta, w_out, b_out,
                          g_bias, eps=eps)
    bs = _split_j(b_full, 1, nb, jb)
    gls = _split_j(g_lin, 2, nb, jb)

    def step(_, xs):
        bb, gl = xs
        return None, _tri_block(a, bb, gl, gamma, beta, w_out, b_out,
                                g_bias, eps=eps)

    _, (outs, means, invs) = jax.lax.scan(step, None, (bs, gls))
    return (_merge_j(outs, 2, j_len), _merge_j(means, 2, j_len),
            _merge_j(invs, 2, j_len))


def triangle_mult_bwd(eps: float, tile: int, res, dout):
    """Recompute backward for ops.fused_triangle_mult: rebuilds the product
    tile-by-tile over j blocks from the saved inputs + per-tile (mean, inv)
    stats — no (B, I, J, C) residual. Returns grads for every diff input."""
    (a_lin, ga, mask, b_full, gamma, beta, w_out, b_out, g_lin, g_bias,
     mean, inv, out) = res
    f32 = jnp.float32
    sig = jax.nn.sigmoid(ga.astype(f32))
    u = (a_lin.astype(f32) * sig).astype(a_lin.dtype)
    a = u * mask.astype(a_lin.dtype)[..., None]
    j_len = b_full.shape[1]
    gam = gamma.astype(f32)

    def block(b_blk, gl_blk, mean_b, inv_b, g_b, out_b):
        o = jnp.einsum("bikc,bjkc->bijc", a, b_blk,
                       preferred_element_type=f32)
        xhat = (o - mean_b[..., None]) * inv_b[..., None]
        y = (xhat * gam + beta.astype(f32)).astype(a.dtype)
        s = jax.nn.sigmoid(gl_blk.astype(f32) + g_bias.astype(f32))
        gf = g_b.astype(f32)
        dz = gf * s
        # Output-gate cotangent from the saved output: g·z·s(1-s) with
        # z = out/s rearranged to g·out·(1-s) — no z recompute, no division.
        dgl = gf * out_b.astype(f32) * (1.0 - s)
        dy = jnp.einsum("bijd,cd->bijc", dz, w_out.astype(f32))
        dw = jnp.einsum("bijc,bijd->cd", y.astype(f32), dz)
        dgamma = jnp.einsum("bijc,bijc->c", dy, xhat)
        dbeta = jnp.sum(dy, axis=(0, 1, 2))
        dbo = jnp.sum(dz, axis=(0, 1, 2))
        dgb = jnp.sum(dgl, axis=(0, 1, 2))
        gg = dy * gam
        do = inv_b[..., None] * (
            gg - jnp.mean(gg, axis=-1, keepdims=True)
            - xhat * jnp.mean(gg * xhat, axis=-1, keepdims=True))
        da = jnp.einsum("bijc,bjkc->bikc", do, b_blk.astype(f32))
        db = jnp.einsum("bijc,bikc->bjkc", do, a.astype(f32))
        return da, db, dgl, dw, dgamma, dbeta, dbo, dgb

    jb = min(tile or j_len, j_len)
    nb = _ceil_div(j_len, jb)
    if nb <= 1:
        (da, db_full, dgl, dw, dgamma, dbeta, dbo, dgb) = block(
            b_full, g_lin, mean, inv, dout, out)
    else:
        bs = _split_j(b_full, 1, nb, jb)
        gls = _split_j(g_lin, 2, nb, jb)
        # Padded-j stats are zero-padded (finite); padded dout rows are zero
        # so every padded contribution vanishes.
        means = _split_j(mean, 2, nb, jb)
        invs = _split_j(inv, 2, nb, jb)
        gs = _split_j(dout, 2, nb, jb)
        outs = _split_j(out, 2, nb, jb)

        def step(carry, xs):
            da_c, dw_c, dga_c, dbe_c, dbo_c, dgb_c = carry
            bb, gl, me, iv, g_b, out_b = xs
            da, db, dgl, dw, dgamma, dbeta, dbo, dgb = block(
                bb, gl, me, iv, g_b, out_b)
            return ((da_c + da, dw_c + dw, dga_c + dgamma, dbe_c + dbeta,
                     dbo_c + dbo, dgb_c + dgb), (db, dgl))

        zeros = (
            jnp.zeros(a.shape, f32), jnp.zeros(w_out.shape, f32),
            jnp.zeros(gamma.shape, f32), jnp.zeros(beta.shape, f32),
            jnp.zeros(b_out.shape, f32), jnp.zeros(g_bias.shape, f32),
        )
        carry, (dbs, dgls) = jax.lax.scan(step, zeros,
                                          (bs, gls, means, invs, gs, outs))
        da, dw, dgamma, dbeta, dbo, dgb = carry
        db_full = _merge_j(dbs, 1, j_len)
        dgl = _merge_j(dgls, 2, j_len)

    # Input-gating adjoints (a = (a_lin * sigmoid(ga)).astype(dt) * mask).
    da_m = da * mask.astype(f32)[..., None]
    da_lin = (da_m * sig).astype(a_lin.dtype)
    dga = (da_m * a_lin.astype(f32) * sig * (1.0 - sig)).astype(ga.dtype)
    dmask = jnp.einsum("bikc,bikc->bik", da, u.astype(f32)).astype(mask.dtype)
    return (da_lin, dga, dmask, db_full.astype(b_full.dtype),
            dgamma.astype(gamma.dtype), dbeta.astype(beta.dtype),
            dw.astype(w_out.dtype), dbo.astype(b_out.dtype),
            dgl.astype(g_lin.dtype), dgb.astype(g_bias.dtype))


# ---------------------------------------------------------------------------
# Outer-product-mean — Pallas forward
# ---------------------------------------------------------------------------


def _opm_kernel(at_ref, b_ref, nrm_ref, wt_ref, bias_ref, o_ref, acc_ref,
                zt_ref, *, i_t: int, c: int):
    ss = pl.program_id(3)
    n_s = pl.num_programs(3)

    @pl.when(ss == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a_t = at_ref[0]                                 # (i_t*C, s_t), rows (i, x)

    # acc[y][(i, x), j] += sum_s a[s, i, x] * b[s, j, y]: one MXU GEMM per
    # right channel y, each with the whole i-tile's channels as rows.
    def accumulate(y, carry):
        acc_ref[y] += jax.lax.dot_general(
            a_t, b_ref[0, y], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=KERNEL_PRECISION)
        return carry

    jax.lax.fori_loop(0, c, accumulate, 0)

    @pl.when(ss == n_s - 1)
    def _epilogue():
        # c²→d projection, transposed: zt[i][d, j] = sum_{x, y} W[x, y, d]
        # * acc[y][(i, x), j]. Every operand is a row-aligned slice, so the
        # (i, j, x, y) outer-product tile is never re-laid out in VMEM.
        zt_ref[...] = jnp.zeros_like(zt_ref)

        def project(y, carry):
            w_y = wt_ref[y]                         # (D, C_x)
            for i in range(i_t):
                slab = acc_ref[y, pl.ds(i * c, c), :].astype(w_y.dtype)
                zt_ref[i] += jax.lax.dot_general(
                    w_y, slab, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=KERNEL_PRECISION)
            return carry

        jax.lax.fori_loop(0, c, project, 0)
        nrm = nrm_ref[0]                            # (i_t, j_t) fp32
        bias = bias_ref[...].astype(jnp.float32)    # (1, D)
        for i in range(i_t):
            # The mask-norm is a per-(i, j) scalar and the projection linear,
            # so dividing after the projection is the same normalization.
            z = zt_ref[i] / (nrm[i:i + 1, :] + OPM_NORM_EPS)   # (D, j_t)
            o_ref[0, i] = (z.T + bias).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("s_tile", "interpret"))
def fused_opm_pallas(
    a: jax.Array,        # (B, S, I, C) left projection, masked
    b: jax.Array,        # (B, S, J, C) right projection, masked (gathered)
    mask_a: jax.Array,   # (B, S, I)
    mask_b: jax.Array,   # (B, S, J)
    w: jax.Array,        # (C*C, D)
    bias: jax.Array,     # (D,)
    *,
    s_tile: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """Fused outer-product-mean (see module docstring). Returns
    (B, I, J, D) in a.dtype.

    Layouts staged here for the TPU (8, 128) block rule: the left operand
    as (B, I·C, S) (s in lanes, so the s tile is a lane tile), the right as
    (B, C, S, J), the projection as (C_y, D, C_x) and the (B, I, J) fp32
    mask-norm computed up front (it is an (I, J, S) contraction of 0/1
    masks — negligible next to the c² product)."""
    bsz, s_len, i_len, c = a.shape
    j_len = b.shape[2]
    d = w.shape[1]
    dt = a.dtype

    cp = _pad_to(c, 8)
    i_t = min(8, _pad_to(i_len, 8))
    j_t = min(LANE, _pad_to(j_len, 8))
    s_t = _lane_tile(s_len, s_tile or DEFAULT_PALLAS_TILE)
    ip, jp, sp = _pad_to(i_len, i_t), _pad_to(j_len, j_t), _pad_to(s_len, s_t)
    dp = _pad_to(d, LANE)

    a_t = jnp.pad(a, ((0, 0), (0, sp - s_len), (0, ip - i_len),
                      (0, cp - c)))
    a_t = a_t.transpose(0, 2, 3, 1).reshape(bsz, ip * cp, sp)
    b_t = jnp.pad(b, ((0, 0), (0, sp - s_len), (0, jp - j_len),
                      (0, cp - c))).transpose(0, 3, 1, 2)
    f32 = jnp.float32
    nrm = jnp.einsum("bsi,bsj->bij", mask_a.astype(f32), mask_b.astype(f32))
    nrm = jnp.pad(nrm, ((0, 0), (0, ip - i_len), (0, jp - j_len)))
    w3 = jnp.pad(w.reshape(c, c, d), ((0, cp - c), (0, cp - c), (0, dp - d)))
    w_t = w3.transpose(1, 2, 0).astype(dt)          # (C_y, D, C_x)
    bias_p = jnp.pad(bias, (0, dp - d)).reshape(1, dp)

    grid = (bsz, ip // i_t, jp // j_t, sp // s_t)
    out = pl.pallas_call(
        functools.partial(_opm_kernel, i_t=i_t, c=cp),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, i_t * cp, s_t), lambda b_, i, j, s: (b_, i, s)),
            pl.BlockSpec((1, cp, s_t, j_t), lambda b_, i, j, s: (b_, 0, s, j)),
            pl.BlockSpec((1, i_t, j_t), lambda b_, i, j, s: (b_, i, j)),
            pl.BlockSpec((cp, dp, cp), lambda b_, i, j, s: (0, 0, 0)),
            pl.BlockSpec((1, dp), lambda b_, i, j, s: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, i_t, j_t, dp),
                               lambda b_, i, j, s: (b_, i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, ip, jp, dp), dt),
        scratch_shapes=[
            pltpu.VMEM((cp, i_t * cp, j_t), jnp.float32),
            pltpu.VMEM((i_t, dp, j_t), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(a_t, b_t, nrm, w_t, bias_p)
    return out[:, :i_len, :j_len, :d]


# ---------------------------------------------------------------------------
# OPM — XLA-native leg + recompute backward
# ---------------------------------------------------------------------------


def _opm_block(a, b_blk, mask_a, mask_b_blk, w, bias):
    """One fused OPM j-block on the XLA leg: the mask-norm divides by a
    per-(i, j) scalar and the c²→d projection is linear, so the contraction
    reassociates — ``(Σ_s a⊗b / denom) @ w == (a · (b · w3)) / denom`` with
    ``w3 = w.reshape(c, c, d)``. The (B, I, J, C, C) outer-product tensor is
    never formed AT ALL on this leg (the Pallas kernel accumulates it
    per-tile in VMEM instead); the largest transient is the
    (B, S, j_block, C, D) half-contraction ``h``, linear in j_block. The
    reassociated GEMMs are also the layouts XLA:CPU runs ~5x faster than
    the outer-product einsum — this is where the fused path's wall-time win
    over the materialized baseline comes from off-TPU."""
    f32 = jnp.float32
    c = a.shape[-1]
    w3 = w.reshape(c, c, w.shape[-1]).astype(a.dtype)
    h = jnp.einsum("bsjy,xyd->bsjxd", b_blk, w3,
                   preferred_element_type=f32)
    numer = jnp.einsum("bsix,bsjxd->bijd", a, h,
                       preferred_element_type=f32)
    norm = jnp.einsum("bsi,bsj->bij", mask_a.astype(f32),
                      mask_b_blk.astype(f32))
    out = numer / (norm[..., None] + OPM_NORM_EPS) + bias.astype(f32)
    return out.astype(a.dtype)


def fused_opm_xla(a, b_full, mask_a, mask_b, w, bias, *, j_block: int = 0):
    """XLA-native fused OPM: lax.scan over j output blocks with the
    normalization + projection fused into each block — the fp32
    (B, I, j_block, C, C) transient never reaches full-J size."""
    j_len = b_full.shape[2]
    jb = min(j_block or j_len, j_len)
    nb = _ceil_div(j_len, jb)
    if nb <= 1:
        return _opm_block(a, b_full, mask_a, mask_b, w, bias)
    bs = _split_j(b_full, 2, nb, jb)
    mbs = _split_j(mask_b, 2, nb, jb)

    def step(_, xs):
        bb, mb = xs
        return None, _opm_block(a, bb, mask_a, mb, w, bias)

    _, outs = jax.lax.scan(step, None, (bs, mbs))
    return _merge_j(outs, 2, j_len)


def opm_bwd(tile: int, res, dout):
    """Recompute backward for ops.fused_outer_product_mean: per j block,
    push the cotangent through the reassociated contraction (see
    _opm_block) — no (B, I, J, C, C) tensor is ever formed; the transients
    are the (B, S, ·, C, D) half-contractions, j-block bounded. The saved
    output gives the mask-norm cotangent directly
    (Σ_x ov·(g@wᵀ) = Σ_d (out - bias)·g), skipping a c²-wide reduction."""
    a, b_full, mask_a, mask_b, w, bias, out = res
    f32 = jnp.float32
    j_len = b_full.shape[2]
    c = a.shape[-1]
    maf = mask_a.astype(f32)
    w3 = w.reshape(c, c, w.shape[-1]).astype(a.dtype)

    def block(b_blk, mb_blk, g_b, out_b):
        # Natural adjoint of the reassociated forward: recompute the right
        # half-contraction h, then da via (u, h) and db/dw via the shared
        # dh = a·u half-contraction — two (s·r·j_block·c·d)-MAC GEMMs total,
        # never a (i, j, c, c) tensor.
        gf = g_b.astype(f32)
        norm = jnp.einsum("bsi,bsj->bij", maf, mb_blk.astype(f32))
        denom = norm + OPM_NORM_EPS
        u = gf / denom[..., None]
        h = jnp.einsum("bsjy,xyd->bsjxd", b_blk, w3,
                       preferred_element_type=f32)
        da = jnp.einsum("bijd,bsjxd->bsix", u, h)
        dh = jnp.einsum("bsix,bijd->bsjxd", a.astype(f32), u)
        db = jnp.einsum("bsjxd,xyd->bsjy", dh, w3.astype(f32))
        dw = jnp.einsum("bsjy,bsjxd->xyd", b_blk.astype(f32), dh
                        ).reshape(c * c, -1)
        dnorm = -jnp.einsum("bijd,bijd->bij", out_b.astype(f32)
                            - bias.astype(f32), gf) / denom
        dma = jnp.einsum("bij,bsj->bsi", dnorm, mb_blk.astype(f32))
        dmb = jnp.einsum("bij,bsi->bsj", dnorm, maf)
        dbias = jnp.sum(gf, axis=(0, 1, 2))
        return da, db, dma, dmb, dw, dbias

    jb = min(tile or j_len, j_len)
    nb = _ceil_div(j_len, jb)
    if nb <= 1:
        da, db_full, dma, dmb, dw, dbias = block(b_full, mask_b, dout, out)
    else:
        bs = _split_j(b_full, 2, nb, jb)
        mbs = _split_j(mask_b, 2, nb, jb)
        gs = _split_j(dout, 2, nb, jb)
        outs = _split_j(out, 2, nb, jb)

        def step(carry, xs):
            da_c, dma_c, dw_c, dbias_c = carry
            bb, mb, g_b, out_b = xs
            da, db, dma, dmb, dw, dbias = block(bb, mb, g_b, out_b)
            return ((da_c + da, dma_c + dma, dw_c + dw, dbias_c + dbias),
                    (db, dmb))

        zeros = (jnp.zeros(a.shape, f32), jnp.zeros(mask_a.shape, f32),
                 jnp.zeros(w.shape, f32), jnp.zeros(bias.shape, f32))
        carry, (dbs, dmbs) = jax.lax.scan(step, zeros, (bs, mbs, gs, outs))
        da, dma, dw, dbias = carry
        db_full = _merge_j(dbs, 2, j_len)
        dmb = _merge_j(dmbs, 2, j_len)

    return (da.astype(a.dtype), db_full.astype(b_full.dtype),
            dma.astype(mask_a.dtype), dmb.astype(mask_b.dtype),
            dw.astype(w.dtype), dbias.astype(bias.dtype))
