"""Plain reference of the benchmarked AlphaFold-2 model, its loss and its
optimizer, in ``jax.numpy`` and float32.

It imports nothing of the system under test. It follows the published
architecture (Jumper et al. 2021, Supplementary Information, Algorithms
2-32) as the system lays it out: the same parameter tree, the same masks,
the same residual dropout draws (one Bernoulli draw per site from
``jax.random``, shared along the site's axis), the same recycling and the
same simplified structure module and losses. Departures of the system from
the paper that the reference copies, so that the two compute one function:

- the structure module is the CA-only variant (no side chains, no
  torsions); FAPE is computed on CA frames built from the neighbours;
- the recycled distance embedding bins CA distances, not CB;
- every recycling pass draws dropout from the same key as the last one.

Every matrix product goes through ``Numerics.mm``: at ``"fp32"`` the
operands stay float32 and the product runs at ``Precision.HIGHEST``; at
``"fp8"`` each operand, and in the backward pass each cotangent, is first
rounded through float8 e4m3 with a per-tensor scale. That second form is the
control: the reference in the precision a step below the system's
bfloat16.

Memory: attention runs in chunks of groups and the outer product mean in
chunks of rows, each chunk rematerialized in the gradient pass, as is each
Evoformer block, so the reference fits one chip once the program's state is
freed.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
N_MSA_TOK = 23
N_AA = 21
N_DIST_BINS = 64
RELPOS_K = 32
NEG_INF = -1e9
FP8_MAX = 448.0  # largest finite float8_e4m3fn


def _fp8(x):
    """Round to float8 e4m3 with a per-tensor scale that maps the largest
    magnitude to the format's largest finite value."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, FP8_MAX / amax, 1.0)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale


@jax.custom_vjp
def fp8_round(x):
    """``_fp8`` forward, and ``_fp8`` of the cotangent backward: matrix
    products see float8 operands in the forward and the backward pass, each
    tensor scaled on its own, as float8 training does."""
    return _fp8(x)


fp8_round.defvjp(lambda x: (_fp8(x), None), lambda _, ct: (_fp8(ct),))


@dataclasses.dataclass(frozen=True)
class Numerics:
    """How the reference multiplies: ``"fp32"`` (the reference) or ``"fp8"``
    (the control)."""

    kind: str = "fp32"

    def cast(self, x):
        x = x.astype(F32)
        if self.kind == "fp32":
            return x
        if self.kind != "fp8":
            raise ValueError(f"unknown numerics {self.kind!r}")
        return fp8_round(x)

    def mm(self, eq, a, b):
        return jnp.einsum(eq, self.cast(a), self.cast(b), precision=HIGHEST,
                          preferred_element_type=F32)


FP32 = Numerics("fp32")


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes the reference needs, read from a configuration file."""

    d_msa: int
    d_pair: int
    msa_heads: int
    pair_heads: int
    head_dim: int
    opm_dim: int
    tri_mult_dim: int
    transition_factor: int
    dropout_msa: float
    dropout_pair: float
    n_blocks: int
    c_s: int
    ipa_heads: int
    ipa_c_hidden: int
    ipa_qk_points: int
    ipa_v_points: int
    structure_iterations: int
    trans_scale: float
    n_recycle: int
    recycle_bins: int

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        return cls(**{f.name: cfg[f.name] for f in dataclasses.fields(cls)})


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

# The system initializes residual output projections, IPA's point weights,
# biases and LayerNorm offsets to zero. A zero residual projection would make
# each update exactly zero and hide the kernels from the comparison, so these
# leaves are drawn at 0.1 / sqrt(fan_in) instead ("small").
SMALL_SCALE = 0.1


def _dense(d_in, d_out, bias=True, zero=False):
    p = {"w": ("small" if zero else "fan_in", (d_in, d_out))}
    if bias:
        p["b"] = ("small", (d_out,))
    return p


def _ln(d):
    return {"gamma": ("ones", (d,)), "beta": ("small", (d,))}


def _attn(d_in, heads, hd, d_out):
    return {"wqkv": _dense(d_in, 3 * heads * hd, bias=False),
            "wo": _dense(heads * hd, d_out, zero=True),
            "wg": {"w": ("fan_in", (d_in, heads * hd)),
                   "b": ("ones", (heads * hd,))}}


def _block_spec(d: Dims) -> dict:
    dm, dz, hd, c = d.d_msa, d.d_pair, d.head_dim, d.tri_mult_dim
    f = d.transition_factor

    def tri_mult():
        return {"ln_in": _ln(dz), "proj": _dense(dz, 2 * c),
                "gate": _dense(dz, 2 * c), "ln_out": _ln(c),
                "out": _dense(c, dz, zero=True), "gate_out": _dense(dz, dz)}

    def tri_attn():
        return {"ln": _ln(dz), "bias": _dense(dz, d.pair_heads, bias=False),
                "attn": _attn(dz, d.pair_heads, hd, dz)}

    def trans(dd):
        return {"wi": _dense(dd, f * dd), "wo": _dense(f * dd, dd, zero=True)}

    return {
        "msa_row": {"ln_m": _ln(dm), "ln_z": _ln(dz),
                    "bias": _dense(dz, d.msa_heads, bias=False),
                    "attn": _attn(dm, d.msa_heads, hd, dm)},
        "msa_col": {"ln": _ln(dm), "attn": _attn(dm, d.msa_heads, hd, dm)},
        "msa_trans": {"ln": _ln(dm), "mlp": trans(dm)},
        "opm": {"ln": _ln(dm), "proj": _dense(dm, 2 * d.opm_dim),
                "out": _dense(d.opm_dim ** 2, dz, zero=True)},
        "tri_mult_out": tri_mult(), "tri_mult_in": tri_mult(),
        "tri_attn_start": tri_attn(), "tri_attn_end": tri_attn(),
        "pair_trans": {"ln": _ln(dz), "mlp": trans(dz)},
    }


def _structure_spec(d: Dims) -> dict:
    cs, cz, h, c = d.c_s, d.d_pair, d.ipa_heads, d.ipa_c_hidden
    qp, vp = d.ipa_qk_points, d.ipa_v_points
    concat = h * c + h * cz + h * vp * 4
    return {
        "ln_s": _ln(cs), "ln_z": _ln(cz),
        "proj_s": _dense(cs, cs, bias=False),
        "ipa": {"q": _dense(cs, h * c, bias=False),
                "kv": _dense(cs, 2 * h * c, bias=False),
                "q_pts": _dense(cs, h * qp * 3, bias=False),
                "kv_pts": _dense(cs, h * (qp + vp) * 3, bias=False),
                "bias_z": _dense(cz, h, bias=False),
                "head_w": ("small", (h,)),
                "out": _dense(concat, cs, zero=True)},
        "ln_ipa": _ln(cs),
        "trans1": _dense(cs, cs), "trans2": _dense(cs, cs),
        "trans3": _dense(cs, cs, zero=True), "ln_trans": _ln(cs),
        "bb_update": _dense(cs, 6, zero=True),
    }


def param_spec(d: Dims) -> dict:
    """Nested dict of ``(kind, shape)`` leaves; Evoformer leaves carry a
    leading ``n_blocks`` axis (the system scans over stacked blocks)."""
    dm, dz = d.d_msa, d.d_pair
    block = jax.tree.map(lambda leaf: (leaf[0], (d.n_blocks,) + leaf[1], 1),
                         _block_spec(d), is_leaf=_is_spec)
    return {
        "msa_embed": _dense(N_MSA_TOK, dm), "target_embed_m": _dense(N_AA, dm),
        "left_embed": _dense(N_AA, dz), "right_embed": _dense(N_AA, dz),
        "relpos_embed": _dense(2 * RELPOS_K + 1, dz),
        "recycle": {"ln_m": _ln(dm), "ln_z": _ln(dz),
                    "dist_embed": _dense(d.recycle_bins, dz)},
        "evoformer": block,
        "single_proj": _dense(dm, d.c_s),
        "structure": _structure_spec(d),
        "msa_head": _dense(dm, N_MSA_TOK),
        "dist_head": _dense(dz, N_DIST_BINS),
    }


def _is_spec(x):
    """A leaf of ``param_spec``: (kind, shape) or, stacked over blocks,
    (kind, shape, 1)."""
    return isinstance(x, tuple) and isinstance(x[0], str)


def init_params(key, d: Dims):
    """Weights from ``key``: truncated normal (±2σ) at 1/sqrt(fan_in) for the
    input projections, ones for LayerNorm scales and attention gate biases,
    N(0, 1) · 0.1/sqrt(fan_in) for the rest. Run it under ``jax.jit``."""
    spec = param_spec(d)
    leaves, tree = jax.tree.flatten(spec, is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    out = []
    for (kind, shape, *stacked), k in zip(leaves, keys):
        own = shape[len(stacked):]
        fan_in = own[-2] if len(own) >= 2 else own[-1]
        if kind == "ones":
            x = jnp.ones(shape, F32)
        elif kind == "fan_in":
            x = (jax.random.truncated_normal(k, -2.0, 2.0, shape, F32)
                 / math.sqrt(fan_in))
        else:
            x = SMALL_SCALE / math.sqrt(fan_in) * jax.random.normal(k, shape,
                                                                    F32)
        out.append(x)
    return jax.tree.unflatten(tree, out)


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------


def layer_norm(p, x, eps=1e-5):
    x = x.astype(F32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["gamma"] + p["beta"]


def dense(p, x, nx: Numerics):
    y = nx.mm("...i,io->...o", x, p["w"])
    return y + p["b"] if "b" in p else y


def _dropout_add(upd, residual, rate, key, shared_axis):
    """residual + dropout(upd): one Bernoulli draw per site at the update's
    shape with ``shared_axis`` set to 1, kept values scaled by 1/(1-rate)."""
    if key is None or rate == 0.0:
        return residual + upd
    shape = list(upd.shape)
    shape[shared_axis] = 1
    keep = jax.random.bernoulli(key, 1.0 - rate, tuple(shape)).astype(F32)
    return residual + upd * keep / (1.0 - rate)


def _chunks(n, target):
    """Largest divisor of n that is at most target."""
    c = max(1, min(n, target))
    while n % c:
        c -= 1
    return c


def gated_attention(p, x, bias, key_mask, heads, hd, nx: Numerics,
                    group_chunk=64):
    """x (B, G, S, d); bias (B, H, S, S) shared over G or None; key_mask
    (B, G, S) in {0, 1}. Gated multi-head attention over S, groups in
    chunks."""
    b, g, s, _ = x.shape

    def one(xc, mc):
        qkv = nx.mm("bgsd,de->bgse", xc, p["wqkv"]["w"])
        q, k, v = jnp.split(qkv.reshape(b, -1, s, 3 * heads, hd), 3, axis=3)
        logits = nx.mm("bgqhd,bgkhd->bghqk", q, k) / math.sqrt(hd)
        if bias is not None:
            logits = logits + bias[:, None]
        logits = logits + jnp.where(mc > 0, 0.0, NEG_INF)[:, :, None, None, :]
        probs = jax.nn.softmax(logits, axis=-1)
        ctx = nx.mm("bghqk,bgkhd->bgqhd", probs, v).reshape(b, -1, s,
                                                            heads * hd)
        gate = jax.nn.sigmoid(nx.mm("bgsd,de->bgse", xc, p["wg"]["w"])
                              + p["wg"]["b"])
        return dense(p["wo"], gate * ctx, nx)

    gc = _chunks(g, group_chunk)
    if gc == g:
        return one(x, key_mask)
    n = g // gc

    def split(t):
        return t.reshape(b, n, gc, *t.shape[2:]).swapaxes(0, 1)

    out = jax.lax.map(jax.checkpoint(lambda xm: one(*xm)),
                      (split(x), split(key_mask)))
    return out.swapaxes(0, 1).reshape(b, g, s, -1)


def msa_row_attention(p, msa, pair, seq_mask, d: Dims, nx):
    bias = dense(p["bias"], layer_norm(p["ln_z"], pair), nx)  # (B, i, j, H)
    bias = bias.transpose(0, 3, 1, 2)
    b, s, r, _ = msa.shape
    key_mask = jnp.broadcast_to(seq_mask[:, None, :], (b, s, r))
    return gated_attention(p["attn"], layer_norm(p["ln_m"], msa), bias,
                           key_mask, d.msa_heads, d.head_dim, nx)


def msa_col_attention(p, msa, msa_mask, d: Dims, nx):
    x = layer_norm(p["ln"], msa).transpose(0, 2, 1, 3)      # (B, r, s, d)
    out = gated_attention(p["attn"], x, None, msa_mask.transpose(0, 2, 1),
                          d.msa_heads, d.head_dim, nx)
    return out.transpose(0, 2, 1, 3)


def transition(p, x, nx):
    h = jax.nn.relu(dense(p["mlp"]["wi"], layer_norm(p["ln"], x), nx))
    return dense(p["mlp"]["wo"], h, nx)


def outer_product_mean(p, msa, msa_mask, d: Dims, nx, row_chunk=64):
    """mean over s of a_si ⊗ b_sj, normalized by the count of unmasked pairs
    plus 1e-3, projected c² → d_pair; rows i in chunks."""
    c = d.opm_dim
    ab = dense(p["proj"], layer_norm(p["ln"], msa), nx)
    mask = msa_mask[..., None]
    a, bb = ab[..., :c] * mask, ab[..., c:] * mask
    norm = jnp.einsum("bsi,bsj->bij", msa_mask, msa_mask, precision=HIGHEST)
    bsz, _, r, _ = a.shape
    ic = _chunks(r, row_chunk)
    n = r // ic

    def rows(args):
        a_c, norm_c = args                                   # (B,s,ic,c)
        o = nx.mm("bsic,bsjd->bijcd", a_c, bb)
        o = o / (norm_c[..., None, None] + 1e-3)
        return dense(p["out"], o.reshape(o.shape[:3] + (c * c,)), nx)

    a_c = a.reshape(bsz, a.shape[1], n, ic, c).transpose(2, 0, 1, 3, 4)
    norm_c = norm.reshape(bsz, n, ic, r).swapaxes(0, 1)
    out = jax.lax.map(jax.checkpoint(rows), (a_c, norm_c))   # (n,B,ic,r,dz)
    return out.swapaxes(0, 1).reshape(bsz, r, r, -1)


def triangle_mult(p, z, pair_mask, d: Dims, nx):
    """Outgoing update on ``z`` (B, i, k, d): o_ij = Σ_k a_ik b_jk with
    sigmoid-gated, masked projections, then LN → projection → output gate.
    The incoming update is this on the transposed pair."""
    c = d.tri_mult_dim
    zn = layer_norm(p["ln_in"], z)
    ab = dense(p["proj"], zn, nx) * jax.nn.sigmoid(dense(p["gate"], zn, nx))
    ab = ab * pair_mask[..., None]
    a, b = ab[..., :c], ab[..., c:]
    o = nx.mm("bikc,bjkc->bijc", a, b)
    upd = dense(p["out"], layer_norm(p["ln_out"], o), nx)
    return jax.nn.sigmoid(dense(p["gate_out"], zn, nx)) * upd


def triangle_attention(p, z, seq_mask, d: Dims, nx):
    """Around the starting node: row i attends over k with bias b_jk."""
    zn = layer_norm(p["ln"], z)
    bias = dense(p["bias"], zn, nx).transpose(0, 3, 1, 2)    # (B, H, j, k)
    b, i, r, _ = z.shape
    key_mask = jnp.broadcast_to(seq_mask[:, None, :], (b, i, r))
    return gated_attention(p["attn"], zn, bias, key_mask, d.pair_heads,
                           d.head_dim, nx)


def evoformer_block(p, msa, pair, msa_mask, seq_mask, d: Dims, nx, key):
    keys = (list(jax.random.split(key, 8)) if key is not None
            else [None] * 8)
    pair_mask = seq_mask[:, :, None] * seq_mask[:, None, :]
    t = lambda x: x.swapaxes(1, 2)  # noqa: E731  (pair transpose i <-> j)
    msa = _dropout_add(msa_row_attention(p["msa_row"], msa, pair, seq_mask,
                                         d, nx),
                       msa, d.dropout_msa, keys[0], 2)
    msa = msa + msa_col_attention(p["msa_col"], msa, msa_mask, d, nx)
    msa = msa + transition(p["msa_trans"], msa, nx)
    pair = _dropout_add(outer_product_mean(p["opm"], msa, msa_mask, d, nx),
                        pair, d.dropout_pair, keys[1], 1)
    pair = _dropout_add(triangle_mult(p["tri_mult_out"], pair, pair_mask, d,
                                      nx),
                        pair, d.dropout_pair, keys[2], 1)
    pair = _dropout_add(t(triangle_mult(p["tri_mult_in"], t(pair),
                                        t(pair_mask), d, nx)),
                        pair, d.dropout_pair, keys[3], 1)
    pair = _dropout_add(triangle_attention(p["tri_attn_start"], pair,
                                           seq_mask, d, nx),
                        pair, d.dropout_pair, keys[4], 1)
    pair = _dropout_add(t(triangle_attention(p["tri_attn_end"], t(pair),
                                             seq_mask, d, nx)),
                        pair, d.dropout_pair, keys[5], 2)
    pair = pair + transition(p["pair_trans"], pair, nx)
    return msa, pair


# ---------------------------------------------------------------------------
# structure module (CA frames, invariant point attention)
# ---------------------------------------------------------------------------


def _apply(rot, trans, x):
    return jnp.einsum("...ij,...pj->...pi", rot, x,
                      precision=HIGHEST) + trans[..., None, :]


def _invert_apply(rot, trans, x):
    return jnp.einsum("...ji,...pj->...pi", rot, x - trans[..., None, :],
                      precision=HIGHEST)


def _quat_to_rot(q):
    q = q / (jnp.linalg.norm(q, axis=-1, keepdims=True) + 1e-8)
    w, x, y, z = q[..., 0], q[..., 1], q[..., 2], q[..., 3]
    return jnp.stack([
        jnp.stack([1 - 2 * (y * y + z * z), 2 * (x * y - w * z),
                   2 * (x * z + w * y)], -1),
        jnp.stack([2 * (x * y + w * z), 1 - 2 * (x * x + z * z),
                   2 * (y * z - w * x)], -1),
        jnp.stack([2 * (x * z - w * y), 2 * (y * z + w * x),
                   1 - 2 * (x * x + y * y)], -1)], axis=-2)


def ipa(p, s, z, rot, trans, seq_mask, d: Dims, nx):
    b, r, _ = s.shape
    h, c = d.ipa_heads, d.ipa_c_hidden
    qp, vp = d.ipa_qk_points, d.ipa_v_points
    q = dense(p["q"], s, nx).reshape(b, r, h, c)
    k, v = jnp.split(dense(p["kv"], s, nx).reshape(b, r, h, 2 * c), 2, -1)
    q_pts = _apply(rot, trans, dense(p["q_pts"], s, nx).reshape(
        b, r, h * qp, 3)).reshape(b, r, h, qp, 3)
    kv_pts = _apply(rot, trans, dense(p["kv_pts"], s, nx).reshape(
        b, r, h * (qp + vp), 3)).reshape(b, r, h, qp + vp, 3)
    k_pts, v_pts = kv_pts[..., :qp, :], kv_pts[..., qp:, :]
    logits = nx.mm("bihc,bjhc->bhij", q, k) / math.sqrt(3 * c)
    logits = logits + dense(p["bias_z"], z, nx).transpose(0, 3, 1, 2) \
        / math.sqrt(3.0)
    d2 = jnp.sum(jnp.square(q_pts[:, :, None] - k_pts[:, None]), axis=-1)
    w_pt = (jax.nn.softplus(p["head_w"]) / math.sqrt(3.0)
            * math.sqrt(9.0 / (2 * qp)) * 0.5)
    logits = logits - jnp.einsum("bijhp,h->bhij", d2, w_pt,
                                 precision=HIGHEST)
    logits = jnp.where(seq_mask[:, None, None, :] > 0, logits, NEG_INF)
    attn = jax.nn.softmax(logits, axis=-1)
    o_s = nx.mm("bhij,bjhc->bihc", attn, v).reshape(b, r, h * c)
    o_z = nx.mm("bhij,bijc->bihc", attn, z).reshape(b, r, -1)
    o_p = nx.mm("bhij,bjhpx->bihpx", attn, v_pts).reshape(b, r, h * vp, 3)
    o_p = _invert_apply(rot, trans, o_p)
    o_n = jnp.linalg.norm(o_p + 1e-8, axis=-1, keepdims=True)
    o_pf = jnp.concatenate([o_p, o_n], -1).reshape(b, r, h * vp * 4)
    return dense(p["out"], jnp.concatenate([o_s, o_z, o_pf], -1), nx)


def structure_module(p, single, pair, seq_mask, d: Dims, nx):
    b, r, _ = single.shape
    s = dense(p["proj_s"], layer_norm(p["ln_s"], single), nx)
    zn = layer_norm(p["ln_z"], pair)
    rot = jnp.broadcast_to(jnp.eye(3, dtype=F32), (b, r, 3, 3))
    trans = jnp.zeros((b, r, 3), F32)

    def body(carry, _):
        s, rot, trans = carry
        s = layer_norm(p["ln_ipa"], s + ipa(p["ipa"], s, zn, rot, trans,
                                              seq_mask, d, nx))
        hdn = jax.nn.relu(dense(p["trans1"], s, nx))
        hdn = jax.nn.relu(dense(p["trans2"], hdn, nx))
        s = layer_norm(p["ln_trans"], s + dense(p["trans3"], hdn, nx))
        upd = dense(p["bb_update"], s, nx)
        rot_u = _quat_to_rot(jnp.concatenate(
            [jnp.ones((b, r, 1), F32), upd[..., :3]], -1))
        trans = jnp.einsum("...ij,...j->...i", rot, upd[..., 3:]
                           * d.trans_scale, precision=HIGHEST) + trans
        rot = jnp.einsum("...ij,...jk->...ik", rot, rot_u, precision=HIGHEST)
        return (s, rot, trans), (rot, trans)

    (s, rot, trans), traj = jax.lax.scan(body, (s, rot, trans), None,
                                         length=d.structure_iterations)
    return trans, (rot, trans), traj


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------


def _iteration(params, batch, prev, d: Dims, nx, key, remat):
    oh = lambda x, n: jax.nn.one_hot(x, n, dtype=F32)  # noqa: E731
    aa = oh(batch["aatype"], N_AA)
    msa = (dense(params["msa_embed"], oh(batch["msa"], N_MSA_TOK), nx)
           + dense(params["target_embed_m"], aa, nx)[:, None])
    pair = (dense(params["left_embed"], aa, nx)[:, :, None]
            + dense(params["right_embed"], aa, nx)[:, None])
    ri = batch["residue_index"]
    rel = jnp.clip(ri[:, :, None] - ri[:, None, :], -RELPOS_K,
                   RELPOS_K) + RELPOS_K
    pair = pair + dense(params["relpos_embed"], oh(rel, 2 * RELPOS_K + 1), nx)

    prev_m, prev_z, prev_x = prev
    rp = params["recycle"]
    msa = msa.at[:, 0].add(layer_norm(rp["ln_m"], prev_m))
    pair = pair + layer_norm(rp["ln_z"], prev_z)
    dist = jnp.linalg.norm(prev_x[:, :, None] - prev_x[:, None] + 1e-8,
                           axis=-1)
    edges = jnp.linspace(3.375, 21.375, d.recycle_bins - 1)
    bins = jnp.sum(dist[..., None] > edges, axis=-1)
    pair = pair + dense(rp["dist_embed"], oh(bins, d.recycle_bins), nx)

    msa_mask, seq_mask = batch["msa_mask"], batch["seq_mask"]
    keys = (jax.random.split(key, d.n_blocks) if key is not None
            else jnp.zeros((d.n_blocks, 2), jnp.uint32))

    def body(carry, xs):
        p, k = xs
        return evoformer_block(p, *carry, msa_mask, seq_mask, d, nx,
                               k if key is not None else None), None

    if remat:
        body = jax.checkpoint(body)
    (msa, pair), _ = jax.lax.scan(body, (msa, pair),
                                  (params["evoformer"], keys))
    single = dense(params["single_proj"], msa[:, 0], nx)
    coords, frames, traj = structure_module(params["structure"], single, pair,
                                            seq_mask, d, nx)
    return {"msa_first_row": msa[:, 0], "pair": pair, "coords": coords,
            "frames": frames, "traj": traj,
            "msa_logits": dense(params["msa_head"], msa, nx),
            "distogram_logits": dense(params["dist_head"], pair, nx)}


def forward(params, batch, d: Dims, nx: Numerics = FP32, key=None,
            remat=False):
    """The fold: ``n_recycle`` passes under stop_gradient, then the last
    pass. ``key`` turns residual dropout on (training)."""
    b, _, r = batch["msa"].shape
    prev = (jnp.zeros((b, r, d.d_msa), F32),
            jnp.zeros((b, r, r, d.d_pair), F32), jnp.zeros((b, r, 3), F32))

    def body(_, prev):
        out = _iteration(params, batch, prev, d, nx, key, remat=False)
        return out["msa_first_row"], out["pair"], out["coords"]

    prev = jax.lax.stop_gradient(jax.lax.fori_loop(0, d.n_recycle, body,
                                                   prev))
    return _iteration(params, batch, prev, d, nx, key, remat)


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------


def _frames_from_ca(x):
    x1, x2, x3 = jnp.roll(x, 1, axis=-2), x, jnp.roll(x, -1, axis=-2)
    v1, v2 = x3 - x2, x1 - x2
    e1 = v1 / (jnp.linalg.norm(v1, axis=-1, keepdims=True) + 1e-8)
    u2 = v2 - e1 * jnp.sum(e1 * v2, axis=-1, keepdims=True)
    e2 = u2 / (jnp.linalg.norm(u2, axis=-1, keepdims=True) + 1e-8)
    return jnp.stack([e1, e2, jnp.cross(e1, e2)], axis=-1), x2


def _fape(rot, trans, t_rot, t_trans, pos, t_pos, seq_mask, clamp=10.0,
          scale=10.0):
    def local(rr, tt, xx):
        return jnp.einsum("bixy,bijx->bijy", rr,
                          xx[:, None, :, :] - tt[:, :, None, :],
                          precision=HIGHEST)

    err = jnp.sqrt(jnp.sum(jnp.square(local(rot, trans, pos)
                                      - local(t_rot, t_trans, t_pos)), -1)
                   + 1e-8)
    err = jnp.minimum(err, clamp) / scale
    m2 = seq_mask[:, :, None] * seq_mask[:, None, :]
    return jnp.sum(err * m2) / (jnp.sum(m2) + 1e-6)


def loss(params, batch, d: Dims, nx: Numerics, key):
    """AlphaFold's training loss as the system weighs it: 0.5 FAPE + 0.5
    mean trajectory FAPE + 2 masked-MSA + 0.3 distogram."""
    out = forward(params, batch, d, nx, key, remat=True)
    seq_mask, true_x = batch["seq_mask"], batch["pseudo_beta"]
    t_rot, t_trans = _frames_from_ca(true_x)
    rot, trans = out["frames"]
    l_fape = _fape(rot, trans, t_rot, t_trans, trans, true_x, seq_mask)
    traj_rot, traj_trans = out["traj"]
    l_aux = jnp.mean(jax.vmap(
        lambda rr, tt: _fape(rr, tt, t_rot, t_trans, tt, true_x, seq_mask))(
            traj_rot, traj_trans))
    logp = jax.nn.log_softmax(out["msa_logits"], axis=-1)
    ll = jnp.take_along_axis(logp, batch["true_msa"][..., None], -1)[..., 0]
    l_msa = -jnp.sum(ll * batch["bert_mask"]) / (jnp.sum(batch["bert_mask"])
                                                 + 1e-6)
    dist = jnp.linalg.norm(true_x[:, :, None] - true_x[:, None] + 1e-8,
                           axis=-1)
    target = jnp.sum(dist[..., None] > jnp.linspace(2.3125, 21.6875,
                                                    N_DIST_BINS - 1), -1)
    logp = jax.nn.log_softmax(out["distogram_logits"], axis=-1)
    ll = jnp.take_along_axis(logp, target[..., None], -1)[..., 0]
    m2 = seq_mask[:, :, None] * seq_mask[:, None, :]
    l_dist = -jnp.sum(ll * m2) / (jnp.sum(m2) + 1e-6)
    return 0.5 * l_fape + 0.5 * l_aux + 2.0 * l_msa + 0.3 * l_dist


# ---------------------------------------------------------------------------
# optimizer: Adam with global-norm clipping and the cosine schedule
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Adam:
    lr: float
    warmup_steps: int
    total_steps: int
    clip_norm: float = 1.0
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    final_frac: float = 0.1

    def schedule(self, step):
        s = jnp.asarray(step, F32)
        warm = jnp.minimum(1.0, (s + 1) / max(1, self.warmup_steps))
        prog = jnp.clip((s - self.warmup_steps)
                        / max(1, self.total_steps - self.warmup_steps), 0, 1)
        cos = self.final_frac + (1 - self.final_frac) * 0.5 * (
            1 + jnp.cos(jnp.pi * prog))
        return self.lr * warm * cos

    def step(self, params, grads, m, v, step):
        """One update at 0-based ``step``; returns (params, m, v, clipped
        gradient)."""
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in
                          jax.tree.leaves(grads)))
        grads = jax.tree.map(
            lambda g: g * jnp.minimum(1.0, self.clip_norm / (gn + 1e-6)),
            grads)
        t = jnp.asarray(step + 1, F32)
        bc1, bc2 = 1 - self.b1 ** t, 1 - self.b2 ** t
        lr = self.schedule(step)
        m = jax.tree.map(lambda m_, g: self.b1 * m_ + (1 - self.b1) * g, m,
                         grads)
        v = jax.tree.map(lambda v_, g: self.b2 * v_ + (1 - self.b2) * g * g,
                         v, grads)
        params = jax.tree.map(
            lambda p, m_, v_: p - lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2)
                                                     + self.eps),
            params, m, v)
        return params, m, v, grads
