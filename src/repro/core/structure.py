"""Structure module: Invariant Point Attention + backbone frame updates.

Faithful-but-reduced AlphaFold structure module: 8 shared-weight iterations of
IPA (scalar + point + pair attention terms), residue-frame composition via
quaternion updates, and per-iteration backbone outputs for the auxiliary FAPE
loss. The paper (FastFold) optimizes the Evoformer and leaves this module
untouched; it is <10% of step time, replicated under DAP.

Frames are (rotation (..., 3, 3), translation (..., 3)) acting as x -> Rx + t.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.layers.norms import init_layer_norm, layer_norm
from repro.layers.params import Params, dense, init_dense


@dataclass(frozen=True)
class StructureConfig:
    c_s: int = 384          # single representation
    c_z: int = 128          # pair representation
    n_heads: int = 12
    c_hidden: int = 16      # scalar head dim
    n_qk_points: int = 4
    n_v_points: int = 8
    n_iterations: int = 8
    trans_scale: float = 10.0  # nm-scale translations (AlphaFold convention)


# --- rigid-frame utilities --------------------------------------------------

def identity_frames(shape) -> tuple[jax.Array, jax.Array]:
    rot = jnp.broadcast_to(jnp.eye(3, dtype=jnp.float32), shape + (3, 3))
    trans = jnp.zeros(shape + (3,), jnp.float32)
    return rot, trans


def frames_apply(rot, trans, x):
    """x: (..., P, 3) points in local coords -> global."""
    return jnp.einsum("...ij,...pj->...pi", rot, x) + trans[..., None, :]


def frames_invert_apply(rot, trans, x):
    return jnp.einsum("...ji,...pj->...pi", rot, x - trans[..., None, :])


def quat_to_rot(q):
    """Unnormalized quaternion (..., 4) -> rotation matrix (..., 3, 3)."""
    q = q / (jnp.linalg.norm(q, axis=-1, keepdims=True) + 1e-8)
    w, x, y, z = q[..., 0], q[..., 1], q[..., 2], q[..., 3]
    return jnp.stack(
        [
            jnp.stack([1 - 2 * (y**2 + z**2), 2 * (x * y - w * z), 2 * (x * z + w * y)], -1),
            jnp.stack([2 * (x * y + w * z), 1 - 2 * (x**2 + z**2), 2 * (y * z - w * x)], -1),
            jnp.stack([2 * (x * z - w * y), 2 * (y * z + w * x), 1 - 2 * (x**2 + y**2)], -1),
        ],
        axis=-2,
    )


def compose_frames(rot1, trans1, rot2, trans2):
    """(R1,t1) ∘ (R2,t2): first apply 2, then 1."""
    rot = jnp.einsum("...ij,...jk->...ik", rot1, rot2)
    trans = jnp.einsum("...ij,...j->...i", rot1, trans2) + trans1
    return rot, trans


def frames_from_3_points(x1, x2, x3):
    """Gram-Schmidt frames from 3 points (AlphaFold Alg. 21): origin x2,
    x3-x2 defines e1. Used to build ground-truth frames from CA traces."""
    v1 = x3 - x2
    v2 = x1 - x2
    e1 = v1 / (jnp.linalg.norm(v1, axis=-1, keepdims=True) + 1e-8)
    u2 = v2 - e1 * jnp.sum(e1 * v2, axis=-1, keepdims=True)
    e2 = u2 / (jnp.linalg.norm(u2, axis=-1, keepdims=True) + 1e-8)
    e3 = jnp.cross(e1, e2)
    rot = jnp.stack([e1, e2, e3], axis=-1)  # columns are the basis
    return rot, x2


# --- IPA --------------------------------------------------------------------

def init_ipa(key, cfg: StructureConfig) -> Params:
    ks = iter(jax.random.split(key, 10))
    h, c = cfg.n_heads, cfg.c_hidden
    qp, vp = cfg.n_qk_points, cfg.n_v_points
    concat_dim = h * c + h * cfg.c_z + h * vp * 4  # scalar + pair + points(3)+norm
    return {
        "q": init_dense(next(ks), cfg.c_s, h * c, bias=False),
        "kv": init_dense(next(ks), cfg.c_s, 2 * h * c, bias=False),
        "q_pts": init_dense(next(ks), cfg.c_s, h * qp * 3, bias=False),
        "kv_pts": init_dense(next(ks), cfg.c_s, h * (qp + vp) * 3, bias=False),
        "bias_z": init_dense(next(ks), cfg.c_z, h, bias=False),
        "head_w": jnp.zeros((h,), jnp.float32),  # softplus(head_w) point weights
        "out": init_dense(next(ks), concat_dim, cfg.c_s, bias=True, zero_init=True),
    }


def ipa(p: Params, s: jax.Array, z: jax.Array, rot, trans, seq_mask,
        cfg: StructureConfig) -> jax.Array:
    """s: (B, r, c_s); z: (B, r, r, c_z); frames (B, r, 3, 3)/(B, r, 3)."""
    b, r, _ = s.shape
    h, c = cfg.n_heads, cfg.c_hidden
    qp, vp = cfg.n_qk_points, cfg.n_v_points

    q = dense(p["q"], s).reshape(b, r, h, c)
    k, v = jnp.split(dense(p["kv"], s).reshape(b, r, h, 2 * c), 2, axis=-1)
    q_pts = dense(p["q_pts"], s).reshape(b, r, h * qp, 3)
    kv_pts = dense(p["kv_pts"], s).reshape(b, r, h * (qp + vp), 3)
    # local -> global points
    q_pts = frames_apply(rot, trans, q_pts).reshape(b, r, h, qp, 3)
    kv_pts = frames_apply(rot, trans, kv_pts)
    k_pts, v_pts = jnp.split(kv_pts.reshape(b, r, h, qp + vp, 3), [qp], axis=-2)

    # scalar term
    logits = jnp.einsum("bihc,bjhc->bhij", q, k) * (1.0 / jnp.sqrt(3 * c))
    # pair bias term
    logits = logits + jnp.einsum("bijh->bhij", dense(p["bias_z"], z)) * (1.0 / jnp.sqrt(3.0))
    # point distance term
    d2 = jnp.sum(
        jnp.square(q_pts[:, :, None] - k_pts[:, None]), axis=-1
    )  # (b, i, j, h, qp)
    gamma = jax.nn.softplus(p["head_w"])  # (h,)
    w_pt = gamma * (1.0 / jnp.sqrt(3.0)) * (9.0 / (2 * qp)) ** 0.5 * 0.5
    logits = logits - jnp.einsum("bijhp,h->bhij", d2, w_pt)
    logits = jnp.where(seq_mask[:, None, None, :] > 0, logits, -1e9)
    attn = jax.nn.softmax(logits, axis=-1)  # (b, h, i, j)

    o_scalar = jnp.einsum("bhij,bjhc->bihc", attn, v).reshape(b, r, h * c)
    o_pair = jnp.einsum("bhij,bijc->bihc", attn, z).reshape(b, r, h * cfg.c_z)
    o_pts = jnp.einsum("bhij,bjhpx->bihpx", attn, v_pts)  # global coords
    o_pts_local = frames_invert_apply(rot, trans, o_pts.reshape(b, r, h * vp, 3))
    o_pts_norm = jnp.linalg.norm(o_pts_local + 1e-8, axis=-1, keepdims=True)
    o_pts_feat = jnp.concatenate([o_pts_local, o_pts_norm], axis=-1).reshape(b, r, h * vp * 4)

    o = jnp.concatenate([o_scalar, o_pair, o_pts_feat], axis=-1)
    return dense(p["out"], o)


# --- structure module -------------------------------------------------------

def init_structure_module(key, cfg: StructureConfig) -> Params:
    ks = iter(jax.random.split(key, 8))
    return {
        "ln_s": init_layer_norm(cfg.c_s),
        "ln_z": init_layer_norm(cfg.c_z),
        "proj_s": init_dense(next(ks), cfg.c_s, cfg.c_s, bias=False),
        "ipa": init_ipa(next(ks), cfg),
        "ln_ipa": init_layer_norm(cfg.c_s),
        "trans1": init_dense(next(ks), cfg.c_s, cfg.c_s, bias=True),
        "trans2": init_dense(next(ks), cfg.c_s, cfg.c_s, bias=True),
        "trans3": init_dense(next(ks), cfg.c_s, cfg.c_s, bias=True, zero_init=True),
        "ln_trans": init_layer_norm(cfg.c_s),
        "bb_update": init_dense(next(ks), cfg.c_s, 6, bias=True, zero_init=True),
    }


def structure_module(p: Params, s_init: jax.Array, z: jax.Array,
                     seq_mask: jax.Array, cfg: StructureConfig):
    """Returns (final_coords (B, r, 3), traj rot/trans per iteration)."""
    with jax.named_scope("structure.module"):
        b, r, _ = s_init.shape
        s = dense(p["proj_s"], layer_norm(p["ln_s"], s_init))
        z_n = layer_norm(p["ln_z"], z)
        rot, trans = identity_frames((b, r))

        def body(carry, _):
            s, rot, trans = carry
            s = s + ipa(p["ipa"], s, z_n, rot, trans, seq_mask, cfg)
            s = layer_norm(p["ln_ipa"], s)
            h = jax.nn.relu(dense(p["trans1"], s))
            h = jax.nn.relu(dense(p["trans2"], h))
            s = layer_norm(p["ln_trans"], s + dense(p["trans3"], h))
            upd = dense(p["bb_update"], s)  # (b, r, 6)
            quat = jnp.concatenate(
                [jnp.ones((b, r, 1), upd.dtype), upd[..., :3]], axis=-1
            )
            rot_u = quat_to_rot(quat)
            trans_u = upd[..., 3:] * cfg.trans_scale
            # Frames updated by right-composition with the local update;
            # gradients flow through rotations (no stop-grad: reduced variant
            # trains fine).
            rot, trans = compose_frames(rot, trans, rot_u, trans_u)
            return (s, rot, trans), (rot, trans)

        (s, rot, trans), traj = jax.lax.scan(
            body, (s, rot, trans), None, length=cfg.n_iterations
        )
    return trans, (rot, trans), traj  # CA coords = frame origins
