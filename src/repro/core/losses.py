"""AlphaFold training losses: masked-MSA, distogram, FAPE (+aux traj FAPE)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.structure import frames_from_3_points, frames_invert_apply

N_MSA_TOK = 23
N_DIST_BINS = 64


def masked_msa_loss(logits, true_msa, bert_mask):
    """logits (B, s, r, 23); true_msa int (B, s, r); bert_mask (B, s, r)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, true_msa[..., None], axis=-1)[..., 0]
    denom = jnp.sum(bert_mask) + 1e-6
    return -jnp.sum(ll * bert_mask) / denom


def distogram_loss(logits, pseudo_beta, seq_mask, min_d=2.3125, max_d=21.6875):
    """logits (B, r, r, 64); pseudo_beta (B, r, 3)."""
    d = jnp.linalg.norm(
        pseudo_beta[:, :, None] - pseudo_beta[:, None] + 1e-8, axis=-1
    )
    edges = jnp.linspace(min_d, max_d, N_DIST_BINS - 1)
    target = jnp.sum(d[..., None] > edges, axis=-1)  # (B, r, r) in [0, 63]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, target[..., None], axis=-1)[..., 0]
    mask2 = seq_mask[:, :, None] * seq_mask[:, None, :]
    return -jnp.sum(ll * mask2) / (jnp.sum(mask2) + 1e-6)


def true_frames_from_ca(coords):
    """Ground-truth frames from a CA trace via Gram-Schmidt on neighbours."""
    prev_ca = jnp.roll(coords, 1, axis=-2)
    next_ca = jnp.roll(coords, -1, axis=-2)
    return frames_from_3_points(prev_ca, coords, next_ca)


def fape(pred_rot, pred_trans, true_rot, true_trans, pred_pos, true_pos,
         seq_mask, clamp=10.0, scale=10.0):
    """Frame-Aligned Point Error (AlphaFold Alg. 28), CA-only variant.

    pred/true frames: (B, r, 3, 3), (B, r, 3); positions: (B, r, 3).
    """
    # Local coords of every position j in every frame i: (B, i, j, 3)
    p_local = _pairwise_local(pred_rot, pred_trans, pred_pos)
    t_local = _pairwise_local(true_rot, true_trans, true_pos)
    err = jnp.sqrt(jnp.sum(jnp.square(p_local - t_local), axis=-1) + 1e-8)
    err = jnp.minimum(err, clamp) / scale
    mask2 = seq_mask[:, :, None] * seq_mask[:, None, :]
    return jnp.sum(err * mask2) / (jnp.sum(mask2) + 1e-6)


def _pairwise_local(rot, trans, pos):
    """x_ij = R_i^{-1} (pos_j - t_i): (B, i, j, 3)."""
    rel = pos[:, None, :, :] - trans[:, :, None, :]
    return jnp.einsum("bixy,bijx->bijy", rot, rel)


def alphafold_loss(outputs, batch, *, w_fape=0.5, w_msa=2.0, w_dist=0.3,
                   w_aux=0.5):
    """outputs: dict from the model; batch: ProteinBatch-style dict."""
    with jax.named_scope("alphafold.loss"):
        seq_mask = batch["seq_mask"]
        true_rot, true_trans = true_frames_from_ca(batch["pseudo_beta"])
        rot, trans = outputs["frames"]
        l_fape = fape(rot, trans, true_rot, true_trans, trans,
                      batch["pseudo_beta"], seq_mask)
        # Aux: mean FAPE over the structure-module trajectory.
        traj_rot, traj_trans = outputs["traj"]

        def traj_fape(rt):
            r, t = rt
            return fape(r, t, true_rot, true_trans, t, batch["pseudo_beta"],
                        seq_mask)

        l_aux = jnp.mean(jax.vmap(traj_fape)((traj_rot, traj_trans)))
        l_msa = masked_msa_loss(outputs["msa_logits"], batch["true_msa"],
                                batch["bert_mask"])
        l_dist = distogram_loss(outputs["distogram_logits"],
                                batch["pseudo_beta"], seq_mask)
        total = (w_fape * l_fape + w_aux * l_aux + w_msa * l_msa
                 + w_dist * l_dist)
    return total, {
        "loss": total, "fape": l_fape, "aux_fape": l_aux,
        "masked_msa": l_msa, "distogram": l_dist,
    }
