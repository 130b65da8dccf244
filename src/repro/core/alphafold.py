"""End-to-end AlphaFold-2 model: embedders, recycling, Evoformer trunk (DAP-
parallelizable), structure module, and training heads.

Chunking: ``alphafold_forward`` resolves the Evoformer chunk knobs through the
AutoChunk planner (repro.memory.autochunk) at trace time — the largest
settings whose modeled activation memory fits the per-chip HBM budget, no
chunking when everything fits. Hand-set nonzero knobs and
``evoformer.auto_chunk=False`` opt out.

Execution policy: the ``dist`` backend, the HBM budget, and AutoChunk knob
overrides default to the context-local ExecutionPlan
(``repro.exec.plan.current_plan()``) — ``with use_plan(plan):`` around a
call (or the ``repro.exec.session.FastFold`` facade, which binds the plan
once) steers them without kwarg plumbing. Explicit ``dist=`` /
``hbm_budget=`` arguments still win for composition (the DAP drivers hand
shard_map-local backends directly)."""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp

from repro.exec.plan import current_plan
from repro.core.evoformer import (
    EvoformerConfig,
    evoformer_stack,
    extra_msa_stack,
    init_evoformer_stack,
)
from repro.core.losses import N_DIST_BINS, N_MSA_TOK, alphafold_loss
from repro.core.structure import (
    StructureConfig,
    init_structure_module,
    structure_module,
)
from repro.layers.norms import init_layer_norm, layer_norm
from repro.layers.params import Params, dense, init_dense
from repro.memory.autochunk import resolve_evoformer_config

N_AA = 21
RELPOS_K = 32
# Extra-MSA features (SI Alg. 2 line 13): the MSA one-hot, has_deletion and
# deletion_value.
N_EXTRA_FEAT = N_MSA_TOK + 2


@dataclass(frozen=True)
class AlphaFoldConfig:
    evoformer: EvoformerConfig = field(default_factory=EvoformerConfig)
    structure: StructureConfig = field(default_factory=StructureConfig)
    n_recycle: int = 3          # extra passes (total passes = n_recycle + 1)
    recycle_bins: int = 15
    compute_dtype: Any = jnp.bfloat16
    # The extra-MSA stack (SI Alg. 18) and its embedding: a block config
    # with ``global_column`` set and the trunk's d_pair, run over the
    # batch's ``extra_msa`` rows before the trunk. None: off (no extra
    # parameters, inputs or work).
    extra_msa: EvoformerConfig | None = None

    @property
    def d_msa(self):
        return self.evoformer.d_msa

    @property
    def d_pair(self):
        return self.evoformer.d_pair


def init_alphafold(key, cfg: AlphaFoldConfig) -> Params:
    ks = iter(jax.random.split(key, 16))
    d_m, d_z = cfg.d_msa, cfg.d_pair
    params = {
        "msa_embed": init_dense(next(ks), N_MSA_TOK, d_m, bias=True),
        "target_embed_m": init_dense(next(ks), N_AA, d_m, bias=True),
        "left_embed": init_dense(next(ks), N_AA, d_z, bias=True),
        "right_embed": init_dense(next(ks), N_AA, d_z, bias=True),
        "relpos_embed": init_dense(next(ks), 2 * RELPOS_K + 1, d_z, bias=True),
        "recycle": {
            "ln_m": init_layer_norm(d_m),
            "ln_z": init_layer_norm(d_z),
            "dist_embed": init_dense(next(ks), cfg.recycle_bins, d_z, bias=True),
        },
        "evoformer": init_evoformer_stack(next(ks), cfg.evoformer),
        "single_proj": init_dense(next(ks), d_m, cfg.structure.c_s, bias=True),
        "structure": init_structure_module(next(ks), cfg.structure),
        "msa_head": init_dense(next(ks), d_m, N_MSA_TOK, bias=True),
        "dist_head": init_dense(next(ks), d_z, N_DIST_BINS, bias=True),
    }
    if cfg.extra_msa is not None:
        params["extra_msa_embed"] = init_dense(
            next(ks), N_EXTRA_FEAT, cfg.extra_msa.d_msa, bias=True)
        params["extra_msa_stack"] = init_evoformer_stack(next(ks),
                                                         cfg.extra_msa)
    return params


def embed_inputs(params, batch, cfg: AlphaFoldConfig):
    """batch: dict with msa (B,s,r) int, aatype (B,r) int, residue_index (B,r)."""
    dt = cfg.compute_dtype
    msa_oh = jax.nn.one_hot(batch["msa"], N_MSA_TOK, dtype=dt)
    aa_oh = jax.nn.one_hot(batch["aatype"], N_AA, dtype=dt)
    msa_rep = dense(params["msa_embed"], msa_oh)
    msa_rep = msa_rep + dense(params["target_embed_m"], aa_oh)[:, None]
    left = dense(params["left_embed"], aa_oh)
    right = dense(params["right_embed"], aa_oh)
    pair = left[:, :, None, :] + right[:, None, :, :]
    rel = jnp.clip(
        batch["residue_index"][:, :, None] - batch["residue_index"][:, None, :],
        -RELPOS_K, RELPOS_K,
    ) + RELPOS_K
    pair = pair + dense(params["relpos_embed"],
                        jax.nn.one_hot(rel, 2 * RELPOS_K + 1, dtype=dt))
    return msa_rep, pair


def embed_extra_msa(params, batch, cfg: AlphaFoldConfig):
    """batch: extra_msa (B, s_e, r) int, extra_has_deletion and
    extra_deletion_value (B, s_e, r) float -> (B, s_e, r, c_e)."""
    dt = cfg.compute_dtype
    feat = jnp.concatenate([
        jax.nn.one_hot(batch["extra_msa"], N_MSA_TOK, dtype=dt),
        batch["extra_has_deletion"][..., None].astype(dt),
        batch["extra_deletion_value"][..., None].astype(dt),
    ], axis=-1)
    return dense(params["extra_msa_embed"], feat)


def embed_recycle(params, msa, pair, prev, cfg: AlphaFoldConfig):
    """Add recycled features (Jumper et al. §1.10): LN'ed previous reps and a
    binned distance embedding of the previous predicted CB/CA positions."""
    prev_msa_row, prev_pair, prev_pos = prev
    msa = msa.at[:, 0].add(
        layer_norm(params["recycle"]["ln_m"], prev_msa_row).astype(msa.dtype)
    )
    pair = pair + layer_norm(params["recycle"]["ln_z"], prev_pair).astype(pair.dtype)
    d = jnp.linalg.norm(
        prev_pos[:, :, None] - prev_pos[:, None] + 1e-8, axis=-1
    )
    edges = jnp.linspace(3.375, 21.375, cfg.recycle_bins - 1)
    bins = jnp.sum(d[..., None] > edges, axis=-1)
    pair = pair + dense(
        params["recycle"]["dist_embed"],
        jax.nn.one_hot(bins, cfg.recycle_bins, dtype=pair.dtype),
    )
    return msa, pair


def alphafold_iteration(params, batch, prev, cfg: AlphaFoldConfig, *,
                        dist=None, rng=None, train=False):
    """One recycling iteration: embed -> Evoformer -> structure + heads.

    Under DAP the caller passes already-sharded batch tensors and a dist
    backend; embedding/heads/structure are element-wise or replicated-safe.
    ``dist=None`` resolves the current plan's ParallelPolicy.
    """
    if dist is None:
        dist = current_plan().parallel.make_dist()
    dt = cfg.compute_dtype
    with jax.named_scope("alphafold.embed"):
        msa, pair = embed_inputs(params, batch, cfg)
    with jax.named_scope("alphafold.recycle"):
        msa, pair = embed_recycle(params, msa, pair, prev, cfg)
        msa = msa.astype(dt)
        pair = pair.astype(dt)

    seq_mask = batch["seq_mask"]
    pair_mask = seq_mask[:, :, None] * seq_mask[:, None, :]
    if cfg.extra_msa is not None:
        # SI Alg. 2 lines 13-14: the extra MSA updates the pair, then goes.
        with jax.named_scope("alphafold.extra_msa_embed"):
            extra = embed_extra_msa(params, batch, cfg).astype(dt)
        with jax.named_scope("alphafold.extra_msa_stack"):
            pair = extra_msa_stack(
                params["extra_msa_stack"], extra, pair,
                batch["extra_msa_mask"], seq_mask, pair_mask, dist=dist,
                cfg=cfg.extra_msa, train=train,
                rng=None if rng is None else jax.random.fold_in(rng, 1))
    msa, pair = evoformer_stack(
        params["evoformer"], msa, pair, batch["msa_mask"], seq_mask, pair_mask,
        dist=dist, cfg=cfg.evoformer, rng=rng, train=train,
    )

    with jax.named_scope("alphafold.heads"):
        single = dense(params["single_proj"], msa[:, 0].astype(jnp.float32))
        msa_logits = dense(params["msa_head"], msa.astype(jnp.float32))
        distogram_logits = dense(params["dist_head"],
                                 pair.astype(jnp.float32))
    coords, frames, traj = structure_module(
        params["structure"], single, pair.astype(jnp.float32), seq_mask,
        cfg.structure,
    )
    return {
        "msa": msa,
        "pair": pair,
        "coords": coords,
        "frames": frames,
        "traj": traj,
        "msa_logits": msa_logits,
        "distogram_logits": distogram_logits,
    }


def alphafold_forward(params, batch, cfg: AlphaFoldConfig, *,
                      n_recycle: int | jax.Array | None = None,
                      dist=None, rng=None, train=False,
                      hbm_budget: int | None = None):
    """Full forward with recycling. Pre-final iterations run under
    stop_gradient (AlphaFold training recipe); the number of recycles can be a
    traced scalar (sampled per-batch during training, fixed 3 at inference).

    ``hbm_budget`` overrides the per-chip HBM budget the AutoChunk planner
    resolves chunk knobs against (default: the current plan's
    MemoryPolicy.hbm_budget, else launch.mesh.HBM_BYTES). ``dist=None``
    resolves the current plan's ParallelPolicy; the plan's MemoryPolicy knob
    overrides are applied to the Evoformer config before planning."""
    plan = current_plan()
    if dist is None:
        dist = plan.parallel.make_dist()
    b, s, r = batch["msa"].shape

    def resolve(evo_cfg, n_seq):
        # AutoChunk (trace-time, static shapes): fill chunk knobs left at 0
        # from the HBM budget instead of hand-set constants. budget_bytes=
        # None lets the planner resolve the plan's MemoryPolicy budget
        # itself (one path).
        return resolve_evoformer_config(
            plan.memory.apply(evo_cfg), batch=b, n_seq=n_seq, n_res=r,
            dap=getattr(dist, "axis_size", 1), budget_bytes=hbm_budget)

    evo_cfg = resolve(cfg.evoformer, s)
    if evo_cfg is not cfg.evoformer:
        cfg = dataclasses.replace(cfg, evoformer=evo_cfg)
    if cfg.extra_msa is not None:
        # The extra stack's shapes differ (5120 rows at width 64 against
        # the trunk's 512 at 256), so its knobs are planned on their own.
        cfg = dataclasses.replace(cfg, extra_msa=resolve(
            cfg.extra_msa, batch["extra_msa"].shape[1]))
    d_m, d_z = cfg.d_msa, cfg.d_pair
    if n_recycle is None:
        n_recycle = cfg.n_recycle
    prev = (
        jnp.zeros((b, r, d_m), jnp.float32),
        jnp.zeros((b, r, r, d_z), jnp.float32),
        jnp.zeros((b, r, 3), jnp.float32),
    )

    def body(i, prev):
        out = alphafold_iteration(params, batch, prev, cfg, dist=dist,
                                  rng=rng, train=train)
        return (out["msa"][:, 0].astype(jnp.float32),
                out["pair"].astype(jnp.float32), out["coords"])

    prev = jax.lax.stop_gradient(
        jax.lax.fori_loop(0, n_recycle, body, prev)
    )
    return alphafold_iteration(params, batch, prev, cfg, dist=dist, rng=rng,
                               train=train)


def alphafold_train_loss(params, batch, cfg: AlphaFoldConfig, rng=None,
                         n_recycle=None, dist=None):
    out = alphafold_forward(params, batch, cfg, n_recycle=n_recycle, dist=dist,
                            rng=rng, train=True)
    return alphafold_loss(out, batch)
