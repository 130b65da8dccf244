"""Dynamic Axial Parallelism drivers (paper §IV.B).

``dap_shard_map(fn, mesh)`` wraps an Evoformer computation written against the
Dist interface so it runs with *explicit* collectives over the ``model`` mesh
axis — the paper-faithful path. Inputs/outputs use the DAP sharding
convention:

  msa      (B, s, r, Hm) sharded P(batch_axes, 'model', None, None)
  pair     (B, i, j, Hz) sharded P(batch_axes, 'model', None, None)
  msa_mask like msa; pair_mask_loc like pair; seq_mask replicated over model.
  params   replicated over 'model' (DAP's defining property: full parameters
           per device, sharded activations).

Inside the shard_map body every tensor is a local shard, so the Evoformer's
four attention sites run the fused flash-attention kernel directly on their
local (B, G/N, S, H, D) blocks (ShardMapDist.sharded_attention) — the
paper-faithful DAP path composes with the §IV.A kernels with no resharding.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.dist import ShardMapDist, batch_spec, unchecked_shard_map
from repro.core import evoformer as evo


def dap_specs(mesh):
    b = batch_spec(mesh)
    seq = P(b, "model", None, None)
    mask3 = P(b, "model", None)
    return {
        "msa": seq,
        "pair": seq,
        "msa_mask": mask3,
        "seq_mask": P(b, None),
        "pair_mask": mask3,
    }


def shard_dap_inputs(mesh, msa, pair, msa_mask, seq_mask, pair_mask):
    """Place global arrays with the DAP sharding (host -> devices)."""
    s = dap_specs(mesh)
    put = lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec))
    return (
        put(msa, s["msa"]),
        put(pair, s["pair"]),
        put(msa_mask, s["msa_mask"]),
        put(seq_mask, s["seq_mask"]),
        put(pair_mask, s["pair_mask"]),
    )


def dap_evoformer_stack(mesh, cfg: evo.EvoformerConfig, *, train: bool = False,
                        remat: bool = True):
    """Returns a jit-able fn(params, msa, pair, msa_mask, seq_mask, pair_mask,
    rng?) running the full Evoformer stack under paper-faithful DAP."""
    s = dap_specs(mesh)
    dist = ShardMapDist(axis="model")

    def local_fn(params, msa, pair, msa_mask, seq_mask, pair_mask):
        return evo.evoformer_stack(
            params, msa, pair, msa_mask, seq_mask, pair_mask,
            dist=dist, cfg=cfg, rng=None, train=train, remat=remat,
        )

    return unchecked_shard_map(
        local_fn,
        mesh,
        (P(), s["msa"], s["pair"], s["msa_mask"], s["seq_mask"],
         s["pair_mask"]),
        (s["msa"], s["pair"]),
    )
