"""Distribution backends for Dynamic Axial Parallelism (paper §IV.B).

The Evoformer is written once against this interface; three backends give the
three execution modes:

* ``LocalDist``      — single device, all collectives are identity. Oracle.
* ``ShardMapDist``   — *paper-faithful* DAP: runs inside ``shard_map`` over the
  ``model`` mesh axis; ``all_to_all`` swaps the sharded sequence axis exactly
  where Fig. 6 places it, ``all_gather`` materializes cross-axis operands
  (Outer Product Mean, Triangular Updates, pair-bias broadcast).
* ``GspmdDist``      — production path: tensors are global, collectives are
  identity, and ``constrain`` pins the DAP sharding state machine with
  ``with_sharding_constraint`` so GSPMD inserts the *same* collective schedule.
  This is what the multi-pod dry-run lowers and what composes with ZeRO-3 /
  expert parallelism for the assigned architectures.

Sharded-axis convention (shard_map local view): the DAP axis shards exactly one
named dimension of each tensor; helpers below move it.

``sharded_attention`` contract (the kernel-side sharding hook): group
attention ``softmax(scale*qk^T + bias + mask) @ v`` on the 5D Evoformer
layout — q, k, v ``(B, G, S, H, D)`` with the G (group) dim riding the DAP
axis, bias ``(B, H, S, S)`` replicated over G (or None), mask ``(B, G, S)``
additive fp32 (or None). Each backend must run ``ops.fused_attention`` on
*local* ``(B_loc, G_loc, S, H, D)`` blocks so the kernel's internal
``(B·G, S, H, D)`` flatten never merges two mesh-sharded dims:

* ``LocalDist`` / ``ShardMapDist`` — the tensors in hand are already local
  (whole array / shard_map local view): call the kernel directly.
* ``GspmdDist`` — tensors are global: wrap the kernel call in ``shard_map``
  over ``(batch_axes, 'model')`` with the bias replicated, so each device
  runs the fused kernel on its local block and GSPMD never sees a merged
  ``(B·G, ...)`` reshape (which would force an all-gather of the whole
  representation). ``sharded_attention_supported`` reports whether the
  global shape divides the mesh; callers fall back to the (unflattened)
  scores-materialized path otherwise.

``sharded_triangle`` / ``sharded_opm`` contracts (pair-stack counterparts,
PR 3): the fused triangular-multiplicative-update and outer-product-mean
kernels (``ops.fused_triangle_mult`` / ``ops.fused_outer_product_mean``) on
the DAP layouts — triangle: a_lin/ga ``(B, I, K, C)`` and g_lin
``(B, I, J, D)`` with I (the pair-row dim) riding the DAP axis, b_full
``(B, J, K, C)`` the gathered right operand replicated over it; OPM:
a ``(B, S, I, C)`` with I riding the DAP axis, b_full ``(B, S, J, C)``
replicated. Same rules as attention: LocalDist/ShardMapDist hand the ops
already-local blocks; GspmdDist shard_maps the op over
``(batch_axes, 'model')`` so the kernel's tiling and the backward's j-block
scan run on local shards and no merged-sharded-dim reshape reaches GSPMD.
``sharded_triangle_supported`` / ``sharded_opm_supported`` report whether
the sharded extent divides the mesh; the Evoformer falls back to its
materialized jnp path otherwise.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

def unchecked_shard_map(fn, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the varying-manual-axes check: the fused
    kernels' custom VJPs and the DAP collectives are written for local
    shards, which that check cannot see through."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _local_fused_attention(q, k, v, *, bias=None, mask=None, scale=None,
                           kv_tile=0):
    from repro.kernels import ops

    return ops.fused_attention(q, k, v, bias=bias, mask=mask, scale=scale,
                               kv_tile=kv_tile)


def _local_fused_triangle(a_lin, ga, mask, b_full, gamma, beta, w_out, b_out,
                          g_lin, g_bias, *, tile=0):
    from repro.kernels import ops

    return ops.fused_triangle_mult(a_lin, ga, mask, b_full, gamma, beta,
                                   w_out, b_out, g_lin, g_bias, tile=tile)


def _local_fused_opm(a, b_full, mask_a, mask_b, w, bias, *, tile=0):
    from repro.kernels import ops

    return ops.fused_outer_product_mean(a, b_full, mask_a, mask_b, w, bias,
                                        tile=tile)


class LocalDist:
    """Identity backend (1 DAP device)."""

    axis_size: int = 1
    # Tensors handed to this backend are device-local (safe to flatten).
    local_tensors: bool = True

    def all_to_all(self, x, *, split_axis: int, concat_axis: int):
        return x

    def all_gather(self, x, *, axis: int):
        return x

    def psum_scatter(self, x, *, axis: int):
        return x

    def constrain(self, x, dims):
        return x

    def sharded_attention_supported(self, q_shape) -> bool:
        return True

    def sharded_attention(self, q, k, v, *, bias=None, mask=None, scale=None,
                          kv_tile=0):
        return _local_fused_attention(q, k, v, bias=bias, mask=mask,
                                      scale=scale, kv_tile=kv_tile)

    def sharded_triangle_supported(self, i_extent: int) -> bool:
        return True

    def sharded_triangle(self, a_lin, ga, mask, b_full, gamma, beta, w_out,
                         b_out, g_lin, g_bias, *, tile=0):
        return _local_fused_triangle(a_lin, ga, mask, b_full, gamma, beta,
                                     w_out, b_out, g_lin, g_bias, tile=tile)

    def sharded_opm_supported(self, i_extent: int) -> bool:
        return True

    def sharded_opm(self, a, b_full, mask_a, mask_b, w, bias, *, tile=0):
        return _local_fused_opm(a, b_full, mask_a, mask_b, w, bias, tile=tile)


@dataclass(frozen=True)
class ShardMapDist:
    """Explicit-collective DAP; use inside shard_map(..., axis_names=(axis,))."""

    axis: str = "model"
    # Inside shard_map every tensor is a local shard (safe to flatten).
    local_tensors: bool = True

    @property
    def axis_size(self) -> int:
        return jax.lax.axis_size(self.axis)

    def all_to_all(self, x, *, split_axis: int, concat_axis: int):
        # Swap which axis is sharded: locally split `split_axis`, concat shards
        # along `concat_axis`. Volume per device: 1/N^2 of the global tensor
        # (paper Table III).
        return jax.lax.all_to_all(
            x, self.axis, split_axis=split_axis, concat_axis=concat_axis,
            tiled=True,
        )

    def all_gather(self, x, *, axis: int):
        return jax.lax.all_gather(x, self.axis, axis=axis, tiled=True)

    def psum_scatter(self, x, *, axis: int):
        return jax.lax.psum_scatter(x, self.axis, scatter_dimension=axis,
                                    tiled=True)

    def constrain(self, x, dims):
        return x

    def sharded_attention_supported(self, q_shape) -> bool:
        return True

    def sharded_attention(self, q, k, v, *, bias=None, mask=None, scale=None,
                          kv_tile=0):
        # Already inside shard_map: q/k/v/mask are the local (B, G/N, S, ...)
        # shards and bias was all_gathered to the full (B, H, S, S) — the
        # fused kernel runs on the local block as-is.
        return _local_fused_attention(q, k, v, bias=bias, mask=mask,
                                      scale=scale, kv_tile=kv_tile)

    def sharded_triangle_supported(self, i_extent: int) -> bool:
        return True

    def sharded_triangle(self, a_lin, ga, mask, b_full, gamma, beta, w_out,
                         b_out, g_lin, g_bias, *, tile=0):
        # Inside shard_map the I dim is already the local shard and b_full
        # was all_gathered to the full (B, J, K, C) — run the op as-is.
        return _local_fused_triangle(a_lin, ga, mask, b_full, gamma, beta,
                                     w_out, b_out, g_lin, g_bias, tile=tile)

    def sharded_opm_supported(self, i_extent: int) -> bool:
        return True

    def sharded_opm(self, a, b_full, mask_a, mask_b, w, bias, *, tile=0):
        return _local_fused_opm(a, b_full, mask_a, mask_b, w, bias, tile=tile)


@dataclass(frozen=True)
class GspmdDist:
    """GSPMD backend: sharding constraints instead of explicit collectives.

    ``spec`` arguments name which dim rides the DAP (`model`) axis; batch dims
    ride (`pod`, `data`). The mesh is taken from the surrounding jit context
    (jax.sharding.use_mesh / with mesh:).
    """

    mesh: object  # jax.sharding.Mesh
    axis: str = "model"
    # Tensors are GLOBAL views whose dims may be mesh-sharded: flattening
    # (B, G, ...) leading dims merges sharded dims (forced all-gather).
    local_tensors: bool = False

    @property
    def axis_size(self) -> int:
        return self.mesh.shape[self.axis]

    def all_to_all(self, x, *, split_axis: int, concat_axis: int):
        return x

    def all_gather(self, x, *, axis: int):
        return x

    def psum_scatter(self, x, *, axis: int):
        return x

    def constrain(self, x, dims):
        """dims: per-axis entries — 'b' (batch axes), 'm' (DAP/model axis) or
        None. Pins the DAP sharding state machine under GSPMD so XLA inserts
        the same all_to_all/all_gather schedule the shard_map path uses."""
        spec = P(*[
            (batch_spec(self.mesh) if d == "b" else
             ("model" if d == "m" else None))
            for d in dims
        ])
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, spec)
        )

    def _batch_shardable(self, b: int) -> bool:
        bx = batch_spec(self.mesh)
        nb = 1
        for a in bx:
            nb *= self.mesh.shape[a]
        return b % nb == 0

    def sharded_attention_supported(self, q_shape) -> bool:
        """The shard_map wrapper needs the group dim to divide the DAP axis
        (a non-dividing batch dim is handled by replicating batch)."""
        return q_shape[1] % self.mesh.shape[self.axis] == 0

    def sharded_attention(self, q, k, v, *, bias=None, mask=None, scale=None,
                          kv_tile=0):
        """Run the fused kernel under shard_map over (batch_axes, model):
        each device gets its local (B_loc, G_loc, S, H, D) block with the
        gathered bias replicated — the kernel's (B·G) flatten happens on
        local shards only, so GSPMD never inserts a merged-(B, G) all-gather.
        Differentiable (shard_map transposes the kernel's custom_vjp)."""
        bx = batch_spec(self.mesh)
        if not self._batch_shardable(q.shape[0]):
            bx = None  # replicate batch; the DAP axis still shards G
        io = P(bx, self.axis, None, None, None)
        in_specs = [io, io, io]
        args = [q, k, v]
        has_bias, has_mask = bias is not None, mask is not None
        if has_bias:
            in_specs.append(P(bx, None, None, None))
            args.append(bias)
        if has_mask:
            in_specs.append(P(bx, self.axis, None))
            args.append(mask)

        def local_fn(*xs):
            b_ = xs[3] if has_bias else None
            m_ = xs[3 + has_bias] if has_mask else None
            return _local_fused_attention(xs[0], xs[1], xs[2], bias=b_,
                                          mask=m_, scale=scale,
                                          kv_tile=kv_tile)

        return unchecked_shard_map(local_fn, self.mesh, tuple(in_specs), io)(
            *args)

    def sharded_triangle_supported(self, i_extent: int) -> bool:
        """The shard_map wrapper needs the pair-row (I) dim to divide the
        DAP axis (a non-dividing batch dim is handled by replicating it)."""
        return i_extent % self.mesh.shape[self.axis] == 0

    def sharded_triangle(self, a_lin, ga, mask, b_full, gamma, beta, w_out,
                         b_out, g_lin, g_bias, *, tile=0):
        """Run the fused triangle update under shard_map over
        (batch_axes, model): each device gets its local (B_loc, I_loc, K, C)
        left block and gate tile with the gathered b_full replicated — the
        kernel's tiling and the backward's j-block recompute scan see local
        shards only, so GSPMD never inserts a merged-(B, I) all-gather.
        Differentiable (shard_map transposes the op's custom_vjp)."""
        bx = batch_spec(self.mesh)
        if not self._batch_shardable(a_lin.shape[0]):
            bx = None
        row4 = P(bx, self.axis, None, None)
        rep = lambda x: P(*([None] * x.ndim))
        in_specs = (row4, row4, P(bx, self.axis, None),
                    P(bx, None, None, None), rep(gamma), rep(beta),
                    rep(w_out), rep(b_out), row4, rep(g_bias))

        def local_fn(al, g_, mk, bf, gam, bet, w_, bo, gl, gb):
            return _local_fused_triangle(al, g_, mk, bf, gam, bet, w_, bo,
                                         gl, gb, tile=tile)

        return unchecked_shard_map(local_fn, self.mesh, in_specs, row4)(
            a_lin, ga, mask, b_full, gamma, beta, w_out, b_out, g_lin,
            g_bias)

    def sharded_opm_supported(self, i_extent: int) -> bool:
        return i_extent % self.mesh.shape[self.axis] == 0

    def sharded_opm(self, a, b_full, mask_a, mask_b, w, bias, *, tile=0):
        """Run the fused outer-product-mean under shard_map over
        (batch_axes, model): the I dim of the left projection/mask rides the
        DAP axis, the gathered right operand and its mask are replicated,
        and the output lands I-sharded — matching the pair rep."""
        bx = batch_spec(self.mesh)
        if not self._batch_shardable(a.shape[0]):
            bx = None
        rep = lambda x: P(*([None] * x.ndim))
        in_specs = (P(bx, None, self.axis, None), P(bx, None, None, None),
                    P(bx, None, self.axis), P(bx, None, None),
                    rep(w), rep(bias))
        out_spec = P(bx, self.axis, None, None)

        def local_fn(a_, bf, ma, mb, w_, bi):
            return _local_fused_opm(a_, bf, ma, mb, w_, bi, tile=tile)

        return unchecked_shard_map(local_fn, self.mesh, in_specs, out_spec)(
            a, b_full, mask_a, mask_b, w, bias)


def dist_from_policy(policy):
    """Build the dist backend a ``repro.exec.plan.ParallelPolicy`` names —
    the single place the plan's parallel policy turns into one of the three
    backends above (``ParallelPolicy.make_dist`` delegates here). 'gspmd'
    requires ``policy.mesh`` to carry the jax Mesh."""
    if policy.backend == "local":
        return LocalDist()
    if policy.backend == "shard_map":
        return ShardMapDist(axis=policy.axis)
    if policy.backend == "gspmd":
        if policy.mesh is None:
            raise ValueError(
                "ParallelPolicy(backend='gspmd') needs a mesh — e.g. "
                "ParallelPolicy('gspmd', mesh=launch.mesh.make_host_mesh())")
        return GspmdDist(mesh=policy.mesh, axis=policy.axis)
    raise ValueError(f"unknown dist backend {policy.backend!r}")


def batch_spec(mesh) -> tuple:
    """Mesh axes that shard the batch dimension: ('pod','data') or ('data',)."""
    return ("pod", "data") if "pod" in mesh.shape else ("data",)


def dap_msa_spec(mesh, shard_dim: str):
    """PartitionSpec for MSA rep (B, s, r, H): shard_dim in {'s','r'}."""
    b = batch_spec(mesh)
    if shard_dim == "s":
        return P(b, "model", None, None)
    return P(b, None, "model", None)


def dap_pair_spec(mesh, shard_dim: str):
    """PartitionSpec for pair rep (B, i, j, H): shard_dim in {'i','j'}."""
    b = batch_spec(mesh)
    if shard_dim == "i":
        return P(b, "model", None, None)
    return P(b, None, "model", None)
