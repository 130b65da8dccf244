"""Faults planted under the timed path, to show that the check catches
them. A train fault wraps the program's step ``(state, batch, key) ->
(state, metrics)``; a fold fault wraps the function that compiles the fold,
``(params, batch) -> fold``, where ``fold(params, batch, rng) -> outputs``.
"""
from __future__ import annotations

import contextlib


def unchanged(step):
    """A train step that returns its state unchanged."""
    return lambda state, batch, key: (state, step(state, batch, key)[1])


def half_batch(step):
    """Half of the batch left out, the mean taken over the rest: a step
    holds one crop, so the second half of its residues is masked out."""
    import jax.numpy as jnp

    def broken(state, batch, key):
        r = batch["seq_mask"].shape[1]
        keep = (jnp.arange(r) < r // 2).astype(jnp.float32)
        b = dict(batch, seq_mask=batch["seq_mask"] * keep,
                 msa_mask=batch["msa_mask"] * keep,
                 bert_mask=batch["bert_mask"] * keep)
        return step(state, b, key)
    return broken


def altered_loss(step):
    """The step's answer, its loss, altered by 1% where it is produced."""
    def broken(state, batch, key):
        state, metrics = step(state, batch, key)
        return state, dict(metrics, loss=metrics["loss"] * 1.01)
    return broken


def altered_coords(compile_fold):
    """The fold's answer altered where it is produced: the structure
    scaled by 1.25."""
    def compile_broken(params, batch):
        fold = compile_fold(params, batch)

        def broken(p, b, rng):
            out = fold(p, b, rng)
            return dict(out, coords=out["coords"] * 1.25)
        return broken
    return compile_broken


@contextlib.contextmanager
def _no_triangle_gather():
    """Under GSPMD DAP, the fused triangle update sees only its own device's
    rows of the right operand, repeated, instead of the gathered rows."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core import dist

    orig = dist.GspmdDist.sharded_triangle

    def sharded_triangle(self, a_lin, ga, mask, b_full, gamma, beta, w_out,
                         b_out, g_lin, g_bias, *, tile=0):
        n = self.mesh.shape[self.axis]
        row4 = P(None, self.axis, None, None)
        rep = lambda x: P(*([None] * x.ndim))  # noqa: E731

        def local_fn(al, g_, mk, bf, gam, bet, w_, bo, gl, gb):
            return dist._local_fused_triangle(
                al, g_, mk, jnp.tile(bf, (1, n, 1, 1)), gam, bet, w_, bo, gl,
                gb, tile=tile)

        in_specs = (row4, row4, P(None, self.axis, None), row4, rep(gamma),
                    rep(beta), rep(w_out), rep(b_out), row4, rep(g_bias))
        return dist.unchecked_shard_map(local_fn, self.mesh, in_specs, row4)(
            a_lin, ga, mask, b_full, gamma, beta, w_out, b_out, g_lin,
            g_bias)

    dist.GspmdDist.sharded_triangle = sharded_triangle
    try:
        yield
    finally:
        dist.GspmdDist.sharded_triangle = orig


def exchange_left_out(compile_fold):
    """The exchange between chips left out: the fold compiled with the
    triangle update's all-gather of its right operand skipped."""
    def compile_broken(params, batch):
        with _no_triangle_gather():
            return compile_fold(params, batch)
    return compile_broken


TRAIN = {"unchanged": unchanged, "half_batch": half_batch,
         "altered_loss": altered_loss}
FOLD = {"altered_coords": altered_coords,
        "exchange_left_out": exchange_left_out}
