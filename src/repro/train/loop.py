"""Training step factory: loss -> grads (bf16 compute, fp32 reduce) ->
global-norm clip -> LR schedule -> optimizer -> new state. Supports gradient
accumulation (the paper's micro-batching for DP scaling) and composes with
pjit shardings supplied by parallel/plan.py.

Robustness: ``guard_nonfinite`` (default on) skips the parameter/optimizer
update whenever the global grad norm is non-finite (one bad batch or a
transient numeric fault must not poison the whole run — at ParaFold scale a
single NaN step otherwise costs the job). The guard is a where-select on
the already-computed update, so healthy steps are *bit-identical* with the
guard on or off (trace-time overhead only); skipped steps still advance
``state.step`` (the LR schedule keeps its wall-clock meaning) and report
``metrics['nonfinite_skips'] = 1.0`` so callers can count them.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from repro.optim import clip_by_global_norm, make_optimizer
from repro.optim.schedules import cosine_schedule
from repro.train.state import TrainState, make_train_state


def make_train_step(
    loss_fn: Callable,                    # (params, batch, rng) -> (loss, metrics)
    *,
    optimizer: str = "adamw",
    base_lr: float = 1e-3,
    warmup_steps: int = 100,
    total_steps: int = 10_000,
    weight_decay: float = 0.0,
    clip_norm: float = 1.0,
    accum_steps: int = 1,
    state_dtype=jnp.float32,
    guard_nonfinite: bool = True,
):
    opt_init_raw, opt_update = make_optimizer(optimizer)
    opt_init = partial(opt_init_raw, state_dtype=state_dtype)

    def init_state(params) -> TrainState:
        return make_train_state(params, opt_init)

    def compute_grads(params, batch, rng):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch, rng)
        return loss, metrics, grads

    def train_step(state: TrainState, batch, rng=None):
        """One optimizer step: ``(state, batch, rng) -> (state, metrics)``.

        Stable metrics-key contract — every key below is present on EVERY
        step (never conditionally), so downstream aggregation (obs
        ``train_step`` events, CSV logs) sees a fixed schema:

            loss             scalar training loss (micro-batch mean under
                             gradient accumulation)
            grad_norm        pre-clip global L2 norm of the gradients
            lr               this step's scheduled learning rate
            nonfinite_skips  1.0 when the non-finite guard discarded the
                             update, else 0.0 (always 0.0 with
                             ``guard_nonfinite=False``)

        ``loss_fn`` aux metrics ride along unchanged; new always-present
        keys may be added, but existing keys are never renamed, removed,
        or made conditional.
        """
        if accum_steps == 1:
            loss, metrics, grads = compute_grads(state.params, batch, rng)
        else:
            # micro-batching: batch leading dim must divide accum_steps
            def micro(i, carry):
                acc, loss_acc = carry
                mb = jax.tree.map(
                    lambda x: jax.lax.dynamic_slice_in_dim(
                        x, i * (x.shape[0] // accum_steps),
                        x.shape[0] // accum_steps, axis=0), batch)
                r = jax.random.fold_in(rng, i) if rng is not None else None
                loss, metrics, grads = compute_grads(state.params, mb, r)
                acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32),
                                   acc, grads)
                return acc, loss_acc + loss

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            grads, loss_sum = jax.lax.fori_loop(
                0, accum_steps, micro, (zeros, jnp.zeros((), jnp.float32)))
            grads = jax.tree.map(lambda g: g / accum_steps, grads)
            loss = loss_sum / accum_steps
            metrics = {"loss": loss}

        # Clip, schedule and update run under one scope, so the device trace
        # attributes the optimizer's time.
        with jax.named_scope("train.optimizer"):
            grads, gnorm = clip_by_global_norm(grads, clip_norm)
            metrics = dict(metrics)
            # Contract: "loss" is always present, whether or not the
            # loss_fn's aux dict reports one of its own (an aux "loss" wins —
            # it may be the unscaled/per-token variant the caller prefers to
            # log).
            metrics.setdefault("loss", loss)
            if guard_nonfinite:
                # One non-finite leaf makes gnorm (the global L2) non-finite,
                # so this single scalar guards the whole grad tree. Feed
                # zeros to the optimizer so NaNs never propagate, then
                # discard the update via where-select — when healthy,
                # where(True, x, .) is x, bit for bit.
                ok = jnp.isfinite(gnorm)
                grads = jax.tree.map(
                    lambda g: jnp.where(ok, g, jnp.zeros_like(g)), grads)
                metrics["nonfinite_skips"] = (~ok).astype(jnp.float32)
            else:
                # Guard off: the key is still reported (constant 0.0) so the
                # metrics schema is never ragged across configurations.
                metrics["nonfinite_skips"] = jnp.zeros((), jnp.float32)
            lr = cosine_schedule(state.step, base_lr, warmup_steps,
                                 total_steps)
            new_params, new_opt = opt_update(
                state.params, grads, state.opt_state, lr,
                weight_decay=weight_decay)
            if guard_nonfinite:
                new_params = jax.tree.map(
                    lambda n, o: jnp.where(ok, n, o), new_params,
                    state.params)
                new_opt = jax.tree.map(
                    lambda n, o: jnp.where(ok, n, o), new_opt,
                    state.opt_state)
        metrics.update({"grad_norm": gnorm, "lr": lr})
        return TrainState(state.step + 1, new_params, new_opt), metrics

    return init_state, train_step


def instrument_train_step(step_fn, *, tokens_per_step: float | None = None,
                          metric_keys=("loss", "grad_norm",
                                       "nonfinite_skips")):
    """Wrap an (optionally jitted) ``train_step`` so each call emits one
    obs ``train_step`` event when a tracer is scoped — and is the identity
    call (same objects returned, no added work beyond one contextvar read)
    when none is.

    Host-side wrapper by design: ``make_train_step`` callers jit the step
    themselves, and anything inside the jitted function would run once at
    trace time, not per step. The wrapper measures the host *dispatch*
    time only (no ``block_until_ready`` — the hot path gains no sync) and
    records the selected metric scalars as live device arrays; they are
    resolved to floats when the tracer serializes, off the hot path.
    ``tokens_per_step`` (e.g. batch * seq_len) rides along for throughput
    aggregation."""
    from repro.obs.trace import current_tracer, monotonic_ns

    step_counter = [0]

    def instrumented(state, batch, rng=None):
        tr = current_tracer()
        if tr is None:
            return step_fn(state, batch, rng)
        t0 = monotonic_ns()
        state, metrics = step_fn(state, batch, rng)
        dur = monotonic_ns() - t0
        step_counter[0] += 1
        tr.emit("train_step", "train_step", step=step_counter[0],
                dur_ns=dur, tokens=tokens_per_step,
                metrics={k: metrics[k] for k in metric_keys
                         if k in metrics})
        return state, metrics

    return instrumented
