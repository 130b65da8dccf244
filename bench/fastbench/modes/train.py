"""Training: optimizer steps of the program's jitted
``make_train_step(FastFold(cfg, ExecutionPlan()).loss_fn)``, closed loop.

Set-up makes the weights from the seed in one jitted call on the chip,
places the feed's batches, compiles the step with its state donated, and
drives that one compiled step through its first ``CHECKED_STEPS`` steps on
distinct batches and dropout keys. Those steps are what the reference
follows; the window then continues the same state through the same call
and feed. After the window the program's state is freed and the reference
runs the same steps from the same weights (``check.train_numbers``).
"""
from __future__ import annotations

import collections
import functools
import math
import time

import jax
import numpy as np

from fastbench import check, data, program, reference, runtime
from fastbench.modes import Outcome
from fastbench.runtime import span

CHECKED_STEPS = 3
KEY_SALT, WEIGHT_SALT = 3, 2

State = collections.namedtuple("State", "step params opt_state")
OptState = collections.namedtuple("OptState", "step m v")


def program_system(cfg: dict, dims):
    """(init_state, step, params_check) of the program under test."""
    ff = program.fastfold(cfg)
    init_state, step = program.train_step(ff, cfg["optimizer"])
    return init_state, step, functools.partial(program.check_layout, ff)


def adam(cfg: dict) -> reference.Adam:
    o = cfg["optimizer"]
    return reference.Adam(lr=o["learning_rate"], warmup_steps=o["warmup_steps"],
                          total_steps=o["total_steps"],
                          clip_norm=o["clip_norm"], b1=o["b1"], b2=o["b2"],
                          eps=o["eps"])


def reference_system(nx: reference.Numerics):
    """The reference at ``nx`` in the program's place, with the program's
    state layout (the control is this at ``fp8``)."""

    def build(cfg: dict, dims):
        import jax.numpy as jnp

        opt = adam(cfg)

        def init_state(params):
            zeros = jax.tree.map(jnp.zeros_like, params)
            return State(jnp.zeros((), jnp.int32), params,
                         OptState(jnp.zeros((), jnp.int32), zeros, zeros))

        def step(state, batch, key):
            loss, grads = jax.value_and_grad(reference.loss)(
                state.params, batch, dims, nx, key)
            p, m, v, _ = opt.step(state.params, grads, state.opt_state.m,
                                  state.opt_state.v, state.step)
            return (State(state.step + 1, p, OptState(state.step + 1, m, v)),
                    {"loss": loss, "nonfinite_skips": jnp.zeros(())})

        return init_state, step, lambda shape: None

    return build


def _leaf_norms(tree):
    import jax.numpy as jnp

    return jnp.stack([jnp.linalg.norm(x.astype(jnp.float32).ravel())
                      for x in jax.tree.leaves(tree)])


def _change_norms(a, b):
    return _leaf_norms(jax.tree.map(lambda x, y: x - y, a, b))


@functools.partial(jax.jit, static_argnames=("dims", "nx", "opt"),
                   donate_argnums=(0, 1, 2))
def _reference_step(p, m, v, batch, key, k, *, dims, nx, opt):
    loss, grads = jax.value_and_grad(reference.loss)(p, batch, dims, nx, key)
    p, m, v, g = opt.step(p, grads, m, v, k)
    return p, m, v, loss, _leaf_norms(g)


def reference_steps(cfg, dims, init, wkey, batches, keys, nx) -> dict:
    """The reference's CHECKED_STEPS steps: losses, the first clipped
    gradient's leaf norms, and the leaf norms of the change of the weights."""
    import jax.numpy as jnp

    opt = adam(cfg)
    with jax.default_matmul_precision("highest"):
        p = init(wkey)
        m = jax.tree.map(jnp.zeros_like, p)
        v = jax.tree.map(jnp.zeros_like, p)
        losses = []
        for k in range(CHECKED_STEPS):
            p, m, v, loss, gn = _reference_step(
                p, m, v, batches[k], keys[k], jnp.int32(k), dims=dims, nx=nx,
                opt=opt)
            losses.append(float(loss))
            if k == 0:
                grad_norms = np.asarray(gn)
        runtime.free(m, v)
        p0 = init(wkey)
        change = np.asarray(jax.jit(_change_norms)(p, p0))
        runtime.free(p, p0)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


def run(ctx, system=program_system) -> Outcome:
    out, prog, inputs = program_phase(ctx, system)
    with span("reference"):
        ref = reference_steps(ctx.cell.config, *inputs, reference.FP32)
    out.numbers = check.train_numbers(prog, ref)
    out.numbers["window_compiles"] = float(ctx.counter.count)
    return out


def program_phase(ctx, system=program_system):
    """Set-up, the checked steps and the window of ``system``; returns the
    Outcome without its numbers, the program's readings of the checked
    steps, and the inputs the reference needs (dims, init, weight key,
    batches, dropout keys). The program's state is freed."""
    cfg, mix = ctx.cell.config, ctx.cell.traffic
    dims = reference.Dims.from_config(cfg)
    dev = ctx.devices[0]
    b1 = cfg["optimizer"]["b1"]
    with span("setup"):
        init = jax.jit(functools.partial(reference.init_params, d=dims),
                       out_shardings=jax.sharding.SingleDeviceSharding(dev))
        wkey = jax.random.PRNGKey(data.jax_seed(ctx.seed, WEIGHT_SALT))
        init_state, step, check_layout = system(cfg, dims)
        check_layout(jax.eval_shape(init, wkey))
        batches = [jax.device_put(b, dev) for b in data.feed(ctx.seed, mix)]
        key_rng = np.random.default_rng(data.seed_sequence(ctx.seed,
                                                           KEY_SALT))
        keys = [np.asarray(key_rng.integers(0, 2 ** 32, 2, dtype=np.uint32))
                for _ in range(CHECKED_STEPS)]
        state = jax.jit(init_state)(init(wkey))
        compiled = jax.jit(step, donate_argnums=0).lower(
            state, batches[0], keys[0]).compile()
        norms = jax.jit(_leaf_norms)
        change_norms = jax.jit(_change_norms)

    def key(k):
        while len(keys) <= k:
            keys.append(np.asarray(key_rng.integers(0, 2 ** 32, 2,
                                                    dtype=np.uint32)))
        return keys[k]

    def dispatch(k):
        nonlocal state
        state, metrics = compiled(state, batches[k % len(batches)], key(k))
        return metrics

    failed = 0

    def finish(metrics):
        nonlocal failed
        loss = float(metrics["loss"])
        if not math.isfinite(loss) or float(metrics["nonfinite_skips"]):
            failed += 1
        return loss

    with span("setup"):
        prog = {"losses": []}
        for k in range(CHECKED_STEPS):
            prog["losses"].append(finish(dispatch(k)))
            if k == 0:
                prog["grad_norms"] = np.asarray(norms(state.opt_state.m)) \
                    / (1.0 - b1)
        p0 = init(wkey)
        prog["change_norms"] = np.asarray(change_norms(state.params, p0))
        runtime.free(p0)
    failed = 0
    setup_s = time.perf_counter() - ctx.t0

    def window_dispatch(k):
        return dispatch(CHECKED_STEPS + k)

    ctx.counter.armed = True
    out = Outcome(setup_s=setup_s, attempted=0, failed=0, numbers={},
                  memory_peak_bytes=0)
    if ctx.trace:
        out.trace_file, out.cleanup = runtime.traced(
            window_dispatch, finish, 1)
        out.attempted = out.traced_units = 1
    else:
        window_s, out.attempted = runtime.closed_loop(window_dispatch, finish,
                                                      ctx.seconds)
        out.per_unit_s = window_s / out.attempted
    ctx.counter.armed = False
    out.failed = failed
    out.memory_peak_bytes = runtime.memory_peak([dev])
    runtime.free(state)
    return out, prog, (dims, init, wkey, batches, keys[:CHECKED_STEPS])
