"""Folding: the program's ``FastFold(cfg, plan).forward`` with recycling,
closed loop, under DAP on a (1, ``dap``) mesh when the traffic file asks
for it (parameters replicated, MSA inputs split on s), else on one device.

Set-up makes the weights from the seed in one jitted call that places them
where the plan wants them, places the feed's batches, compiles the fold and
runs it once. The window keeps the distogram logits, MSA logits and
coordinates of every fold it ran; afterwards a sample of them drawn from the
seed (``checked_folds``) is compared with the reference's fold of the same
batch, on one device, once the program's state is freed.
"""
from __future__ import annotations

import functools
import time

import jax
import numpy as np

from fastbench import check, data, program, reference, runtime
from fastbench.modes import Outcome
from fastbench.runtime import span

WEIGHT_SALT, SAMPLE_SALT = 2, 4
KEPT = ("distogram_logits", "msa_logits", "coords")


def program_system(cfg: dict, mesh):
    """(compile the fold for given params and batch, params layout
    check) of the program under test."""
    plan = program.dap_plan(mesh) if mesh is not None else None
    ff = program.fastfold(cfg, plan)
    return (lambda p, b: ff.lower("forward", p, b).compile(),
            functools.partial(program.check_layout, ff))


@functools.partial(jax.jit, static_argnames=("dims", "nx"))
def _reference_fold(params, batch, *, dims, nx):
    return {k: v for k, v in reference.forward(params, batch, dims,
                                               nx).items() if k in KEPT}


def reference_folds(dims, wkey, feed, indices, device, nx) -> dict:
    """The reference's fold of each feed batch in ``indices``, on one
    device, as numpy arrays."""
    from jax.sharding import SingleDeviceSharding

    with jax.default_matmul_precision("highest"):
        params = jax.jit(functools.partial(reference.init_params, d=dims),
                         out_shardings=SingleDeviceSharding(device))(wkey)
        out = {b: jax.device_get(_reference_fold(
            params, jax.device_put(feed[b], device), dims=dims, nx=nx))
            for b in sorted(set(indices))}
        runtime.free(params)
    return out


def control_numbers(dims, wkey, feed, ref: dict, device) -> dict:
    """The control's fold numbers for each batch of ``ref`` (feed index ->
    the reference's fold): the reference with every matrix product's
    operands rounded through float8 e4m3, in the program's place."""
    ctl = reference_folds(dims, wkey, feed, list(ref), device,
                          reference.Numerics("fp8"))
    return {b: check.fold_numbers(ctl[b], ref[b], feed[b]) for b in ref}


def run(ctx, system=program_system) -> Outcome:
    out, checked, inputs = program_phase(ctx, system)
    dims, wkey, feed = inputs
    with span("reference"):
        ref = reference_folds(dims, wkey, feed, [b for b, _ in checked],
                              ctx.devices[0], reference.FP32)
    out.numbers = check.worst([check.fold_numbers(got, ref[b], feed[b])
                               for b, got in checked])
    out.numbers["window_compiles"] = float(ctx.counter.count)
    return out


def program_phase(ctx, system=program_system):
    """Set-up and the window of ``system``; returns the Outcome without its
    numbers, the sampled folds [(feed index, outputs)] as numpy arrays, and
    the inputs the reference needs (dims, weight key, feed). The program's
    state is freed."""
    from jax.sharding import NamedSharding, PartitionSpec, \
        SingleDeviceSharding

    cfg, mix = ctx.cell.config, ctx.cell.traffic
    dims = reference.Dims.from_config(cfg)
    dap = mix.get("dap", 1)
    devs = ctx.devices[:max(dap, 1)]
    with span("setup"):
        if dap > 1:
            mesh = program.dap_mesh(devs, dap)
            weights_at = NamedSharding(mesh, PartitionSpec())
        else:
            mesh = None
            weights_at = SingleDeviceSharding(devs[0])
        compile_fold, check_layout = system(cfg, mesh)
        init = jax.jit(functools.partial(reference.init_params, d=dims),
                       out_shardings=weights_at)
        wkey = jax.random.PRNGKey(data.jax_seed(ctx.seed, WEIGHT_SALT))
        check_layout(jax.eval_shape(init, wkey))
        feed = data.feed(ctx.seed, mix)
        at = (program.batch_shardings(mesh, feed[0]) if mesh is not None
              else {k: weights_at for k in feed[0]})
        batches = [{k: jax.device_put(v, at[k]) for k, v in b.items()}
                   for b in feed]
        params = init(wkey)
        fold = compile_fold(params, batches[0])
        jax.block_until_ready(fold(params, batches[0], None))

    kept = []

    def dispatch(k):
        out = fold(params, batches[k % len(batches)], None)
        kept.append((k % len(batches), {n: out[n] for n in KEPT}))
        return kept[-1][1]

    def finish(out):
        jax.block_until_ready(out)

    setup_s = time.perf_counter() - ctx.t0
    ctx.counter.armed = True
    out = Outcome(setup_s=setup_s, attempted=0, failed=0, numbers={},
                  memory_peak_bytes=0)
    if ctx.trace:
        out.trace_file, out.cleanup = runtime.traced(
            dispatch, finish, mix["traced_folds"])
        out.attempted = out.traced_units = mix["traced_folds"]
    else:
        window_s, out.attempted = runtime.closed_loop(dispatch, finish,
                                                      ctx.seconds)
        out.per_unit_s = window_s / out.attempted
    ctx.counter.armed = False
    out.memory_peak_bytes = runtime.memory_peak(devs)

    out.failed = sum(not np.all(np.isfinite(np.asarray(o["coords"])))
                     for _, o in kept)
    rng = np.random.default_rng(data.seed_sequence(ctx.seed, SAMPLE_SALT))
    sample = rng.choice(len(kept), size=min(mix["checked_folds"], len(kept)),
                        replace=False)
    checked = [(kept[i][0], jax.device_get(kept[i][1])) for i in sample]
    runtime.free(params, batches, [o for _, o in kept])
    kept.clear()
    return out, checked, (dims, wkey, feed)
