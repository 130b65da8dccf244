"""Folding AlphaFold-2 ``model_3``: ``modes/fold.py``'s closed loop of the
program's ``FastFold(cfg, plan).forward``, with the extra-MSA stack on, on
one device, checked against ``reference_xmsa``.

The feed is ``data.feed``'s batches with an extra MSA for each, drawn from
the seed under a salt of its own: ``n_extra_seq`` rows (``real_extra_seq``
of them unmasked) that copy the target at a per-position conservation
level, with deletion features. A traffic file whose ``n_extra_seq``
differs from the configuration's ``num_extra_msa`` is refused, and so is a
program without the extra stack (``program_xmsa``), before anything is
compiled.

On a traced run the mode also keeps the program scope of each device op of
the traced window (``scopes.load`` over the compiled fold's ``op_name``s),
for ``readers/scope_path_s.py``: the harness deletes the trace before its
readers run, and its ``Trace`` carries no scopes.
"""
from __future__ import annotations

import functools
import time

import jax
import numpy as np

from fastbench import check, data, program, program_xmsa, reference, \
    reference_xmsa, runtime, scopes
from fastbench.modes import Outcome
from fastbench.modes.fold import KEPT, SAMPLE_SALT, WEIGHT_SALT
from fastbench.runtime import span

EXTRA_SALT = 5

# The numbers ``run`` compares, each under a limit of the cell's file.
NUMBERS = ("distogram_gap", "msa_logits_gap", "coords_gap",
           "window_compiles")

# {device id: [(start, end, scope path)]} of the last traced window, unclipped.
_traced_scopes: dict = {}


def traced_scopes() -> dict:
    """The scope paths kept from this process's last traced window."""
    return _traced_scopes


def program_system(cfg: dict, mesh=None, extra: bool = True):
    """(compile the fold for given params and batch, params layout check)
    of the program under test; ``extra=False`` is the fault of the extra
    stack left out (the layout check still holds the full model's)."""
    del mesh
    check_layout = functools.partial(program.check_layout,
                                     program_xmsa.fastfold(cfg))
    ff = program_xmsa.fastfold(cfg, extra=extra)
    return (lambda p, b: ff.lower("forward", p, b).compile(), check_layout)


extra_left_out = functools.partial(program_system, extra=False)


def extra_msa(rng: np.random.Generator, batch: dict, mix: dict) -> dict:
    """The extra MSA of one batch: ``n_extra_seq`` rows, ``real_extra_seq``
    of them unmasked, each copying the target at a per-position
    conservation level and substituting elsewhere; per-position deletion
    counts (Poisson, mean 0.2) give AlphaFold's ``has_deletion`` (count >
    0) and ``deletion_value`` (2/pi * arctan(count / 3))."""
    aatype = batch["aatype"]
    b, r = aatype.shape
    s = mix["n_extra_seq"]
    lo, hi = mix["real_extra_seq"]
    n_real = rng.integers(lo, hi + 1, size=b)
    conservation = rng.beta(2.0, 2.0, size=(b, 1, r))
    mutate = rng.random((b, s, r)) > conservation
    subs = rng.integers(0, 20, size=(b, s, r))
    deletions = rng.poisson(0.2, size=(b, s, r))
    rows_on = (np.arange(s)[None, :] < n_real[:, None]).astype(np.float32)
    return {
        "extra_msa": np.where(mutate, subs,
                              aatype[:, None, :]).astype(np.int32),
        "extra_msa_mask": rows_on[:, :, None] * batch["seq_mask"][:, None, :],
        "extra_has_deletion": (deletions > 0).astype(np.float32),
        "extra_deletion_value": (2.0 / np.pi * np.arctan(deletions / 3.0)
                                 ).astype(np.float32),
    }


def with_extra_msa(seed: int, mix: dict, feed: list[dict]) -> list[dict]:
    """``feed``'s batches, each with its extra MSA drawn from the seed."""
    rng = np.random.default_rng(data.seed_sequence(seed, EXTRA_SALT))
    return [{**batch, **extra_msa(rng, batch, mix)} for batch in feed]


@functools.partial(jax.jit, static_argnames=("dims", "nx"))
def _reference_fold(params, batch, *, dims, nx):
    return {k: v for k, v in reference_xmsa.forward(params, batch, dims,
                                                    nx).items() if k in KEPT}


def reference_folds(dims, wkey, feed, indices, device, nx) -> dict:
    """The reference's fold of each feed batch in ``indices``, on one
    device, as numpy arrays."""
    from jax.sharding import SingleDeviceSharding

    with jax.default_matmul_precision("highest"):
        params = jax.jit(functools.partial(reference_xmsa.init_params,
                                           d=dims),
                         out_shardings=SingleDeviceSharding(device))(wkey)
        out = {b: jax.device_get(_reference_fold(
            params, jax.device_put(feed[b], device), dims=dims, nx=nx))
            for b in sorted(set(indices))}
        runtime.free(params)
    return out


def control_numbers(dims, wkey, feed, ref: dict, device) -> dict:
    """The float8 control's fold numbers for each batch of ``ref``, as
    ``modes/fold.py`` computes them for the trunk."""
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
    """Set-up and the window of ``system`` on one device, as
    ``modes/fold.py``'s; returns the Outcome without its numbers, the
    sampled folds [(feed index, outputs)] and (dims, weight key, feed)."""
    from jax.sharding import SingleDeviceSharding

    cfg, mix = ctx.cell.config, ctx.cell.traffic
    if mix["n_extra_seq"] != cfg["num_extra_msa"]:
        raise ValueError(f"{ctx.cell.name}: traffic n_extra_seq "
                         f"{mix['n_extra_seq']} differs from the "
                         f"configuration's num_extra_msa "
                         f"{cfg['num_extra_msa']}")
    if mix.get("dap", 1) != 1:
        raise ValueError(f"{ctx.cell.name}: this mode folds on one device")
    dev = ctx.devices[0]
    at = SingleDeviceSharding(dev)
    with span("setup"):
        compile_fold, check_layout = system(cfg, None)
        dims = reference_xmsa.XDims.from_config(cfg)
        init = jax.jit(functools.partial(reference_xmsa.init_params, d=dims),
                       out_shardings=at)
        wkey = jax.random.PRNGKey(data.jax_seed(ctx.seed, WEIGHT_SALT))
        check_layout(jax.eval_shape(init, wkey))
        feed = with_extra_msa(ctx.seed, mix, data.feed(ctx.seed, mix))
        batches = [{k: jax.device_put(v, at) for k, v in b.items()}
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
        _traced_scopes.clear()
        if hasattr(fold, "as_text"):
            _traced_scopes.update(scopes.load(
                out.trace_file, scopes.op_names(fold.as_text()), [dev.id]))
    else:
        window_s, out.attempted = runtime.closed_loop(dispatch, finish,
                                                      ctx.seconds)
        out.per_unit_s = window_s / out.attempted
    ctx.counter.armed = False
    out.memory_peak_bytes = runtime.memory_peak([dev])

    out.failed = sum(not np.all(np.isfinite(np.asarray(o["coords"])))
                     for _, o in kept)
    rng = np.random.default_rng(data.seed_sequence(ctx.seed, SAMPLE_SALT))
    sample = rng.choice(len(kept), size=min(mix["checked_folds"], len(kept)),
                        replace=False)
    checked = [(kept[i][0], jax.device_get(kept[i][1])) for i in sample]
    runtime.free(params, batches, [o for _, o in kept])
    kept.clear()
    return out, checked, (dims, wkey, feed)
