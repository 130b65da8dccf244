#!/usr/bin/env python3
"""FastFold on the chip: the full-width AlphaFold fold and train step on TPU.

    python chip_smoke.py              # one chip: phases (a), (b), (c)
    python chip_smoke.py --chips 4    # four chips: the DAP phase only

One process, first device. Phases at the published model width
(``configs.alphafold.FULL``: d_msa 256, d_pair 128, 48 Evoformer blocks,
8 structure iterations, 3 recycles) on a synthetic ``protein_batches`` input
at the paper's Table I initial-training shapes (n_res 256, n_seq 128, one
sequence per chip), masked as a padded crop:

  (a) fold:   ``FastFold(FULL, ExecutionPlan())`` init, then ``forward``.
  (b) train:  three optimizer steps through ``FastFold.loss_fn`` and
              ``train.loop.make_train_step`` (the calls that
              ``examples/train_alphafold_mini.py`` makes).
  (c) oracle: the same fold, and the first step's loss and gradient,
              again under ``preset("oracle")`` (jnp references, no
              Pallas), compared with the default plan's.
  --chips 4:  the same fold under ``ParallelPolicy("gspmd")`` on a (1, 4)
              mesh of ``jax.devices()``, against the fold on one device.

Weights are random from a seed. AlphaFold zero-initializes every residual
output projection, which would make each Evoformer update exactly zero and
the default-vs-oracle comparison blind to the kernels, so the zero leaves
are redrawn at a small scale.

Exits non-zero, with no result line, when: no TPU is found; a kernel family
on the path resolves to a leg other than its default on TPU (``pallas`` for
attention, triangle and OPM, ``xla`` for the row-wise families, see
``kernels/ops.py``): an ``REPRO_PLAN`` override or an envelope fallback; a
compiled program holds no
``tpu_custom_call``; a loss is non-finite; a comparison misses its
tolerance; or any phase raises. Otherwise the last stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import re
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

N_RES, N_SEQ, BATCH = 256, 128, 1     # paper Table I, initial training
# The batch is a padded crop: a 229-residue chain with 117 MSA sequences,
# padded to the Table I shapes. The masks then zero a tail of residues and
# of MSA rows that ends mid-tile in every kernel, so the comparisons see
# the attention, softmax, triangle and OPM masking and the kv-length
# padding of the row statistics.
N_RES_REAL, N_SEQ_REAL = 229, 117
TRAIN_STEPS = 3
# AlphaFold warms Adam up to 1e-3 over 1000 steps; from a random init the
# 48-block trunk diverges at that rate, so the three steps take a small one.
LEARNING_RATE = 3e-5
SEED = 0
# Scale of the redrawn zero-initialized leaves: each update is ~0.1x its
# input, so 48 blocks x 4 passes stay well inside bf16 range.
ZERO_LEAF_SCALE = 0.1
# Default (Pallas) vs oracle (jnp) and DAP vs one device: worst relative L2
# error of the distogram logits, MSA logits and coordinates, and of the
# first step's gradient. Both sides compute in bf16 and round in different
# orders through 48 blocks. Each limit sits between the sound tree's reading
# on a TPU v5e and the readings with one fault planted: the flash-attention
# forward kernel without its mask add, or the triangle kernel without its
# pair mask.
#   single-pass fold: sound 3.0e-2 (3.6e-2 DAP); faults 8.4e-2 and 1.9e-1
#   recycled fold:    sound 8.2e-2 (9.5e-2 DAP); faults 1.6e-1 and 2.3e-1
#   gradient:         sound 8.1e-3; attention fault 1.1e-1 (the triangle
#                     fault touches only padded pairs, which no loss reads)
# The loss reads 3.9e-5 sound and moves less than that under either fault,
# so it is held only to catching a gross error.
FOLD_RTOL = 5e-2
RECYCLED_FOLD_RTOL = 1.2e-1
GRAD_RTOL = 3e-2
LOSS_RTOL = 1e-3
# pallas_call wrappers (the ``jit(<name>)`` in each custom call's op_name)
# that a compiled program of each kind must contain.
# The row-wise families run on XLA (kernels/ops.py ``kernel_leg``), so no
# program holds their kernels.
FOLD_KERNELS = ("flash_attention_pallas", "fused_triangle_pallas",
                "fused_opm_pallas")
TRAIN_KERNELS = FOLD_KERNELS + ("flash_attention_bwd_pallas",)
# Under GSPMD the pair-stack and attention kernels run inside the dist
# backend's shard_map, as on one device.
DAP_KERNELS = FOLD_KERNELS
DAP_DEVICES = 4
ROW_FAMILIES = ("softmax", "layer_norm", "elementwise")
OP_FAMILIES = ("attention", "triangle", "opm") + ROW_FAMILIES


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def kernel_census(compiled) -> dict:
    """Count of ``tpu_custom_call`` ops per pallas_call wrapper."""
    counts: dict = {}
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.search(r"jit\((\w+_pallas)\)", line)
        name = m.group(1) if m else "unnamed"
        counts[name] = counts.get(name, 0) + 1
    return counts


def check_census(label: str, compiled, expected) -> None:
    census = kernel_census(compiled)
    total = sum(census.values())
    log(f"[{label}] tpu_custom_calls={total} by_kernel={census}")
    check(total > 0, f"{label}: the compiled program has no tpu_custom_call")
    missing = [k for k in expected if k not in census]
    check(not missing, f"{label}: no {missing} custom call in the program")


def check_legs(plan, cfg) -> None:
    """Every op family resolves to its default leg on TPU (the compiled
    Pallas kernel; XLA for the row-wise families, on every backend), at this
    model's shapes (no envelope fallback), and no env plan overrides the
    default."""
    from repro.exec.plan import ExecutionPlan, use_plan
    from repro.kernels import ops

    env_plan = ExecutionPlan.from_env()
    check(env_plan == ExecutionPlan(),
          f"the environment selects a non-default plan: {env_plan.describe()}")
    evo = cfg.evoformer
    dt = cfg.compute_dtype
    want = {op: "xla" if op in ROW_FAMILIES else "pallas"
            for op in OP_FAMILIES}
    with use_plan(plan):
        legs = {op: ops.kernel_leg(op) for op in OP_FAMILIES}
        envelopes = {
            "msa_row": ops.fused_attention_supported(
                (BATCH, N_SEQ, N_RES, evo.msa_heads, evo.head_dim), N_RES, dt),
            "msa_col": ops.fused_attention_supported(
                (BATCH, N_RES, N_SEQ, evo.msa_heads, evo.head_dim), N_SEQ, dt),
            "tri_attn": ops.fused_attention_supported(
                (BATCH, N_RES, N_RES, evo.pair_heads, evo.head_dim), N_RES,
                dt),
            "tri_mult": ops.fused_triangle_supported(evo.tri_mult_dim,
                                                     evo.d_pair, dt),
            "opm": ops.fused_opm_supported(evo.opm_dim, evo.d_pair, dt),
        }
    log(f"[legs] {legs} envelopes={envelopes}")
    bad = {op: leg for op, leg in legs.items() if leg != want[op]}
    check(not bad, f"kernel families off their leg {want}: {bad}")
    out = [site for site, ok in envelopes.items() if not ok]
    check(not out, f"sites outside the fused-kernel envelope: {out}")


def make_batch(seed: int = SEED):
    """A ``protein_batches`` batch at (N_RES, N_SEQ), masked down to the
    padded crop's N_RES_REAL residues and N_SEQ_REAL MSA rows."""
    import jax.numpy as jnp
    import numpy as np

    from repro.data import protein_batches

    pb = next(protein_batches(batch=BATCH, n_seq=N_SEQ, n_res=N_RES,
                              seed=seed))
    seq_mask = np.zeros_like(pb.seq_mask)
    seq_mask[:, :N_RES_REAL] = 1.0
    msa_mask = np.zeros_like(pb.msa_mask)
    msa_mask[:, :N_SEQ_REAL, :N_RES_REAL] = 1.0
    batch = dataclasses.replace(pb, seq_mask=seq_mask, msa_mask=msa_mask,
                                bert_mask=pb.bert_mask * msa_mask)
    return {k: jnp.asarray(getattr(batch, k)) for k in
            ("msa", "msa_mask", "residue_index", "aatype", "seq_mask",
             "pseudo_beta", "bert_mask", "true_msa")}


def init_params(ff, seed: int = SEED):
    """``FastFold.init`` with the all-zero leaves (residual output
    projections and biases) redrawn from the seed; see the module doc."""
    import jax
    import jax.numpy as jnp

    params = ff.init(jax.random.PRNGKey(seed))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    out = []
    for x, k in zip(leaves, keys):
        if bool(jnp.all(x == 0)):
            fan_in = x.shape[-2] if x.ndim >= 2 else x.shape[-1]
            x = (ZERO_LEAF_SCALE / fan_in ** 0.5
                 * jax.random.normal(k, x.shape, jnp.float32)).astype(x.dtype)
        out.append(x)
    return jax.tree.unflatten(tree, out)


def rel_errs(got, want) -> tuple[float, float]:
    """(relative L2 error, max |got - want| / max |want|)."""
    import jax.numpy as jnp

    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    diff = got - want
    l2 = jnp.linalg.norm(diff) / jnp.maximum(jnp.linalg.norm(want), 1e-30)
    mx = jnp.max(jnp.abs(diff)) / jnp.maximum(jnp.max(jnp.abs(want)), 1e-30)
    return float(l2), float(mx)


def compare_folds(label: str, got: dict, want: dict, tol: float) -> float:
    errs = {k: rel_errs(got[k], want[k])
            for k in ("distogram_logits", "msa_logits", "coords")}
    worst = max(l2 for l2, _ in errs.values())
    log(f"[{label}] " + " ".join(
        f"{k}: rel_l2={l2:.3e} rel_max={mx:.3e}" for k, (l2, mx) in
        errs.items()) + f" worst_rel_l2={worst:.3e} tol={tol:.1e}")
    check(worst <= tol,
          f"{label}: relative L2 difference {worst:.3e} > {tol:.1e}")
    return worst


def run_fold(label: str, ff, params, batch, plan=None, expected=()):
    """Compile and run one fold; returns its outputs."""
    compiled, c_s = compile_timed(ff.lower("forward", params, batch,
                                           plan=plan))
    if expected:
        check_census(label, compiled, expected)
    out, r1 = timed(compiled, params, batch, None)
    log(f"[{label}] compile_s={c_s:.2f} step_s={r1:.4f}")
    return out


def single_pass(cfg):
    return dataclasses.replace(cfg, n_recycle=0)


def timed(fn, *args):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def compile_timed(lowered):
    t0 = time.perf_counter()
    compiled = lowered.compile()
    return compiled, time.perf_counter() - t0


def memory_line(label: str, compiled) -> None:
    mem = compiled.memory_analysis()
    if mem is None:
        return
    gib = 1 << 30
    log(f"[{label}] memory_analysis args={mem.argument_size_in_bytes / gib:.2f}"
        f"GiB temp={mem.temp_size_in_bytes / gib:.2f}GiB "
        f"out={mem.output_size_in_bytes / gib:.2f}GiB")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def run_one_chip(cfg):
    """Phases (a)-(c) on the first device."""
    import jax
    import jax.numpy as jnp

    from repro.exec.plan import ExecutionPlan, preset
    from repro.exec.session import FastFold
    from repro.layers.params import count_params
    from repro.train.loop import make_train_step

    plan = ExecutionPlan()
    oracle = preset("oracle")
    check_legs(plan, cfg)
    ff = FastFold(cfg, plan)
    batch = make_batch()
    params = init_params(ff)
    log(f"[init] params={count_params(params):,} n_blocks="
        f"{cfg.evoformer.n_blocks} n_recycle={cfg.n_recycle} n_res={N_RES} "
        f"n_seq={N_SEQ} batch={BATCH}")

    # (a) fold, 3 recycles
    compiled, c_s = compile_timed(ff.lower("forward", params, batch))
    check_census("fold", compiled, FOLD_KERNELS)
    memory_line("fold", compiled)
    fold, r1 = timed(compiled, params, batch, None)
    _, r2 = timed(compiled, params, batch, None)
    check(bool(jnp.all(jnp.isfinite(fold["coords"]))),
          "fold: non-finite coordinates")
    log(f"[fold] compile_s={c_s:.2f} step_s={r1:.4f} step_s_warm={r2:.4f}")

    # (b) train steps
    init_state, train_step = make_train_step(
        ff.loss_fn, base_lr=LEARNING_RATE, warmup_steps=1,
        total_steps=TRAIN_STEPS)
    state = init_state(params)
    rngs = [jax.random.PRNGKey(i) for i in range(TRAIN_STEPS)]
    compiled, c_s = compile_timed(
        jax.jit(train_step).lower(state, batch, rngs[0]))
    check_census("train", compiled, TRAIN_KERNELS)
    memory_line("train", compiled)
    log(f"[train] compile_s={c_s:.2f}")
    losses = []
    for i, rng in enumerate(rngs):
        (state, metrics), dt = timed(compiled, state, batch, rng)
        loss, gnorm = float(metrics["loss"]), float(metrics["grad_norm"])
        losses.append(loss)
        log(f"[train] step={i + 1} loss={loss:.6f} grad_norm={gnorm:.4f} "
            f"step_s={dt:.4f}")
        check(math.isfinite(loss) and math.isfinite(gnorm)
              and float(metrics["nonfinite_skips"]) == 0.0,
              f"train step {i + 1}: non-finite loss or gradient")

    del state
    # (c) oracle: the same fold (and a single pass), and the first step's
    # loss and gradient
    fold_ref = run_fold("oracle-fold", ff, params, batch, plan=oracle)
    fold_err = compare_folds("oracle-fold", fold, fold_ref,
                             RECYCLED_FOLD_RTOL)
    ff1 = FastFold(single_pass(cfg), plan)
    fold_err = max(fold_err, compare_folds(
        "oracle-fold-1pass",
        run_fold("fold-1pass", ff1, params, batch, expected=FOLD_KERNELS),
        run_fold("oracle-fold-1pass", ff1, params, batch, plan=oracle),
        FOLD_RTOL))
    grads = {}
    for name, p in (("default", plan), ("oracle", oracle)):
        value_and_grad = jax.jit(jax.value_and_grad(
            FastFold(cfg, p).loss_fn, has_aux=True))
        compiled, c_s = compile_timed(
            value_and_grad.lower(params, batch, rngs[0]))
        if name == "default":
            check_census("grad", compiled, TRAIN_KERNELS)
        ((loss, _), grads[name]), r1 = timed(compiled, params, batch,
                                             rngs[0])
        log(f"[{name}-grad] compile_s={c_s:.2f} step_s={r1:.4f} "
            f"loss={float(loss):.6f}")
        grads[name + "_loss"] = float(loss)
    loss_err = (abs(grads["default_loss"] - grads["oracle_loss"])
                / abs(grads["oracle_loss"]))
    leaves = zip(jax.tree.leaves(grads["default"]),
                 jax.tree.leaves(grads["oracle"]))
    num = den = 0.0
    for g, g_ref in leaves:
        num += float(jnp.sum(jnp.square(g.astype(jnp.float32)
                                        - g_ref.astype(jnp.float32))))
        den += float(jnp.sum(jnp.square(g_ref.astype(jnp.float32))))
    grad_err = (num / max(den, 1e-30)) ** 0.5
    log(f"[oracle-grad] loss rel_err={loss_err:.3e} tol={LOSS_RTOL:.0e} "
        f"grad rel_l2={grad_err:.3e} tol={GRAD_RTOL:.0e}")
    check(loss_err <= LOSS_RTOL,
          f"oracle loss differs by {loss_err:.3e} > {LOSS_RTOL:.0e}")
    check(grad_err <= GRAD_RTOL,
          f"oracle gradient differs by {grad_err:.3e} > {GRAD_RTOL:.0e}")
    log(f"[oracle] largest default-vs-oracle difference="
        f"{max(fold_err, loss_err, grad_err):.3e}")


def _spans_all(x, devices) -> bool:
    return set(x.sharding.device_set) == set(devices)


def run_dap(cfg):
    """The fold under GSPMD DAP on a (1, DAP_DEVICES) mesh vs the same fold
    on one device."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.dist import dap_msa_spec
    from repro.exec.plan import ExecutionPlan
    from repro.exec.session import FastFold
    from repro.launch.mesh import make_host_mesh

    devices = jax.devices()
    check(len(devices) >= DAP_DEVICES,
          f"DAP phase needs {DAP_DEVICES} devices, JAX sees {len(devices)}")
    mesh = make_host_mesh(model=DAP_DEVICES, data=1)
    base = ExecutionPlan()
    dap_plan = base.with_parallel(backend="gspmd", mesh=mesh)
    check_legs(base, cfg)
    check_legs(dap_plan, cfg)
    one = FastFold(cfg, base)
    dap = FastFold(cfg, dap_plan)
    params = init_params(one)
    batch = make_batch()

    # One device: the reference folds, with 3 recycles and a single pass.
    d0 = devices[0]
    p0 = jax.device_put(params, d0)
    b0 = jax.device_put(batch, d0)
    ref = run_fold("fold-1dev", one, p0, b0, expected=FOLD_KERNELS)
    ref1 = run_fold("fold-1dev-1pass", FastFold(single_pass(cfg), base),
                    p0, b0, expected=FOLD_KERNELS)

    # DAP: parameters replicated on every device, MSA sharded on s, the
    # masks alongside, per-residue features replicated.
    rep = NamedSharding(mesh, P())
    msa_rows = NamedSharding(mesh, P(*dap_msa_spec(mesh, "s")[:3]))
    pd = jax.device_put(params, rep)
    bd = {k: jax.device_put(v, msa_rows if v.ndim == 3 and k != "pseudo_beta"
                            else rep) for k, v in batch.items()}
    compiled, c_s = compile_timed(dap.lower("forward", pd, bd))
    check_census("fold-dap", compiled, DAP_KERNELS)
    memory_line("fold-dap", compiled)
    out, r1 = timed(compiled, pd, bd, None)
    _, r2 = timed(compiled, pd, bd, None)
    log(f"[fold-dap] devices={DAP_DEVICES} compile_s={c_s:.2f} "
        f"step_s={r1:.4f} step_s_warm={r2:.4f}")

    mesh_devs = list(mesh.devices.flat)
    check(all(_spans_all(x, mesh_devs) for x in jax.tree.leaves(pd)),
          "DAP: a parameter is not on every mesh device")
    check(_spans_all(bd["msa"], mesh_devs), "DAP: the MSA input is not sharded")
    pair = out["pair"]
    shards = pair.addressable_shards
    log(f"[fold-dap] pair sharding={pair.sharding} shard_shapes="
        f"{sorted({tuple(s.data.shape) for s in shards})} devices="
        f"{sorted(d.id for d in pair.sharding.device_set)}")
    check(_spans_all(pair, mesh_devs),
          "DAP: the pair output is not on every device")
    check(all(s.data.shape[1] == N_RES // DAP_DEVICES for s in shards),
          "DAP: the pair output is not split along i across the devices")
    out1 = run_fold("fold-dap-1pass", FastFold(single_pass(cfg), dap_plan),
                    pd, bd, expected=DAP_KERNELS)
    compare_folds("dap-vs-1dev", jax.device_get(out), jax.device_get(ref),
                  RECYCLED_FOLD_RTOL)
    compare_folds("dap-vs-1dev-1pass", jax.device_get(out1),
                  jax.device_get(ref1), FOLD_RTOL)


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the DAP phase on a (1, 4) mesh")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found platform {dev.platform!r} "
              f"({len(devices)} {dev.device_kind!r} device(s)); this smoke "
              f"test runs only on a TPU", file=sys.stderr)
        return 2
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__}")

    sys.path.insert(0, SRC)
    from repro.configs.alphafold import FULL
    from repro.launch.cache import enable_compilation_cache

    log(f"[cache] compilation cache dir={enable_compilation_cache()}")
    try:
        if args.chips == 4:
            run_dap(FULL)
        else:
            run_one_chip(FULL)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
