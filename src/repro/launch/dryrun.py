from repro.exec import envcompat
envcompat.force_host_device_count(512)  # before jax import: no backend yet
# Kernels stay ENABLED: on a non-TPU backend every op lowers its XLA-native
# leg (ops.kernel_leg) — interpret-mode Pallas (a per-grid-cell loop,
# catastrophic inside a 512-device SPMD program) never runs unless the plan
# asks for interpret mode. In particular the Evoformer attention sites lower
# the shard_map-wrapped fused-attention path (GspmdDist.sharded_attention),
# i.e. the dry-run proves the production DAP x fused-kernel composition —
# no oracle fallback, no merged-(B, G) all-gather.

"""Multi-pod dry-run (deliverable e).

For every (architecture x input shape) combination, and for both the 16x16
single-pod and 2x16x16 multi-pod production meshes:

    with mesh:
        lowered = jax.jit(step_fn, in_shardings=..., out_shardings=...) \
            .lower(*input_specs(arch, shape))
        compiled = lowered.compile()
        compiled.memory_analysis()   # proves it fits 16 GB/chip
        compiled.cost_analysis()     # FLOPs/bytes for the roofline

plus the FastFold/AlphaFold model itself (Initial-Training and Fine-tuning
shapes under DAP). Results are dumped as JSON consumed by
benchmarks/roofline_report.py and EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-1.5b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out f.json]
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import INPUT_SHAPES, get_config, list_archs
from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch.cache import enable_compilation_cache
from repro.launch.mesh import (
    HBM_BW, HBM_BYTES, ICI_BW, PEAK_FLOPS_BF16, make_production_mesh,
)
from repro.models.decoder import init_cache, init_model, lm_loss, model_forward
from repro.parallel import plan
from repro.roofline import analysis
from repro.train.loop import make_train_step


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def skip_reason(cfg: ModelConfig, shape: ShapeConfig) -> str | None:
    if shape.name == "long_500k" and not cfg.subquadratic:
        return ("pure full-attention arch: long_500k requires a sub-quadratic "
                "path (DESIGN.md §Arch-applicability)")
    return None


def text_len(cfg: ModelConfig, shape: ShapeConfig) -> int:
    """Text tokens; VLM prefix tokens count toward the sequence budget."""
    if cfg.modality and cfg.modality.n_prefix_tokens and shape.kind != "decode":
        return shape.seq_len - cfg.modality.n_prefix_tokens
    return shape.seq_len


# ---------------------------------------------------------------------------
# step builders: return (fn, example_args, in_shardings, out_shardings)
# ---------------------------------------------------------------------------

def build_train(cfg: ModelConfig, shape: ShapeConfig, mesh):
    cfg = plan.moe_with_groups(cfg, mesh)
    b, s = shape.global_batch, text_len(cfg, shape)
    shard_x = plan.make_shard_x(mesh, shape)

    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    init_state, train_step = make_train_step(
        lambda p, batch, rng: lm_loss(p, batch, cfg, shard_x=shard_x),
        base_lr=3e-4, total_steps=10_000, weight_decay=0.1,
        state_dtype=jnp.bfloat16 if cfg.opt_state_bf16 else jnp.float32)
    state = jax.eval_shape(lambda: init_state(params))

    p_specs = plan.model_param_specs(params, mesh)
    state_specs = plan.train_state_specs(state, mesh, p_specs)
    tok_spec = plan.token_spec(mesh, shape)
    batch = {
        "tokens": sds((b, s), jnp.int32),
        "targets": sds((b, s), jnp.int32),
        "mask": sds((b, s), jnp.float32),
    }
    batch_specs = {"tokens": tok_spec, "targets": tok_spec, "mask": tok_spec}
    if cfg.modality and cfg.modality.n_prefix_tokens:
        batch["prefix_embeds"] = sds(
            (b, cfg.modality.n_prefix_tokens, cfg.d_model), jnp.bfloat16)
        batch_specs["prefix_embeds"] = P(
            plan.batch_axes(mesh), plan.seq_axes(mesh, shape), None)

    def fn(state, batch):
        new_state, metrics = train_step(state, batch, None)
        return new_state, metrics["loss"]

    in_sh = (jax.tree.map(lambda sp: NamedSharding(mesh, sp), state_specs,
                          is_leaf=lambda x: isinstance(x, P)),
             jax.tree.map(lambda sp: NamedSharding(mesh, sp), batch_specs,
                          is_leaf=lambda x: isinstance(x, P)))
    out_sh = (in_sh[0], NamedSharding(mesh, P()))
    return fn, (state, batch), in_sh, out_sh


def build_prefill(cfg: ModelConfig, shape: ShapeConfig, mesh):
    cfg = plan.moe_with_groups(cfg, mesh)
    b, s = shape.global_batch, text_len(cfg, shape)
    shard_x = plan.make_shard_x(mesh, shape)
    params = jax.eval_shape(
        lambda: plan_cast_bf16(init_model(jax.random.PRNGKey(0), cfg)))
    p_specs = plan.model_param_specs(
        params, mesh,
        force_shard=False if cfg.serve_replicate_params else None)

    args = [sds((b, s), jnp.int32)]
    arg_specs = [plan.token_spec(mesh, shape)]
    prefix = None
    if cfg.modality and cfg.modality.n_prefix_tokens:
        args.append(sds((b, cfg.modality.n_prefix_tokens, cfg.d_model),
                        jnp.bfloat16))
        arg_specs.append(P(plan.batch_axes(mesh),
                           plan.seq_axes(mesh, shape), None))

    def fn(params, tokens, *rest):
        pe = rest[0] if rest else None
        out = model_forward(params, tokens, cfg, mode="prefill",
                            prefix_embeds=pe, shard_x=shard_x,
                            max_cache_len=shape.seq_len)
        return out["logits"][:, -1], out["cache"]

    cache_shapes = jax.eval_shape(lambda: init_cache(cfg, b, shape.seq_len))
    c_specs = plan.cache_specs(cache_shapes, mesh, shape, cfg)
    to_sh = lambda tree: jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), tree,
        is_leaf=lambda x: isinstance(x, P))
    in_sh = (to_sh(p_specs), *[NamedSharding(mesh, sp) for sp in arg_specs])
    out_sh = (NamedSharding(mesh, P(plan.batch_axes(mesh), None)),
              to_sh(c_specs))
    return fn, (params, *args), in_sh, out_sh


def build_decode(cfg: ModelConfig, shape: ShapeConfig, mesh):
    cfg = plan.moe_with_groups(cfg, mesh)
    b = shape.global_batch
    shard_x = plan.make_shard_x(mesh, shape)
    params = jax.eval_shape(
        lambda: plan_cast_bf16(init_model(jax.random.PRNGKey(0), cfg)))
    p_specs = plan.model_param_specs(
        params, mesh,
        force_shard=False if cfg.serve_replicate_params else None)
    cache = jax.eval_shape(lambda: init_cache(cfg, b, shape.seq_len))
    c_specs = plan.cache_specs(cache, mesh, shape, cfg)

    tokens = sds((b, 1), jnp.int32)
    lengths = sds((b,), jnp.int32)
    b_ax = plan.batch_axes(mesh) if b > 1 else None

    def fn(params, tokens, cache, lengths):
        out = model_forward(params, tokens, cfg, mode="decode", cache=cache,
                            lengths=lengths, shard_x=shard_x)
        return out["logits"][:, 0], out["cache"]

    to_sh = lambda tree: jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), tree,
        is_leaf=lambda x: isinstance(x, P))
    in_sh = (to_sh(p_specs), NamedSharding(mesh, P(b_ax, None)),
             to_sh(c_specs), NamedSharding(mesh, P(b_ax)))
    out_sh = (NamedSharding(mesh, P(b_ax, None)), to_sh(c_specs))
    return fn, (params, tokens, cache, lengths), in_sh, out_sh


def plan_cast_bf16(params):
    return jax.tree.map(
        lambda x: x.astype(jnp.bfloat16)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, params)


BUILDERS = {"train": build_train, "prefill": build_prefill,
            "decode": build_decode}


# ---------------------------------------------------------------------------
# AlphaFold (the paper's own model) under DAP
# ---------------------------------------------------------------------------

def build_alphafold(variant: str, mesh, evo_overrides: dict | None = None):
    from repro.configs import alphafold as afc
    from repro.core.alphafold import alphafold_train_loss, init_alphafold
    from repro.core.dist import GspmdDist, batch_spec

    cfg = afc.FULL
    if evo_overrides:
        cfg = dataclasses.replace(
            cfg, evoformer=dataclasses.replace(cfg.evoformer, **evo_overrides))
    dims = afc.INITIAL_TRAINING if variant == "initial" else afc.FINE_TUNING
    b = dims["batch"]
    s, r = dims["n_seq"], dims["n_res"]
    dist = GspmdDist(mesh=mesh, axis="model")
    bx = batch_spec(mesh)

    batch = {
        "msa": sds((b, s, r), jnp.int32),
        "msa_mask": sds((b, s, r), jnp.float32),
        "residue_index": sds((b, r), jnp.int32),
        "aatype": sds((b, r), jnp.int32),
        "seq_mask": sds((b, r), jnp.float32),
        "pseudo_beta": sds((b, r, 3), jnp.float32),
        "bert_mask": sds((b, s, r), jnp.float32),
        "true_msa": sds((b, s, r), jnp.int32),
    }
    batch_specs = {
        "msa": P(bx, "model", None), "msa_mask": P(bx, "model", None),
        "residue_index": P(bx, None), "aatype": P(bx, None),
        "seq_mask": P(bx, None), "pseudo_beta": P(bx, None, None),
        "bert_mask": P(bx, "model", None), "true_msa": P(bx, "model", None),
    }

    params = jax.eval_shape(
        lambda: init_alphafold(jax.random.PRNGKey(0), cfg))
    init_state, train_step = make_train_step(
        lambda p, bb, rng: alphafold_train_loss(p, bb, cfg, dist=dist),
        base_lr=1e-3, total_steps=10_000)
    state = jax.eval_shape(lambda: init_state(params))
    # paper-faithful DAP: params fully replicated; ZeRO-1 on optimizer m/v
    p_specs = plan.tree_replicated(params)
    state_specs = plan.train_state_specs(state, mesh, p_specs)

    def fn(state, batch):
        new_state, metrics = train_step(state, batch, None)
        return new_state, metrics["loss"]

    to_sh = lambda tree: jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), tree,
        is_leaf=lambda x: isinstance(x, P))
    in_sh = (to_sh(state_specs), to_sh(batch_specs))
    out_sh = (in_sh[0], NamedSharding(mesh, P()))
    return fn, (state, batch), in_sh, out_sh


# ---------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------

def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            overrides: dict | None = None) -> dict:
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    rec = {"arch": arch, "shape": shape_name, "chips": chips,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "overrides": overrides or {}}

    if arch.startswith("alphafold"):
        variant = arch.split("-")[1]
        fn, args, in_sh, out_sh = build_alphafold(variant, mesh,
                                                  evo_overrides=overrides)
        cfg = None
        shape = ShapeConfig(arch, 0, 128, "train")
    else:
        cfg = get_config(arch)
        if overrides:
            cfg = dataclasses.replace(cfg, **overrides)
        shape = INPUT_SHAPES[shape_name]
        skip = skip_reason(cfg, shape)
        if skip:
            rec.update({"status": "skipped", "reason": skip})
            return rec
        fn, args, in_sh, out_sh = BUILDERS[shape.kind](cfg, shape, mesh)

    # donate the mutable aggregate (train state / decode cache) — realistic
    # steady-state memory, as a real launcher would run it.
    if arch.startswith("alphafold") or shape.kind == "train":
        donate = (0,)
    elif shape.kind == "decode":
        donate = (2,)
    else:
        donate = ()
    with jax.set_mesh(mesh):
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    flops, hbm_bytes = analysis.hlo_cost(hlo)
    coll = analysis.parse_collectives(hlo, mesh.shape["model"])
    # the SPMD HLO is the per-device program: parsed quantities are already
    # per-chip, so the roofline denominator uses 1 chip.
    roof = analysis.Roofline(
        flops=flops, hbm_bytes=hbm_bytes, wire_bytes=coll.wire_bytes,
        chips=1, peak_flops=PEAK_FLOPS_BF16, hbm_bw=HBM_BW, ici_bw=ICI_BW)

    # memory_analysis is per-device under SPMD: live bytes = args (params,
    # optimizer state, caches) + peak temp during execution.
    peak = getattr(mem, "peak_memory_in_bytes", 0) or mem.temp_size_in_bytes
    per_dev_bytes = mem.argument_size_in_bytes + peak
    rec.update({
        "status": "ok",
        "compile_s": round(time.time() - t0, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes": peak,
            "per_device_bytes": per_dev_bytes,
            "fits_16GB": bool(per_dev_bytes <= HBM_BYTES),
        },
        "cost_analysis": {"flops_raw": cost.get("flops", 0.0),
                          "bytes_raw": cost.get("bytes accessed", 0.0)},
        "collectives": {"counts": coll.counts,
                        "payload_bytes": coll.payload_bytes,
                        "wire_bytes": coll.wire_bytes},
        "roofline": roof.as_dict(),
    })
    if cfg is not None:
        from repro.layers.params import count_params
        rec["roofline"]["note"] = ""
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--include-alphafold", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    enable_compilation_cache()

    jobs = []
    if args.all:
        for arch in list_archs():
            for shape in INPUT_SHAPES:
                jobs.append((arch, shape))
        if args.include_alphafold:
            jobs += [("alphafold-initial", "train"),
                     ("alphafold-finetune", "train")]
    else:
        jobs = [(args.arch, args.shape)]

    results = []
    for arch, shape in jobs:
        try:
            rec = run_one(arch, shape, multi_pod=args.multi_pod)
        except Exception as e:  # a failure here is a bug in the system
            rec = {"arch": arch, "shape": shape, "status": "error",
                   "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-2000:]}
        status = rec["status"]
        extra = ""
        if status == "ok":
            r = rec["roofline"]
            extra = (f"bottleneck={r['bottleneck']} "
                     f"tc={r['t_compute_s']:.2e} tm={r['t_memory_s']:.2e} "
                     f"tx={r['t_collective_s']:.2e} "
                     f"fits={rec['memory']['fits_16GB']}")
        elif status == "error":
            extra = rec["error"][:160]
        print(f"[{status:7s}] {arch:24s} {shape:12s} {extra}", flush=True)
        results.append(rec)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print("wrote", args.out)


if __name__ == "__main__":
    main()
