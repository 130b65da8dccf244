"""Training launcher CLI.

Single-host (real devices):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --reduced \
      --steps 50 --batch 4 --seq 64

On a real TPU pod slice this same entry point builds the production mesh and
pjit-shards per parallel/plan.py (the code path the dry-run certifies).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, list_archs
from repro.data import lm_batches
from repro.exec.plan import PRESETS, preset, use_plan
from repro.launch.cache import enable_compilation_cache
from repro.layers.params import count_params
from repro.models.decoder import init_model, lm_loss
from repro.train.checkpoint import save_checkpoint
from repro.train.loop import instrument_train_step, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale variant (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw", choices=["adamw", "lamb"])
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--plan", default="default", choices=sorted(PRESETS),
                    help="ExecutionPlan preset the run executes under")
    ap.add_argument("--trace", default=None, metavar="EVENTS.jsonl",
                    help="record obs train_step telemetry to this JSONL "
                         "file (inspect with `python -m repro.obs report`)")
    args = ap.parse_args()
    enable_compilation_cache()

    with use_plan(preset(args.plan)):
        if args.trace:
            from repro.obs import use_tracer

            with use_tracer() as tr:
                _run(args)
            n = tr.dump_jsonl(args.trace)
            print(f"wrote {args.trace} ({n} events)")
        else:
            _run(args)


def _run(args):
    cfg = get_config(args.arch, reduced_variant=args.reduced)
    params = init_model(jax.random.PRNGKey(0), cfg)
    print(f"{args.arch} ({'reduced' if args.reduced else 'full'}): "
          f"{count_params(params):,} params on {len(jax.devices())} device(s)")

    init_state, train_step = make_train_step(
        lambda p, b, r: lm_loss(p, b, cfg), optimizer=args.optimizer,
        base_lr=args.lr, warmup_steps=max(5, args.steps // 20),
        total_steps=args.steps, accum_steps=args.accum)
    state = init_state(params)
    step_fn = instrument_train_step(
        jax.jit(train_step), tokens_per_step=args.batch * args.seq)

    gen = lm_batches(vocab=cfg.vocab, batch=args.batch, seq=args.seq, seed=0)
    t0 = time.time()
    for i in range(args.steps):
        lb = next(gen)
        batch = {"tokens": jnp.asarray(lb.tokens),
                 "targets": jnp.asarray(lb.targets),
                 "mask": jnp.asarray(lb.mask)}
        if cfg.modality and cfg.modality.n_prefix_tokens:
            batch["prefix_embeds"] = jnp.zeros(
                (args.batch, cfg.modality.n_prefix_tokens, cfg.d_model),
                jnp.bfloat16)
        state, metrics = step_fn(state, batch, jax.random.PRNGKey(i))
        if (i + 1) % 10 == 0:
            print(f"step {i+1:4d} loss {float(metrics['loss']):.4f} "
                  f"ppl {float(metrics['ppl']):.1f} "
                  f"gnorm {float(metrics['grad_norm']):.2f}")
    print(f"{args.steps} steps in {time.time()-t0:.1f}s")
    if args.ckpt_dir:
        print("saved:", save_checkpoint(args.ckpt_dir, args.steps, state))


if __name__ == "__main__":
    main()
