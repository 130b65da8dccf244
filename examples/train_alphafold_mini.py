"""End-to-end driver: train an AlphaFold-family model on synthetic protein
batches for a few hundred steps, with checkpointing and eval.

  PYTHONPATH=src python examples/train_alphafold_mini.py \
      --steps 300 --config smoke          # ~3 min on CPU
  PYTHONPATH=src python examples/train_alphafold_mini.py --config mini  # bigger

The loss (masked-MSA + distogram + FAPE) decreases measurably within a few
hundred steps because the synthetic family generator has real co-evolution
signal (data/synthetic.py).
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import alphafold as afc
from repro.data import protein_batches
from repro.exec.plan import PRESETS, preset
from repro.exec.session import FastFold
from repro.launch.cache import enable_compilation_cache
from repro.layers.params import count_params
from repro.train.checkpoint import latest_checkpoint, restore_checkpoint, \
    save_checkpoint
from repro.train.loop import make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="smoke", choices=["smoke", "mini"])
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--n-res", type=int, default=16)
    ap.add_argument("--n-seq", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="checkpoints/af_mini")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--plan", default="default", choices=sorted(PRESETS),
                    help="ExecutionPlan preset the session binds")
    args = ap.parse_args()
    enable_compilation_cache()

    cfg = afc.SMOKE if args.config == "smoke" else afc.MINI
    # The FastFold facade binds (config, plan) once: the train-loss closure it
    # hands make_train_step carries the kernel/parallel/memory policy.
    ff = FastFold(cfg, preset(args.plan))
    params = ff.init(jax.random.PRNGKey(0))
    print(f"config={args.config} plan={args.plan} "
          f"params={count_params(params):,}")

    init_state, train_step = make_train_step(
        ff.loss_fn, base_lr=args.lr, warmup_steps=20, total_steps=args.steps)
    state = init_state(params)

    ckpt = latest_checkpoint(args.ckpt_dir)
    if ckpt:
        state = restore_checkpoint(ckpt, state)
        print(f"resumed from {ckpt} at step {int(state.step)}")

    gen = protein_batches(batch=args.batch, n_seq=args.n_seq,
                          n_res=args.n_res, seed=0)
    step_fn = jax.jit(train_step)
    t0 = time.time()
    while int(state.step) < args.steps:
        pb = next(gen)
        batch = {k: jnp.asarray(getattr(pb, k)) for k in
                 ("msa", "msa_mask", "residue_index", "aatype", "seq_mask",
                  "pseudo_beta", "bert_mask", "true_msa")}
        state, metrics = step_fn(state, batch,
                                 jax.random.PRNGKey(int(state.step)))
        s = int(state.step)
        if s % 20 == 0 or s == 1:
            dt = (time.time() - t0) / max(1, s)
            print(f"step {s:4d}  loss {float(metrics['loss']):7.4f}  "
                  f"msa {float(metrics['masked_msa']):6.4f}  "
                  f"dist {float(metrics['distogram']):6.4f}  "
                  f"fape {float(metrics['fape']):6.4f}  "
                  f"({dt*1e3:.0f} ms/step)")
        if s % args.ckpt_every == 0:
            path = save_checkpoint(args.ckpt_dir, s, state)
            print("checkpointed:", path)
    print("done in", round(time.time() - t0, 1), "s")


if __name__ == "__main__":
    main()
