"""Serving launcher CLI (reduced configs on CPU; production mesh on TPU).

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-27b --reduced \
      --requests 6 --max-new 12
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, list_archs
from repro.exec.plan import PRESETS, preset
from repro.launch.cache import enable_compilation_cache
from repro.models.decoder import init_model
from repro.serving.engine import ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--plan", default="default", choices=sorted(PRESETS),
                    help="ExecutionPlan preset the engine binds")
    args = ap.parse_args()
    enable_compilation_cache()

    cfg = get_config(args.arch, reduced_variant=args.reduced)
    params = init_model(jax.random.PRNGKey(0), cfg)
    engine = ServingEngine(params, cfg, n_slots=args.slots,
                           max_seq=args.max_seq, plan=preset(args.plan))
    rng = np.random.default_rng(0)
    t0 = time.time()
    for _ in range(args.requests):
        engine.submit(rng.integers(0, cfg.vocab, size=(8,)),
                      max_new_tokens=args.max_new,
                      temperature=args.temperature)
    finished = engine.run()
    dt = time.time() - t0
    toks = sum(len(r.generated) for r in finished)
    print(f"{args.arch}: {len(finished)} requests, {toks} tokens, "
          f"{toks/dt:.1f} tok/s")


if __name__ == "__main__":
    main()
