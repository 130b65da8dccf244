#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, in one process on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 \
        --variants program,control,half_batch

For each seed, each variant's checked units (train steps, or sampled folds
of a short window) are compared with one reference run of that seed, and one
JSON line per (seed, variant) gives the numbers ``check`` computes and
whether they pass the cell's committed limits (``window_compiles`` aside,
which a run of the benchmark counts). Variants:
``program`` (the sound program), ``control`` (the reference in float8 in the
program's place), and the faults of ``fastbench.faults`` planted under the
program. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="program,control")
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)

    import jax

    from fastbench import check, faults, manifest, reference, runtime
    from fastbench.modes import RunContext, fold, train

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 3
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        manifest.CHECKOUT, ".jax_cache")
    from repro.launch.cache import enable_compilation_cache

    enable_compilation_cache()
    cell = manifest.cell(args.workload)
    mode = cell.traffic["mode"]
    fp8 = reference.Numerics("fp8")
    counter = runtime.CompileCounter()
    limits = {k: v for k, v in cell.check["limits"].items()
              if k != "window_compiles"}

    def train_system(variant):
        if variant == "program":
            return train.program_system
        if variant == "control":
            return train.reference_system(fp8)
        fault = faults.TRAIN[variant]

        def system(cfg, dims):
            init_state, step, check_layout = train.program_system(cfg, dims)
            return init_state, fault(step), check_layout
        return system

    def fold_system(variant):
        if variant == "program":
            return fold.program_system
        fault = faults.FOLD[variant]

        def system(cfg, mesh):
            compile_fold, check_layout = fold.program_system(cfg, mesh)
            return fault(compile_fold), check_layout
        return system

    for seed in [int(s) for s in args.seeds.split(",")]:
        got = {}
        for variant in args.variants.split(","):
            t0 = time.perf_counter()
            ctx = RunContext(seed=seed, seconds=args.seconds, trace=False,
                             cell=cell, devices=jax.devices()[:cell.chips],
                             t0=t0, counter=counter)
            try:
                if mode == "train":
                    out, prog, inputs = train.program_phase(
                        ctx, train_system(variant))
                elif variant == "control":
                    out, prog = None, None
                else:
                    out, prog, inputs = fold.program_phase(
                        ctx, fold_system(variant))
            except Exception as e:  # a variant that crashes gives no number
                traceback.print_exc()
                print(json.dumps({"seed": seed, "variant": variant,
                                  "error": repr(e)[:2000]}), flush=True)
                continue
            got[variant] = (out, prog, time.perf_counter() - t0)
        t0 = time.perf_counter()
        if mode == "train":
            ref = train.reference_steps(cell.config, *inputs, reference.FP32)
        else:
            dims, wkey, feed = inputs
            idx = [b for _, prog, _ in got.values() if prog
                   for b, _ in prog]
            ref = fold.reference_folds(dims, wkey, feed, idx, jax.devices()[0],
                                       reference.FP32)
        ref_s = time.perf_counter() - t0
        for variant, (out, prog, phase_s) in got.items():
            if mode == "train":
                numbers = check.train_numbers(prog, ref)
            elif variant == "control":
                numbers = check.worst(list(fold.control_numbers(
                    dims, wkey, feed, ref, jax.devices()[0]).values()))
            else:
                numbers = check.worst([check.fold_numbers(o, ref[b], feed[b])
                                       for b, o in prog])
            ok, _ = check.judge(numbers, limits)
            print(json.dumps({
                "seed": seed, "variant": variant, "numbers": numbers,
                "correct_under_limits": ok,
                "phase_s": phase_s, "reference_s": ref_s,
                "setup_s": out.setup_s if out else None,
                "per_unit_s": out.per_unit_s if out else None,
                "memory_peak_bytes": out.memory_peak_bytes if out else None,
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
