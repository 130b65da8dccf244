"""§Perf hillclimb driver: run named optimization variants of the three chosen
(arch x shape) pairs through the dry-run and print before/after roofline terms.

  PYTHONPATH=src python -m benchmarks.hillclimb --pair yi_train
  PYTHONPATH=src python -m benchmarks.hillclimb --all --out hillclimb.json

Each variant is a hypothesis from EXPERIMENTS.md §Perf; the log there records
predicted vs measured deltas.
"""
import argparse
import json
import subprocess
import sys
import os

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# (pair name) -> (arch, shape, [(variant_name, overrides dict)])
PAIRS = {
    # 1. worst roofline fraction (memory-dominated dense train)
    "yi_train": ("yi-9b", "train_4k", [
        ("baseline", {}),
        ("qfull", {"attn_q_block": 0}),
        ("qfull_gatherkv", {"attn_q_block": 0, "gather_kv": True}),
        ("qfull_gatherkv_kv4k", {"attn_q_block": 0, "gather_kv": True,
                                 "attn_kv_block": 4096}),
    ]),
    # 2. most collective-bound (decode against a sharded cache)
    "qwen2_decode": ("qwen2-1.5b", "decode_32k", [
        ("baseline", {}),
        ("int8kv", {"kv_cache_int8": True}),
    ]),
    # 3. most representative of the paper's technique (MoE+MLA: EP all_to_all
    #    + DAP sequence sharding; the deepseek train step is where expert
    #    dispatch, MLA gathers and DAP interact)
    "deepseek_train": ("deepseek-v2-236b", "train_4k", [
        ("baseline", {}),
        ("qfull_gatherkv", {"attn_q_block": 0, "gather_kv": True}),
        ("qfull_gatherkv_bf16opt", {"attn_q_block": 0, "gather_kv": True,
                                    "opt_state_bf16": True}),
    ]),
    # memory-fit extensions for the two baseline non-fits (beyond the 3
    # hillclimb pairs — recorded in EXPERIMENTS.md §Perf as fit fixes)
    "qwen15_decode_fit": ("qwen1.5-32b", "decode_32k", [
        ("baseline", {}),
        ("int8kv", {"kv_cache_int8": True}),
    ]),
    # second-round variants (hypotheses from round 1 — see EXPERIMENTS §Perf)
    "qwen2_decode_r2": ("qwen2-1.5b", "decode_32k", [
        ("int8kv_repparams", {"kv_cache_int8": True,
                              "serve_replicate_params": True}),
    ]),
    # the paper's own model: remat-policy trade (recompute vs memory)
    "alphafold_ft": ("alphafold-finetune", "train", [
        ("baseline", {}),
        ("remat_dots", {"remat_policy": "dots"}),
    ]),
    # round 3: MLA keeps its latent (no materialized-KV gather) + bf16 moments
    "deepseek_train_r3": ("deepseek-v2-236b", "train_4k", [
        ("bf16opt", {"opt_state_bf16": True}),
    ]),
    # alphafold round 2: chunked Outer-Product-Mean (j-chunks of 64)
    "alphafold_ft_r2": ("alphafold-finetune", "train", [
        ("opm_chunk64", {"opm_chunk": 64}),
    ]),
    # measure the now-default flash/SWA custom VJPs on the windowed dense arch
    # (baseline = pre-VJP numbers in dryrun_single_pod.json)
    "gemma3_train_vjp": ("gemma3-27b", "train_4k", [
        ("flash_swa_vjp_defaults", {}),
    ]),
}

RUN_ONE = r"""
# dryrun sets the 512-device XLA flag (via exec/envcompat) before jax init;
# the materialized-path baseline runs under a use_plan("oracle") scope.
import json, sys
from repro.launch import dryrun
from repro.exec.plan import preset, use_plan
with use_plan(preset("oracle")):
    rec = dryrun.run_one({arch!r}, {shape!r}, overrides={overrides!r})
print("JSON::" + json.dumps(rec))
"""


def run_variant(arch, shape, overrides):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"   # a CPU-device model, never the TPU
    out = subprocess.run(
        [sys.executable, "-c",
         RUN_ONE.format(arch=arch, shape=shape, overrides=overrides)],
        env=env, capture_output=True, text=True, timeout=3600)
    for ln in out.stdout.splitlines():
        if ln.startswith("JSON::"):
            return json.loads(ln[6:])
    return {"status": "error", "error": out.stderr[-500:]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pair", choices=list(PAIRS), default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    names = list(PAIRS) if args.all else [args.pair]
    results = {}
    for name in names:
        arch, shape, variants = PAIRS[name]
        results[name] = []
        base = None
        for vname, ov in variants:
            rec = run_variant(arch, shape, ov)
            rec["variant"] = vname
            results[name].append(rec)
            if rec["status"] != "ok":
                print(f"{name}/{vname}: {rec['status']} "
                      f"{rec.get('error','')[:200]}", flush=True)
                continue
            r = rec["roofline"]
            if vname == "baseline":
                base = r
            delta = ""
            if base is not None and vname != "baseline":
                dom = base["bottleneck"]
                key = {"compute": "t_compute_s", "memory": "t_memory_s",
                       "collective": "t_collective_s"}[dom]
                delta = (f" | dominant({dom}) {base[key]:.3g} -> {r[key]:.3g} "
                         f"({(1 - r[key] / base[key]) * 100:+.1f}%)")
            print(f"{name}/{vname}: tc={r['t_compute_s']:.3g} "
                  f"tm={r['t_memory_s']:.3g} tx={r['t_collective_s']:.3g} "
                  f"bneck={r['bottleneck']}"
                  f" mem={rec['memory']['per_device_bytes']/2**30:.2f}GB"
                  f" fits={rec['memory']['fits_16GB']}{delta}", flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
