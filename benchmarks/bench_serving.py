"""Measured serving benchmark: a mixed-length synthetic trace through the
instrumented ServingEngine, per ExecutionPlan preset — the first *measured*
(not static) perf-trajectory artifact.

For each preset the driver scopes a fresh obs tracer, submits a seeded
mixed-length prompt trace (lengths drawn across [4, max_seq/2] so prefill
cost and slot turnover actually vary), drains the engine, and aggregates
the event stream with ``repro.obs.report``. The checked-in
``BENCH_serving.json`` rows are keyed by the row's full serialized
ExecutionPlan (``plan.to_dict()`` — never the process-salted hash) and
carry the measured p50/p95/p99 queued->done latency, tokens/sec, mean slot
occupancy and jit-entry census. ``python -m repro.obs report --bench`` (CI
leg 8) schema-validates both the JSONL stream and this payload.

Smoke mode (``--smoke``) shrinks the trace for the CI gate; the artifact
records which mode produced it so trend tooling never compares smoke
against full rows.

Usage:
    python benchmarks/bench_serving.py --smoke --out BENCH_serving.json \
        --events-out /tmp/obs_serving.jsonl
"""
import argparse
import json
import time

import numpy as np


def make_trace(n_requests: int, max_seq: int, seed: int) -> list:
    """Seeded mixed-length synthetic prompts (vocab ids below 500 like the
    resilience harness; lengths spread over [4, max_seq // 2])."""
    rng = np.random.default_rng(seed)
    lengths = rng.integers(4, max(5, max_seq // 2 + 1), size=n_requests)
    return [rng.integers(0, 500, size=(int(n),)) for n in lengths]


def bench_preset(name, plan, params, cfg, prompts, *, n_slots, max_seq,
                 max_new):
    from repro.obs import aggregate, use_tracer
    from repro.serving.engine import ServingEngine

    with use_tracer() as tr:
        eng = ServingEngine(params, cfg, n_slots=n_slots, max_seq=max_seq,
                            plan=plan)
        t0 = time.perf_counter()
        for p in prompts:
            eng.submit(p, max_new_tokens=max_new)
        finished = eng.run()
        wall_s = time.perf_counter() - t0

    assert all(r.status == "done" for r in finished), \
        f"bench preset {name}: not every request finished clean"
    events = tr.events_resolved()
    agg = aggregate(events)
    tokens = agg["counters"].get("tokens", 0.0)
    occ = agg["gauges"].get("occupancy", {})
    row = {
        "preset": name,
        "plan": plan.to_dict(),
        "requests": len(prompts),
        "tokens": tokens,
        "wall_s": round(wall_s, 4),
        "tokens_per_s": round(tokens / wall_s, 2) if wall_s else 0.0,
        "latency_ms": {k: round(v, 3) for k, v in
                       agg["requests"]["latency_ms"].items()},
        "occupancy_mean": round(occ.get("mean", 0.0), 3),
        "occupancy_hist": occ.get("hist", {}),
        "jit_entries": agg["jit"],
    }
    return row, tr


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--smoke", action="store_true",
                        help="CI-sized trace (fast, artifact marked smoke)")
    parser.add_argument("--requests", type=int, default=None,
                        help="trace length (default 16, smoke 6)")
    parser.add_argument("--slots", type=int, default=2)
    parser.add_argument("--max-seq", type=int, default=24)
    parser.add_argument("--max-new", type=int, default=None,
                        help="tokens per request (default 8, smoke 3)")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--presets", default="default,oracle",
                        help="comma-separated ExecutionPlan preset names")
    parser.add_argument("--out", default="BENCH_serving.json")
    parser.add_argument("--events-out", default=None,
                        help="also dump the last preset's JSONL stream here")
    args = parser.parse_args(argv)

    n_requests = args.requests or (6 if args.smoke else 16)
    max_new = args.max_new or (3 if args.smoke else 8)

    import jax

    from repro.configs import get_config
    from repro.exec.plan import preset
    from repro.models.decoder import init_model
    from repro.obs.report import BENCH_SCHEMA_VERSION, validate_bench

    cfg = get_config("qwen2-1.5b", reduced_variant=True)
    params = init_model(jax.random.PRNGKey(0), cfg)
    prompts = make_trace(n_requests, args.max_seq, args.seed)

    rows, last_tracer = [], None
    for name in args.presets.split(","):
        name = name.strip()
        row, last_tracer = bench_preset(
            name, preset(name), params, cfg, prompts, n_slots=args.slots,
            max_seq=args.max_seq, max_new=max_new)
        rows.append(row)
        lat = row["latency_ms"]
        print(f"{name:16s} {row['tokens']:.0f} tok in {row['wall_s']:.2f}s "
              f"({row['tokens_per_s']:.1f} tok/s)  latency p50/p95/p99 "
              f"{lat['p50']:.1f}/{lat['p95']:.1f}/{lat['p99']:.1f} ms  "
              f"occupancy {row['occupancy_mean']:.2f}")

    payload = {
        "schema": BENCH_SCHEMA_VERSION,
        "smoke": bool(args.smoke),
        "model": cfg.name,
        "n_slots": args.slots,
        "max_seq": args.max_seq,
        "max_new_tokens": max_new,
        "requests": n_requests,
        "seed": args.seed,
        "rows": rows,
    }
    problems = validate_bench(payload)
    assert not problems, f"self-check failed: {problems}"
    with open(args.out, "w", encoding="utf-8") as fh:
        json.dump(payload, fh, indent=2, sort_keys=True)
        fh.write("\n")
    print(f"wrote {args.out} ({len(rows)} row(s))")

    if args.events_out:
        n = last_tracer.dump_jsonl(args.events_out)
        print(f"wrote {args.events_out} ({n} events)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
