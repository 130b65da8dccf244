#!/usr/bin/env python3
"""The benchmark: one cell of ``BENCHMARK.json``, one run, one result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs only on a TPU with at least the cell's chips; elsewhere it exits 3
with no result line. Set-up (weights from the seed, the feed, compiling
from the persistent cache in ``<checkout>/.jax_cache``, warm-up) is
``setup_s``; the window then runs the cell's units (train steps or folds)
back to back for ``--seconds`` and finishes the one in flight. With
``--trace 1`` the run profiles a short window of its own instead and
reports the cell's per-layer metrics and a ``breakdown`` read from the
device trace. Either way the run ends with the comparison against the plain
reference that decides ``correct``, whose numbers and limits are the last
lines on standard error and the last key (``checks``) of the result line.

Exit codes: 0 a result line was printed (correct or not); 2 bad arguments
or missing benchmark files; 3 no TPU, too few chips or a device without
peaks.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import math  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)


def fail(code: int, msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return code


def e2e_metrics(cell, outcome) -> dict:
    """The cell's end-to-end metrics, each the quantity its metric file
    names."""
    values = {"setup_s": outcome.setup_s, "per_unit_s": outcome.per_unit_s}
    return {name: {"value": values[cell.metric_files[name]["quantity"]],
                   "unit": entry["unit"]}
            for name, entry in cell.end_to_end.items()}


def layer_metrics(cell, ctx) -> dict:
    """The cell's per-layer metrics, each from the reader its metric file
    names; a reader that finds nothing leaves its metric out."""
    out = {}
    for name, entry in cell.per_layer.items():
        spec = cell.metric_files[name]
        reader = importlib.import_module("fastbench.readers." + spec["reader"])
        got = reader.read(ctx, **spec.get("params", {}))
        if got is None:
            print(f"bench: {name}: nothing to read", file=sys.stderr)
            continue
        value, note = got
        print(f"bench: {name} = {value!r} {entry['unit']} {note}".rstrip(),
              file=sys.stderr)
        out[name] = {"value": value, "unit": entry["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from fastbench import manifest

    try:
        cell = manifest.cell(args.workload)
    except (OSError, KeyError, ValueError) as e:
        return fail(2, f"cannot load the benchmark's files: {e!r}")

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return fail(3, f"no TPU: JAX found {len(devices)} {dev.platform!r} "
                       f"device(s); the benchmark runs only on a TPU")
    if len(devices) < cell.chips:
        return fail(3, f"{args.workload} needs {cell.chips} chips, JAX sees "
                       f"{len(devices)}")
    from fastbench import check, peaks, program, runtime
    from fastbench import trace as trace_mod
    from fastbench.modes import RunContext
    from fastbench.readers import Context

    try:
        peak = peaks.peaks(dev.device_kind)
    except KeyError as e:
        return fail(3, str(e))

    if importlib.util.find_spec("repro") is None:
        return fail(2, f"the program is not in {program.SRC}")
    # The persistent compilation cache lives at a fixed path inside the
    # checkout; the program's entry-point helper is handed that path.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        manifest.CHECKOUT, ".jax_cache")
    from repro.launch.cache import enable_compilation_cache

    enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    mode = importlib.import_module("fastbench.modes." + cell.traffic["mode"])
    ctx = RunContext(seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), cell=cell,
                     devices=devices[:cell.chips], t0=T0,
                     counter=runtime.CompileCounter())
    outcome = mode.run(ctx)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips,
              "memory_peak_bytes": outcome.memory_peak_bytes}
    result = {}
    if args.trace:
        try:
            t = trace_mod.load(outcome.trace_file,
                               [d.id for d in ctx.devices])
        finally:
            outcome.cleanup()
        metrics = layer_metrics(cell, Context(
            trace=t, units=outcome.traced_units, chips=cell.chips, peak=peak,
            config=cell.config, shapes=cell.traffic))
        device["busy_s"] = trace_mod.mean_busy_s(t)
        device["window_s"] = t.window_s
        result["breakdown"] = {"device_ops": trace_mod.top_ops(t),
                               "idle_gaps": trace_mod.idle_gaps(t)}
    else:
        metrics = e2e_metrics(cell, outcome)

    correct, checks = check.judge(outcome.numbers, cell.check["limits"])
    correct = correct and outcome.failed == 0
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
        c.update({k: v if math.isfinite(v) else None for k, v in c.items()})
    print(json.dumps({"correct": correct, "attempted": outcome.attempted,
                      "failed": outcome.failed, "metrics": metrics,
                      "device": device, **result, "checks": checks}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
