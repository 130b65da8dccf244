"""Kernel-family A/B of the full-width fold on one TPU.

    python3 benchmarks/ab_fold.py

Folds ``configs.alphafold.FULL`` (3 recycles) on ``chip_smoke.py``'s batch
(n_res 256, n_seq 128, batch 1) under plans that move one kernel family at
a time from its Pallas leg to its XLA leg, then under the oracle preset.
The step between two rows is what that family's Pallas kernels cost or
save in the fold. Prints one ``AB <plan>:`` line per plan: compile seconds,
three fold seconds (``block_until_ready``) and the program's
``tpu_custom_call`` count. Exits non-zero off a TPU.
"""
from __future__ import annotations

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

RUNS = 3


def plans() -> dict:
    from repro.exec.plan import ExecutionPlan, KernelPolicy, preset

    rows = dict(layer_norm="xla", elementwise="xla")
    pair = dict(rows, triangle="xla", opm="xla")
    return {
        "default": ExecutionPlan(),
        "rows_xla": ExecutionPlan(kernels=KernelPolicy(**rows)),
        "rows_tri_opm_xla": ExecutionPlan(kernels=KernelPolicy(**pair)),
        "all_xla": ExecutionPlan(kernels=KernelPolicy(**pair,
                                                      attention="xla")),
        "oracle": preset("oracle"),
    }


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"ab_fold: no TPU: JAX found platform {dev.platform!r}",
              file=sys.stderr)
        return 2
    import chip_smoke as cs
    from repro.configs.alphafold import FULL
    from repro.exec.plan import ExecutionPlan
    from repro.exec.session import FastFold
    from repro.launch.cache import enable_compilation_cache

    enable_compilation_cache()
    print(f"device {dev.device_kind}", flush=True)
    ff = FastFold(FULL, ExecutionPlan())
    params = cs.init_params(ff)
    batch = cs.make_batch()
    for name, plan in plans().items():
        t0 = time.perf_counter()
        compiled = ff.lower("forward", params, batch, plan=plan).compile()
        compile_s = time.perf_counter() - t0
        times = [cs.timed(compiled, params, batch, None)[1]
                 for _ in range(RUNS)]
        calls = sum(cs.kernel_census(compiled).values())
        print(f"AB {name}: compile_s={compile_s:.2f} fold_s="
              f"{' '.join(f'{t:.4f}' for t in times)} custom_calls={calls}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
