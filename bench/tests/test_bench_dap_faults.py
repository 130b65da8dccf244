"""The harness's fold mode under DAP, at a size a test run holds, on four
virtual CPU devices in a child process. The DAP cell is not in
``BENCHMARK.json`` yet (its limits need readings on four chips), so the
cell is built from its configuration and traffic files, and each fault is
held to its separation from the sound program: a fold with the exchange
between chips left out, a fold whose answer is altered where it is
produced, and the float8 control each read at least three times the sound
program's gap on one of the compared numbers."""
import json
import os
import subprocess
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))

CHILD = r'''
import json, sys, time
sys.path.insert(0, sys.argv[1])
import jax
from tiny_cells import tiny_from_files
from fastbench import check, faults, reference, runtime
from fastbench.modes import RunContext, fold

cell = tiny_from_files("af_fold_dap4_r512", "af2_infer_dap",
                       "fold_dap4_r512_msa128", chips=4)
result = {}
for name in ["program"] + sorted(faults.FOLD):
    def system(cfg, mesh, name=name):
        compile_fold, check_layout = fold.program_system(cfg, mesh)
        if name != "program":
            compile_fold = faults.FOLD[name](compile_fold)
        return compile_fold, check_layout
    ctx = RunContext(seed=3000000013, seconds=0.3, trace=False, cell=cell,
                     devices=jax.devices(), t0=time.perf_counter(),
                     counter=runtime.CompileCounter())
    out, checked, (dims, wkey, feed) = fold.program_phase(ctx, system)
    idx = [b for b, _ in checked]
    ref = fold.reference_folds(dims, wkey, feed, idx, jax.devices()[0],
                               reference.FP32)
    result[name] = check.worst([check.fold_numbers(o, ref[b], feed[b])
                                for b, o in checked])
ctl = fold.reference_folds(dims, wkey, feed, idx, jax.devices()[0],
                           reference.Numerics("fp8"))
result["control"] = check.worst([check.fold_numbers(ctl[b], ref[b], feed[b])
                                 for b in idx])
print(json.dumps(result))
'''


@pytest.fixture(scope="module")
def gaps():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", CHILD, TESTS], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_dap_fold_follows_the_reference(gaps):
    # bf16 against the float32 reference at 2 blocks and 1 recycle
    assert all(v < 0.05 for v in gaps["program"].values()), gaps["program"]


@pytest.mark.parametrize("fault", ["altered_coords", "exchange_left_out",
                                   "control"])
def test_fault_separates_from_the_sound_fold(gaps, fault):
    assert any(gaps[fault][k] >= 3 * v for k, v in gaps["program"].items()), \
        (fault, gaps)
