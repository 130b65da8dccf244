"""The harness's fold mode under DAP, at a size a test run holds, on four
virtual CPU devices in a child process: the benchmark's fold cell
``af_fold_r256`` run through the DAP mechanism (``dap`` 4 on four devices)
under that cell's limits. The sound fold comes out correct; a fold with the
exchange between chips left out, and a fold whose answer is altered where
it is produced, come out not correct; and each of them, and the float8
control, reads at least three times the sound fold's gap on one of the
compared numbers. The fold runs 8 blocks, not TINY's 2: with the exchange
left out the distogram gap grows with depth (here 0.14 to 0.22 at 2 blocks
and 0.32 to 0.39 at 8) and at 2 it can stay under the limit. At this depth
the control's gaps stay under the full-size limits, so the control is held
to the separation alone."""
import json
import os
import subprocess
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))

CHILD = r'''
import json, sys
sys.path.insert(0, sys.argv[1])
import dataclasses
from tiny_cells import broken_fold, control_fold, run_fold, tiny
from fastbench import faults
from fastbench.modes import fold

one = tiny("af_fold_r256")
cell = dataclasses.replace(one, chips=4, config=dict(one.config, n_blocks=8),
                           traffic=dict(one.traffic, dap=4))
result = {}
for name in ["program"] + sorted(faults.FOLD):
    system = (fold.program_system if name == "program"
              else broken_fold(faults.FOLD[name]))
    ok, checks = run_fold(cell, system)
    result[name] = {"correct": ok,
                    "gaps": {k: c["value"] for k, c in checks.items()
                             if k != "window_compiles"}}
result["control"] = {"gaps": control_fold(cell)}
print(json.dumps(result))
'''


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", CHILD, TESTS], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_dap_fold_follows_the_reference(runs):
    # bf16 against the float32 reference at 8 blocks and 1 recycle
    sound = runs["program"]
    assert sound["correct"], sound
    assert all(v < 0.05 for v in sound["gaps"].values()), sound


@pytest.mark.parametrize("fault", ["altered_coords", "exchange_left_out"])
def test_planted_fault_is_not_correct(runs, fault):
    assert not runs[fault]["correct"], runs[fault]


@pytest.mark.parametrize("fault", ["altered_coords", "exchange_left_out",
                                   "control"])
def test_fault_separates_from_the_sound_fold(runs, fault):
    sound = runs["program"]["gaps"]
    assert any(runs[fault]["gaps"][k] >= 3 * v for k, v in sound.items()), \
        (fault, runs)
