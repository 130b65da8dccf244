"""Correctness of the one-chip cells' checks, at a size a test run holds,
on the CPU: a run with a fault planted under the timed path comes out not
correct under the cell's own limits. The harness's look for a chip is
skipped. The exchange between chips exists only under DAP, so its fault is
held in ``test_bench_dap_faults.py``."""
import pytest

from tiny_cells import broken_fold, broken_train, run_fold, run_train, tiny

from fastbench import faults

CASES = ([pytest.param("af_train_initial", f, id=f)
          for f in sorted(faults.TRAIN)]
         + [pytest.param("af_fold_r256", f, id=f"af_fold_r256-{f}")
            for f in sorted(faults.FOLD) if f != "exchange_left_out"])


@pytest.mark.parametrize("name,fault", CASES)
def test_planted_fault_is_not_correct(name, fault):
    c = tiny(name)
    if c.traffic["mode"] == "train":
        ok, checks = run_train(c, broken_train(faults.TRAIN[fault]))
    else:
        ok, checks = run_fold(c, broken_fold(faults.FOLD[fault]))
    assert not ok, checks
