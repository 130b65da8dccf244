"""Correctness of the training cell's check, at a size a test run holds, on
the CPU: a run with a fault planted under the timed path comes out not
correct under the cell's own limits. The harness's look for a chip is
skipped."""
import pytest

from tiny_cells import broken_train, run_train, tiny

from fastbench import faults


@pytest.fixture(scope="module")
def cell():
    return tiny("af_train_initial")


@pytest.mark.parametrize("fault", sorted(faults.TRAIN))
def test_planted_fault_is_not_correct(cell, fault):
    ok, checks = run_train(cell, broken_train(faults.TRAIN[fault]))
    assert not ok, checks
