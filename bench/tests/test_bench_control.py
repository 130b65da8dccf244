"""The one-chip cells' checks on the sound program and on its control, at a
size a test run holds, on the CPU. The sound program comes out correct
under the cell's own limits. The float8 control in the program's place
reads at least three times the program's gap (the loss gap of a train step,
one of the gaps of a fold): the separation that its full-size readings on
the chip (PERF.md) turn into a failed check. At this depth (2 blocks, not
48) the control's gaps need not reach the full-size limits, so the test
holds it to the separation and not to the limit."""
import pytest

from tiny_cells import control_fold, run_fold, run_train, tiny

from fastbench import reference
from fastbench.modes import fold, train


@pytest.fixture(scope="module")
def cell():
    return tiny("af_train_initial")


@pytest.fixture(scope="module")
def sound(cell):
    return run_train(cell, train.program_system)


def test_sound_program_is_correct(sound):
    ok, checks = sound
    assert ok, checks


def test_float8_control_separates_from_the_program(cell, sound):
    _, control = run_train(cell,
                           train.reference_system(reference.Numerics("fp8")))
    assert control["loss_gap"]["value"] >= 3 * sound[1]["loss_gap"]["value"]


@pytest.fixture(scope="module")
def fold_cell():
    return tiny("af_fold_r256")


@pytest.fixture(scope="module")
def fold_sound(fold_cell):
    return run_fold(fold_cell, fold.program_system)


def test_sound_fold_is_correct(fold_sound):
    ok, checks = fold_sound
    assert ok, checks


def test_fold_float8_control_separates_from_the_program(fold_cell,
                                                        fold_sound):
    control = control_fold(fold_cell)
    sound = fold_sound[1]
    assert any(v >= 3 * sound[k]["value"] for k, v in control.items()), \
        (control, sound)
