"""The training cell's check on the sound program and on its control, at a
size a test run holds, on the CPU. The sound program comes out correct
under the cell's own limits. The float8 control in the program's place
reads a loss gap at least three times the program's: the separation that
its full-size readings on the chip (PERF.md) turn into a failed check. At
this depth (2 blocks, not 48) the control's gap stays under the full-size
limit, so the test holds it to the separation and not to the limit."""
import pytest

from tiny_cells import run_train, tiny

from fastbench import reference
from fastbench.modes import train


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
