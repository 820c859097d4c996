"""The training cell's check, at a size the CPU runs: a sound run passes;
the fp8 control put in the program's place, a step that returns its state
unchanged, and a step that drops half of the batch each make ``correct``
false."""

import pytest

from chipbench_tiny import run_tiny


def test_sound_run_is_correct():
    res = run_tiny()
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0


@pytest.mark.parametrize("plant", ["control", "unchanged", "half_batch"])
def test_lower_precision_and_broken_steps_are_caught(plant):
    res = run_tiny(plant=plant)
    assert res["correct"] is False, res["checks"]
    if plant == "control":
        # The program's own readings ride beside the control's, and pass.
        assert all(res["check_readings"][f"program_{k}"] <= c["limit"]
                   for k, c in res["checks"].items())
