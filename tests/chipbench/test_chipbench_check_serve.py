"""The serving cell's check, at a size the CPU runs: a sound run passes;
the fp8 control put in the program's place, an insert that leaves the
slots' cache unchanged, a prefill of half of each prompt, and a decoded
token altered where it is produced each make ``correct`` false."""

import pytest

from chipbench_tiny import run_tiny, tiny_serve_cell


def test_sound_run_is_correct():
    res = run_tiny(cell=tiny_serve_cell)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 8 and res["failed"] == 0
    assert res["check_readings"]["compared"] >= 8
    assert res["metrics"]["prefill_tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("plant", ["control", "unchanged", "half_batch", "token"])
def test_lower_precision_and_broken_serving_are_caught(plant):
    res = run_tiny(plant=plant, cell=tiny_serve_cell)
    assert res["correct"] is False, res["checks"]
    if plant == "control":
        # The program's own readings ride beside the control's, and pass.
        assert all(res["check_readings"][f"program_{k}"] <= c["limit"]
                   for k, c in res["checks"].items())
