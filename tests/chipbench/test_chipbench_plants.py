"""A cell refuses a fault that it does not plant, so that ``--plant`` never
runs as a sound run under a fault's name."""

import pytest

from chipbench_tiny import SEED, tiny_cell, tiny_serve_cell
from chipbench import run


@pytest.mark.parametrize("cell,plant", [(tiny_cell, "token"), (tiny_cell, "bogus"),
                                        (tiny_serve_cell, "bogus")])
def test_a_plant_the_cell_does_not_implement_is_refused(cell, plant):
    _, _, conf, mix, _ = cell()
    with pytest.raises(ValueError, match="plants none of"):
        run.make_cell(conf, mix, plant=plant)


def test_an_untraced_serving_run_keeps_the_engines_compiled_decode():
    """The decode log that the readers need wraps the engine's decode call
    in traced runs only."""
    from chipbench import program

    program.import_program()
    _, _, conf, mix, _ = tiny_serve_cell()
    cell, _ = run.make_cell(conf, mix)
    cell.setup(SEED, 1.0)
    counts = cell.engine.compile_counts()
    assert counts["generate"] == 1
    assert cell.decodes == []
