"""What has to come out as NOT correct, at a size a test run can hold.

The control: the plain reference one precision lower (bfloat16 for these
float32 configurations) put in the program's place has to fail at least one
of the cell's numbers.  The faults: the rest of a run, with the timed path
broken underneath, has to print ``correct: false``.
"""

import pytest

from conftest import CELLS

FAULTS = {
    "glm_hashed_sparse.solve": ["state_unchanged", "half_batch",
                                "answer_altered"],
    "game_fe_re.cd": ["state_unchanged", "half_batch", "answer_altered"],
    # a server has no state to leave unchanged and no batch mean to take
    "game_fe_re.serve_steady": ["answer_altered"],
}


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_a_number(rehearsal, cell):
    result, _ = rehearsal(cell, "--control")
    assert result["correct"] is True
    limits = {k: v["limit"] for k, v in result["compared"].items()}
    beyond = [k for k, v in result["control"].items() if not v <= limits[k]]
    assert beyond, (result["control"], limits)


@pytest.mark.parametrize(
    "cell,fault", [(c, f) for c in CELLS for f in FAULTS[c]])
def test_fault_is_not_correct(rehearsal, cell, fault):
    result, _ = rehearsal(cell, "--fault", fault)
    assert result["correct"] is False
    beyond = [k for k, v in result["compared"].items()
              if not v["value"] <= v["limit"]]
    assert beyond
