"""The ``game_music_2re.cd`` cell on the CPU (``--rehearse``: rows and
entities from the configuration's ``rehearse``, widths and the active cap
kept): the last line says ``correct: true``, each of the three faults says
``correct: false``, the control reads beyond a limit; ``work_multi``'s
counts against a shape worked by hand; the benchmark's reference against
the sample rule it is handed."""

import json
import os

import numpy as np
import pytest

from chipbench import work, work_multi
from conftest import ROOT

CELL = "game_music_2re.cd"
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_last_line(rehearsal, trace):
    result, proc = rehearsal(CELL, trace=trace)
    assert KEYS <= set(result)
    assert list(result)[-1] == "compared"
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is True and result["attempted"] > 0
    assert result["failed"] == 0
    assert set(result["compared"]) == {
        "value_gap", "grad_left_fixed", "grad_left_user", "grad_left_song",
        "auc_short"}
    # counts only: no time, rate, share or memory reading from a CPU
    for name in result["metrics"]:
        assert name.split(".")[0] in ("solver", "dispatch", "compile"), name
    if trace:
        with open(os.path.join(ROOT, "chipbench", "configs",
                               "game_music_2re.json")) as f:
            cfg = json.load(f)
        # CD iterations x coordinates, from the configuration's own list
        assert result["metrics"]["solver.evals_per_job"]["value"] == (
            cfg["stopping_rule"]["cd_iterations"] * len(cfg["coordinates"]))
    assert "busy_s" not in result["device"]
    # both tables hold entities past the cap, so both have passive rows
    assert "bucketed_design_host" in result["phases_s"]
    assert "sample_check" in result["phases_s"]


def test_control_fails_a_number(rehearsal):
    result, _ = rehearsal(CELL, "--control")
    assert result["correct"] is True
    limits = {k: v["limit"] for k, v in result["compared"].items()}
    beyond = [k for k, v in result["control"].items() if not v <= limits[k]]
    assert beyond, (result["control"], limits)


@pytest.mark.parametrize(
    "fault", ["state_unchanged", "half_batch", "answer_altered"])
def test_fault_is_not_correct(rehearsal, fault):
    result, _ = rehearsal(CELL, "--fault", fault)
    assert result["correct"] is False
    beyond = [k for k, v in result["compared"].items()
              if not v["value"] <= v["limit"]]
    assert beyond


def test_work_by_hand():
    # 10 rows; a fixed effect d 4; one random effect d 2 over 6 padded
    # slots on 3 lanes; 2 Newton iterations an update, one CD iteration
    counts = {
        "rows": 10,
        "coordinates_work": [
            {"name": "fixed", "kind": "fixed", "dim": 4, "active_slots": 0,
             "entities": 0},
            {"name": "per-user", "kind": "random", "dim": 2,
             "active_slots": 6, "entities": 3},
        ],
        "solver_iterations": [("fixed", 2.0), ("per-user", 2.0)],
    }
    j = work_multi.job(counts)
    objective = {"flops": 10 * (12 + 2), "bytes": 10 * 3 * 4}
    fixed = {
        "flops": 2 * 10 * (8 + 8 + 32 + 12) + 10 * 2 * 4
        + objective["flops"],
        "bytes": 2 * (10 * 4 * 4 + 10 * 3 * 4) + 10 * (4 * 4 + 4)
        + objective["bytes"],
    }
    user = {
        "flops": 2 * 6 * (4 + 4 + 8 + 12) + 10 * 2 * 2 + objective["flops"],
        "bytes": 2 * (6 * 2 * 4 + 6 * 3 * 4)  # two Newton iterations
        + 6 * (4 + 8) + 2 * 3 * 2 * 4  # offsets in, table rows in and out
        + 10 * (2 * 4 + 4 + 4 + 2 * 4)  # rescore
        + objective["bytes"],
    }
    assert j["by_coordinate"] == {"fixed": fixed, "per-user": user}
    assert j["flops"] == fixed["flops"] + user["flops"]
    assert j["bytes"] == fixed["bytes"] + user["bytes"]
    # the per-iteration formulas are work.py's own
    assert work_multi.game_fixed_newton_iter is work.game_fixed_newton_iter
    assert work_multi.job({"rows": 10}) is None


def test_sample_check_refuses_a_design_that_breaks_the_rule():
    from types import SimpleNamespace

    from chipbench.tasks import game_cd_multi as task

    ids = np.array([0, 0, 0, 0, 1, 1], np.int32)

    def design(row_index, weights):
        bucket = SimpleNamespace(row_index=np.array(row_index),
                                 weights=np.array(weights, np.float32))
        return SimpleNamespace(buckets=[bucket], num_entities=2,
                               entity_index=[np.array([0, 1], np.int32)])

    good = design([[0, 2], [4, 5]], [[2.0, 2.0], [1.0, 1.0]])
    np.testing.assert_array_equal(
        task.active_sample(good, ids, 2, "t"), [2, 0, 2, 0, 1, 1])
    for bad in (
        design([[0, 4], [2, 5]], [[2.0, 2.0], [1.0, 1.0]]),  # another's row
        design([[0, -1], [4, 5]], [[2.0, 0.0], [1.0, 1.0]]),  # under the cap
        design([[0, 2], [4, 5]], [[1.0, 1.0], [1.0, 1.0]]),  # not count / cap
        design([[0, 0], [4, 5]], [[2.0, 2.0], [1.0, 1.0]]),  # held twice
    ):
        with pytest.raises(task.SampleBreaksTheRule):
            task.active_sample(bad, ids, 2, "t")
