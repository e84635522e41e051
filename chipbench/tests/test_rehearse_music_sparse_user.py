"""The ``game_music_sparse_user.cd`` cell on the CPU (``--rehearse``: rows,
users and items from the configuration's ``rehearse``; the bag's slots, the
widths and the active cap kept): the last line says ``correct: true``, each
of the three faults says ``correct: false``, the control reads beyond a
limit; ``work_sparse_user``'s counts against a two-bucket toy worked by hand;
the item hierarchy in the source's counts, the same on every seed."""

import json
import os

import numpy as np
import pytest

from chipbench import datagen_music_hierarchy, work_multi, work_sparse_user
from conftest import ROOT

CELL = "game_music_sparse_user.cd"
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def config():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "game_music_sparse_user.json")) as f:
        return json.load(f)


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
        "union_columns_missing", "auc_short"}
    assert result["compared"]["union_columns_missing"]["value"] == 0
    # counts only: no time, rate, share or memory reading from a CPU
    for name in result["metrics"]:
        assert name.split(".")[0] in (
            "solver", "dispatch", "compile", "sparse_re"), name
    if trace:
        cfg = config()
        stop, solve = cfg["stopping_rule"], cfg["user_solve"]
        assert result["metrics"]["solver.evals_per_job"]["value"] == (
            stop["cd_iterations"] * len(cfg["coordinates"]))
        # under tolerance 0 every bucket's solve runs its whole budget
        assert result["metrics"]["sparse_re.passes_per_job"]["value"] == (
            stop["cd_iterations"] * cfg["num_buckets"]
            * (solve["max_iters"] * (1 + solve["max_cg"]) + 1))
    assert "busy_s" not in result["device"]
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
    # 10 rows; a sparse effect over two buckets: 2 lanes of width 128 with
    # 6 held rows holding 20 stored entries, 1 lane of width 256 with 4
    # held rows holding 9; 37 and 21 bucket passes in one update
    rows = 10
    coord = {"name": "per-user", "kind": "sparse", "active_slots": 10,
             "entities": 3, "lanes": [2, 1], "widths": [128, 256],
             "rows_by_bucket": [6, 4], "stored_by_bucket": [20, 9],
             "table": 512, "row_slots_stored": 30}
    counts = {
        "rows": rows,
        "coordinates_work": [
            {"name": "fixed", "kind": "fixed", "dim": 4, "active_slots": 0,
             "entities": 0},
            coord,
        ],
        "solver_work": [("fixed", 2.0, None),
                        ("per-user", 3.0, [{"sparse_re": {
                            "passes": [37, 21]}}])],
    }
    j = work_sparse_user.job(counts)
    vectors = 8  # LANE_VECTORS_A_PASS

    def bucket(r, s, k):
        return {"flops": 4 * s + 12 * r + 2 * vectors * k,
                "bytes": 2 * s * 8 + 4 * r * 4 + vectors * k * 4}

    b1, b2 = bucket(6, 20, 2 * 128), bucket(4, 9, 256)
    objective = {"flops": rows * (12 + 2), "bytes": rows * 3 * 4}
    user = {
        "flops": 37 * b1["flops"] + 21 * b2["flops"] + 2 * 30
        + objective["flops"],
        "bytes": 37 * b1["bytes"] + 21 * b2["bytes"]
        + 10 * 12  # offsets into the held rows
        + 2 * 512 * 4  # the flat table read and written
        + 30 * 12 + rows * 4  # the rescore
        + objective["bytes"],
        "sparse_passes": 58,
    }
    assert j["by_coordinate"]["per-user"] == user
    fixed = work_multi.update(counts["coordinates_work"][0], rows, 2.0, 2)
    assert j["by_coordinate"]["fixed"] == {**fixed, "sparse_passes": 0}
    assert j["sparse_passes"] == 58
    assert work_sparse_user.job({"rows": 10}) is None


def test_the_hierarchy_is_the_sources_and_the_seeds_alike():
    cfg = config()
    counts = datagen_music_hierarchy.kind_counts(cfg, cfg["num_songs"])
    assert counts.tolist() == [cfg["hierarchy"][k] for k in
                               datagen_music_hierarchy.KINDS]
    bag, kind = datagen_music_hierarchy.hierarchy(cfg, 2048)
    assert bag.shape == (2048, datagen_music_hierarchy.SLOTS)
    # every item holds itself; a track its album and that album's artist;
    # an artist and a genre nothing more; no item holds one id twice
    assert np.array_equal(bag[:, 0], np.arange(2048))
    tracks = np.flatnonzero(kind == 0)
    assert np.all(kind[bag[tracks, 1]] == 1)
    assert np.array_equal(bag[tracks, 2], bag[bag[tracks, 1], 1])
    assert np.all(bag[kind >= 2, 1:] == -1)
    for row in bag:
        held = row[row >= 0]
        assert held.size == np.unique(held).size
    again, _ = datagen_music_hierarchy.hierarchy(cfg, 2048)
    assert again is bag or np.array_equal(again, bag)
