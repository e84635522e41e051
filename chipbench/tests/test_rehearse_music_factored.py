"""The ``game_music_factored.cd`` cell on the CPU (``--rehearse``: rows and
entities from the configuration's ``rehearse``, widths, the latent dimension
and the active cap kept): the last line says ``correct: true``, each of the
three faults says ``correct: false``, the control reads beyond a limit;
``work_factored``'s counts against a two-bucket toy worked by hand; the
seed's B0 is the same point in every seed's coordinates."""

import json
import os

import numpy as np
import pytest

from chipbench import datagen_music_lowrank, work, work_factored, work_multi
from conftest import ROOT

CELL = "game_music_factored.cd"
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def config():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "game_music_factored.json")) as f:
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
        "value_gap", "grad_left_fixed", "grad_left_user", "grad_left_gamma",
        "grad_left_B", "tracker_lanes_missing", "auc_short"}
    assert result["compared"]["tracker_lanes_missing"]["value"] == 0
    # counts only: no time, rate, share or memory reading from a CPU
    for name in result["metrics"]:
        assert name.split(".")[0] in (
            "solver", "dispatch", "compile", "factored"), name
    if trace:
        cfg = config()
        assert result["metrics"]["solver.evals_per_job"]["value"] == (
            cfg["stopping_rule"]["cd_iterations"] * len(cfg["coordinates"]))
        # every solve of B runs its whole outer budget: at least
        # outer + 1 value/gradient passes an inner iteration of an update
        solves = (cfg["stopping_rule"]["cd_iterations"]
                  * cfg["num_inner_iterations"])
        assert result["metrics"]["factored.projection_passes_per_job"][
            "value"] > solves * (cfg["projection_solve"]["max_iters"] + 1)
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
    # 10 rows; a factored effect d 4 -> k 2 over two buckets, 2 lanes x 3
    # slots and 1 lane x 4 slots: 10 padded slots on 3 lanes; one update of
    # two inner iterations: the lanes 2.0 and 1.5 Newton iterations, the B
    # solves (outer 2, cg 3) and (outer 2, cg 5)
    slots, lanes, rows, d, k = 10, 3, 10, 4, 2
    inner = [
        {"lanes": {"solver_iterations": 2.0},
         "projection": {"iterations": 2, "cg_iterations": 3, "passes": 6}},
        {"lanes": {"solver_iterations": 1.5},
         "projection": {"iterations": 2, "cg_iterations": 5, "passes": 8}},
    ]
    counts = {
        "rows": rows,
        "coordinates_work": [
            {"name": "fixed", "kind": "fixed", "dim": 4, "active_slots": 0,
             "entities": 0},
            {"name": "per-song", "kind": "factored", "dim": d,
             "latent_dim": k, "active_slots": slots, "entities": lanes},
        ],
        "solver_work": [("fixed", 2.0, None), ("per-song", 1.5, inner)],
    }
    j = work_factored.job(counts)
    objective = {"flops": rows * (12 + 2), "bytes": rows * 3 * 4}
    newton = {"flops": slots * (2 * k + 2 * k + 2 * k * k + 12),
              "bytes": slots * k * 4 + slots * 3 * 4}
    song = {
        "flops": (
            2 * 2 * slots * d * k  # two projections
            + 3.5 * newton["flops"]  # 2.0 + 1.5 Newton iterations
            + (4 * 3 + 6 * 3) * slots * d * k  # B solve 1: 3 v/g, 3 HVPs
            + (4 * 3 + 6 * 5) * slots * d * k  # B solve 2: 3 v/g, 5 HVPs
            + rows * (2 * d * k + 2 * k)  # rescore
            + objective["flops"]),
        "bytes": (
            slots * (4 + 8)  # offsets in, once
            + 2 * slots * (d + k) * 4  # two projections
            + 3.5 * newton["bytes"]
            + 2 * 3 * lanes * k * 4  # gamma rows: out, back, out again
            + (6 + 8) * slots * (d + k + 4) * 4  # 14 passes of the B solves
            + rows * (d * 4 + 4 + k * 4 + 4)  # rescore
            + objective["bytes"]),
        "projection_passes": 14,
    }
    assert j["by_coordinate"]["per-song"] == song
    # a fixed or plain coordinate is work_multi's count, formula for formula
    fixed = work_multi.update(counts["coordinates_work"][0], rows, 2.0, 2)
    assert j["by_coordinate"]["fixed"] == {**fixed, "projection_passes": 0}
    assert j["flops"] == song["flops"] + fixed["flops"]
    assert j["bytes"] == song["bytes"] + fixed["bytes"]
    assert j["projection_passes"] == 14
    assert work_factored.game_user_newton_iter is work.game_user_newton_iter
    assert work_factored.job({"rows": 10}) is None


def test_every_seed_starts_from_the_same_projection():
    """x_seed @ B0_seed == x @ B0: the seed's signed permutation of the song
    features carried into B0."""
    from chipbench.datagen import signed_permutation

    cfg = config()
    d = cfg["song_dim"]
    x = np.random.default_rng(0).normal(size=(5, d)).astype(np.float32)
    want = None
    for seed in (1, 2**31 + 11):
        perm, sign = signed_permutation(seed, d, 3)
        got = (x[:, perm] * sign) @ datagen_music_lowrank.initial_projection(
            cfg, seed)
        want = got if want is None else want
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    b0 = datagen_music_lowrank.initial_projection(cfg, 1)
    assert b0.shape == (d, cfg["latent_dim"]) and b0.dtype == np.float32
