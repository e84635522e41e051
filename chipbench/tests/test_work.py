"""work.py's counts against shapes worked by hand."""

import pytest

from chipbench import work

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_glm_eval_by_hand():
    # 4 rows x 3 slots into 10 coefficients
    w = work.glm_eval(rows=4, slots=3, d=10)
    assert w["flops"] == 4 * 12 + 12 * 4
    assert w["bytes"] == 12 * 8 + 4 * 3 * 4 + 2 * 10 * 4


def test_game_iterations_by_hand():
    f = work.game_fixed_newton_iter(rows=10, d=4)
    assert f["flops"] == 10 * (8 + 8 + 32 + 12)
    assert f["bytes"] == 10 * 4 * 4 + 10 * 3 * 4
    u = work.game_user_newton_iter(active_slots=6, d=2)
    assert u["flops"] == 6 * (4 + 4 + 8 + 12)
    assert u["bytes"] == 6 * 2 * 4 + 6 * 3 * 4


def test_serve_row_by_hand():
    r = work.serve_row(d_fixed=64, d_user=16)
    assert r["flops"] == 160
    assert r["bytes"] == 80 * 4 + 16 * 8 + 8


def test_job_work_sums_the_solver_counts():
    cfg = {"task": "glm_solve", "num_coefficients": 10}
    j = work.job(cfg, {"rows": 4, "slots": 3, "evals_per_job": 5})
    assert j["flops"] == 5 * work.glm_eval(4, 3, 10)["flops"]
    assert j["rows_passed"] == 20
    cfg = {"task": "game_cd", "fixed_dim": 4, "user_dim": 2}
    counts = {"rows": 10, "active_slots": 6,
              "solver_iterations": [("fixed", 2.0), ("per-user", 1.5)]}
    j = work.job(cfg, counts)
    score = work.game_score_pass(10, 4, 2)
    want = (2.0 * work.game_fixed_newton_iter(10, 4)["bytes"]
            + 1.5 * work.game_user_newton_iter(6, 2)["bytes"]
            + 2 * score["bytes"])
    assert j["bytes"] == pytest.approx(want)
    assert work.job({"task": "game_serve"}, {}) is None


def test_shares_are_none_not_zero_without_a_reading():
    assert work.hbm_roofline_pct(1e9, 0.0, PEAKS) is None
    assert work.hbm_roofline_pct(0, 1.0, PEAKS) is None
    assert work.mfu_pct(1e9, None, PEAKS) is None
    # 819 GB in one busy second is the whole roofline
    assert work.hbm_roofline_pct(819e9, 1.0, PEAKS) == pytest.approx(100.0)
    assert work.mfu_pct(197e12, 2.0, PEAKS) == pytest.approx(50.0)
