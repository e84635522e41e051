"""The work the algorithms need, from shapes alone: FLOPs and the bytes that
must cross HBM at least once.  Nothing here reads a compiled program.

Every objective pass of these models is bound by HBM bandwidth, not by the
MXU: a sparse slot costs 8 bytes for 4 FLOPs, a dense float32 feature 4 bytes
for 4-6 FLOPs, against a chip that does 240 FLOPs in the time it moves a byte.
So each ``*_roofline`` share below is a share of the *bandwidth* roofline.
"""

from __future__ import annotations

F32 = 4
I32 = 4


def glm_eval(rows: int, slots: int, d: int) -> dict:
    """One value-and-gradient evaluation over padded sparse rows."""
    stored = rows * slots
    return {
        # margins: mul+add per slot; back-projection: mul+add per slot;
        # ~12 pointwise per row (softplus, sigmoid, weights)
        "flops": 4 * stored + 12 * rows,
        # each slot's index and value once, labels/weights/margins per row,
        # the coefficients read and the gradient written
        "bytes": stored * (I32 + F32) + rows * 3 * F32 + 2 * d * F32,
    }


def game_fixed_newton_iter(rows: int, d: int) -> dict:
    """One Newton iteration of the fixed effect: margins, gradient, the
    explicit (d, d) Hessian X^T D X."""
    return {
        "flops": rows * (2 * d + 2 * d + 2 * d * d + 12),
        "bytes": rows * d * F32 + rows * 3 * F32,
    }


def game_user_newton_iter(active_slots: int, d: int) -> dict:
    """One Newton iteration of every per-user solve over the padded bucketed
    design (``active_slots`` = sum over buckets of entities x row cap)."""
    return {
        "flops": active_slots * (2 * d + 2 * d + 2 * d * d + 12),
        "bytes": active_slots * d * F32 + active_slots * 3 * F32,
    }


def game_score_pass(rows: int, d_fixed: int, d_user: int) -> dict:
    """Rescoring all rows under both coordinates (once per coordinate update)."""
    return {
        "flops": rows * 2 * (d_fixed + d_user),
        "bytes": rows * (d_fixed + d_user) * F32 + rows * (I32 + 2 * F32),
    }


def serve_row(d_fixed: int, d_user: int) -> dict:
    """One scored row: its features, one table row (columns + values), out."""
    return {
        "flops": 2 * (d_fixed + d_user),
        "bytes": (d_fixed + d_user) * F32 + d_user * (I32 + F32) + I32 + F32,
    }


def hbm_roofline_pct(bytes_moved: float, busy_s: float, peaks: dict):
    """Least time the bytes need at the HBM peak, over the time the device
    was busy.  None where there is nothing to divide."""
    if not busy_s or busy_s <= 0 or not bytes_moved:
        return None
    return 100.0 * (bytes_moved / peaks["hbm_bytes_per_s"]) / busy_s


def mfu_pct(flops: float, wall_s: float, peaks: dict, chips: int = 1):
    if not wall_s or wall_s <= 0 or not flops:
        return None
    return 100.0 * flops / wall_s / (peaks["bf16_flops_per_s"] * chips)


def job(config: dict, counts: dict):
    """FLOPs and HBM bytes one training job needs, from the configuration's
    shapes and the solver's own iteration counts; None for other tasks."""
    if config["task"] == "glm_solve":
        one = glm_eval(counts["rows"], counts["slots"],
                       config["num_coefficients"])
        n = counts["evals_per_job"]
        return {"flops": one["flops"] * n, "bytes": one["bytes"] * n,
                "rows_passed": counts["rows"] * n}
    if config["task"] == "game_cd":
        flops = bytes_ = rows_passed = 0.0
        score = game_score_pass(counts["rows"], config["fixed_dim"],
                                config["user_dim"])
        for coordinate, iters in counts["solver_iterations"]:
            if coordinate == "fixed":
                one = game_fixed_newton_iter(counts["rows"],
                                             config["fixed_dim"])
                rows_passed += counts["rows"] * iters
            else:
                one = game_user_newton_iter(counts["active_slots"],
                                            config["user_dim"])
                rows_passed += counts["rows"] * iters
            flops += one["flops"] * iters + score["flops"]
            bytes_ += one["bytes"] * iters + score["bytes"]
        return {"flops": flops, "bytes": bytes_, "rows_passed": rows_passed}
    return None
