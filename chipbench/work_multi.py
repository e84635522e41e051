"""The work a GAME coordinate-descent job over any number of coordinates
needs, from shapes and the solver's own iteration counts: FLOPs, and the
bytes that must cross HBM at least once.  ``work.py`` counts the two
coordinates of ``game_cd`` by name and may not be edited by the PR that adds
this; the per-iteration formulas are its own, taken from it.

``counts["coordinates_work"]`` is the task's list, in update order, of
``{"name", "kind": "fixed" | "random", "dim", "active_slots", "entities"}``
(``active_slots``: padded (entity, row) slots of the bucketed design,
``entities``: lanes of the buckets; both 0 for a fixed effect);
``counts["solver_iterations"]`` the program's own ``[(coordinate, Newton
iterations of that update), ...]``, one entry an update; ``counts["rows"]``.
"""

from __future__ import annotations

from chipbench.work import (
    F32,
    I32,
    game_fixed_newton_iter,
    game_user_newton_iter,
)


def re_gather_scatter(active_slots: int, entities: int, d: int) -> dict:
    """Around one random-effect update: every slot's residual offset
    gathered by its row index, every entity's table row gathered as the
    warm start and scattered back."""
    return {
        "flops": 0,
        "bytes": active_slots * (I32 + 2 * F32) + 2 * entities * d * F32,
    }


def rescore(rows: int, d: int, kind: str) -> dict:
    """Rescoring all rows under the updated coordinate: its features once,
    the scores out; a random effect also reads each row's id and gathers
    its table row."""
    per_row = d * F32 + F32
    if kind == "random":
        per_row += I32 + d * F32
    return {"flops": rows * 2 * d, "bytes": rows * per_row}


def objective(rows: int, coordinates: int) -> dict:
    """The training objective after an update: every coordinate's scores
    and the labels once, ~12 pointwise operations a row."""
    return {"flops": rows * (12 + coordinates),
            "bytes": rows * (coordinates + 1) * F32}


def update(coord: dict, rows: int, iterations: float, coordinates: int):
    """One coordinate update of ``iterations`` Newton iterations, with its
    rescore and the objective after it."""
    if coord["kind"] == "fixed":
        parts = [(game_fixed_newton_iter(rows, coord["dim"]), iterations)]
    else:
        parts = [
            (game_user_newton_iter(coord["active_slots"], coord["dim"]),
             iterations),
            (re_gather_scatter(coord["active_slots"], coord["entities"],
                               coord["dim"]), 1),
        ]
    parts += [(rescore(rows, coord["dim"], coord["kind"]), 1),
              (objective(rows, coordinates), 1)]
    return {
        key: sum(part[key] * times for part, times in parts)
        for key in ("flops", "bytes")
    }


def job(counts: dict):
    """FLOPs and HBM bytes of one job, in total and per coordinate; None
    where the task left no list of coordinates."""
    coords = {c["name"]: c for c in counts.get("coordinates_work") or ()}
    if not coords or not counts.get("solver_iterations"):
        return None
    by_coordinate = {name: {"flops": 0.0, "bytes": 0.0} for name in coords}
    for name, iterations in counts["solver_iterations"]:
        one = update(coords[name], counts["rows"], iterations, len(coords))
        for key in ("flops", "bytes"):
            by_coordinate[name][key] += one[key]
    return {
        "flops": sum(v["flops"] for v in by_coordinate.values()),
        "bytes": sum(v["bytes"] for v in by_coordinate.values()),
        "by_coordinate": by_coordinate,
    }
