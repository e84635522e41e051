"""The work a GAME coordinate-descent job needs when one of its coordinates
is a FACTORED random effect (w_e = B gamma_e, ``game/factored.py``), from
shapes and the program's own counts alone: FLOPs, and the bytes that must
cross HBM at least once.  Fixed and plain random effects are
``work_multi.py``'s, formula for formula; this file adds the factored update.

``counts["coordinates_work"]`` is the task's list, in update order, of
``{"name", "kind": "fixed" | "random" | "factored", "dim", "active_slots",
"entities"}`` plus ``"latent_dim"`` on a factored one;
``counts["solver_work"]`` one entry an update of the job, ``(coordinate,
Newton iterations of that update, inner)``, ``inner`` None but for a
factored coordinate, where it is the program's own record of every inner
iteration: ``{"lanes": {"solver_iterations": mean Newton iterations a lane},
"projection": {"iterations": TRON's outer iterations, "cg_iterations": its
Hessian-vector products, "passes": outer + 1 + CG}}``; ``counts["rows"]``.
"""

from __future__ import annotations

from chipbench.work import F32, I32, game_user_newton_iter
from chipbench.work_multi import objective, update


def offsets_gather(active_slots: int) -> dict:
    """Every padded slot's residual offset, routed in once an update."""
    return {"flops": 0, "bytes": active_slots * (I32 + 2 * F32)}


def project(active_slots: int, d: int, k: int) -> dict:
    """The bucketed design through B: every slot's d features read, its k
    latent features written."""
    return {"flops": 2 * active_slots * d * k,
            "bytes": active_slots * (d + k) * F32}


def gamma_table(entities: int, k: int) -> dict:
    """Around one inner iteration's lane solves: every lane's gamma row
    gathered as the warm start, written back, and gathered again for the
    projection solve."""
    return {"flops": 0, "bytes": 3 * entities * k * F32}


def projection_solve(active_slots: int, d: int, k: int, outer: int,
                     cg: int, passes: int) -> dict:
    """One solve of the shared B (d x k unknowns) over every padded slot, by
    its passes: a value/gradient (``outer + 1`` of them) is two contractions
    of the (slots, d) design with (d, k), a Hessian-vector product (one a CG
    iteration) three; a pass reads a slot's features, its lane's gamma and
    its label, offset, weight and mask."""
    return {
        "flops": (4 * (outer + 1) + 6 * cg) * active_slots * d * k,
        "bytes": passes * active_slots * (d + k + 4) * F32,
    }


def rescore(rows: int, d: int, k: int) -> dict:
    """All rows under the updated factors, (x_i B) . gamma[id_i]: a row's
    features and id read, its gamma row gathered, its score written."""
    return {"flops": rows * (2 * d * k + 2 * k),
            "bytes": rows * (d * F32 + I32 + k * F32 + F32)}


def factored_update(coord: dict, rows: int, inner: list,
                    coordinates: int) -> dict:
    """One factored update: the offsets gather once; an inner iteration's
    projection, lane solves at width k, table traffic and projection solve;
    then the rescore and the objective."""
    slots, lanes = coord["active_slots"], coord["entities"]
    d, k = coord["dim"], coord["latent_dim"]
    parts = [(offsets_gather(slots), 1), (rescore(rows, d, k), 1),
             (objective(rows, coordinates), 1)]
    passes = 0
    for it in inner:
        solve = it["projection"]
        passes += solve["passes"]
        parts += [
            (project(slots, d, k), 1),
            (game_user_newton_iter(slots, k),
             it["lanes"]["solver_iterations"]),
            (gamma_table(lanes, k), 1),
            (projection_solve(slots, d, k, solve["iterations"],
                              solve["cg_iterations"], solve["passes"]), 1),
        ]
    out = {
        key: sum(part[key] * times for part, times in parts)
        for key in ("flops", "bytes")
    }
    out["projection_passes"] = passes
    return out


def job(counts: dict):
    """FLOPs and HBM bytes of one job, in total and per coordinate, and the
    projection solves' passes over the factored design; None where the task
    left no list of coordinates or of updates."""
    coords = {c["name"]: c for c in counts.get("coordinates_work") or ()}
    if not coords or not counts.get("solver_work"):
        return None
    keys = ("flops", "bytes", "projection_passes")
    by_coordinate = {name: dict.fromkeys(keys, 0.0) for name in coords}
    for name, iterations, inner in counts["solver_work"]:
        coord = coords[name]
        if coord["kind"] == "factored":
            one = factored_update(coord, counts["rows"], inner, len(coords))
        else:
            one = update(coord, counts["rows"], iterations, len(coords))
        for key in keys:
            by_coordinate[name][key] += one.get(key, 0)
    return {
        **{key: sum(v[key] for v in by_coordinate.values()) for key in keys},
        "by_coordinate": by_coordinate,
    }
