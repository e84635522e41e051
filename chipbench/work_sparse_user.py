"""The work a GAME coordinate-descent job needs when a coordinate is a random
effect over a SPARSE bag through INDEX_MAP (``game/projected.py
IndexMapRandomEffectCoordinate``: each bucket's lanes in their compact
columns at the bucket's width, TRON a lane), from shapes and the program's
own counts alone: FLOPs, and the bytes that must cross HBM at least once.
Fixed and dense random effects are ``work_multi.py``'s, formula for formula.

A pass of a bucket (a value/gradient or a Hessian-vector product of every
lane) books its held rows' STORED entries, not the padded ones (a padded
slot holds nothing the algorithm needs), and its lanes' vectors and TRON's
vector traffic at the bucket's width.

``counts["coordinates_work"]`` is the task's list, in update order; a
sparse entry carries ``"lanes"``, ``"widths"``, ``"rows_by_bucket"`` (held
rows), ``"stored_by_bucket"`` (their stored entries), ``"table"`` (the flat
table's size) and ``"row_slots_stored"`` (every row's stored entries in the
union: the rescore's); ``counts["solver_work"]`` one entry an update,
``(coordinate, mean solver iterations, inner)``, ``inner`` the program's own
record of a sparse update, ``[{"sparse_re": {"passes": [a bucket's
passes]}}]``; ``counts["rows"]``.
"""

from __future__ import annotations

from chipbench.work import F32, I32
from chipbench.work_multi import objective, update

# TRON's vector traffic a pass, in lane vectors of the bucket's width: the
# product read and written, and the CG step's direction, residual, step
# and their updates
LANE_VECTORS_A_PASS = 8


def bucket_pass(rows: int, stored: int, lanes: int, width: int) -> dict:
    """One value/gradient or Hessian-vector pass of a bucket: every stored
    entry's local id and value read twice (the margins' gather, the
    transpose's segment sum), 2 FLOPs each way; a held row's offset, label,
    weight and curvature; the lanes' vectors."""
    vectors = lanes * width
    return {
        "flops": 4 * stored + 12 * rows + 2 * LANE_VECTORS_A_PASS * vectors,
        "bytes": (2 * stored * (I32 + F32) + 4 * rows * F32
                  + LANE_VECTORS_A_PASS * vectors * F32),
    }


def sparse_update(coord: dict, rows: int, passes: list,
                  coordinates: int) -> dict:
    """One update: the residual offsets gathered into the held rows once;
    each bucket's passes; the flat table read and written; every row
    rescored from its stored entries; the objective."""
    held = sum(coord["rows_by_bucket"])
    parts = [
        ({"flops": 0, "bytes": held * (I32 + 2 * F32)}, 1),
        ({"flops": 0, "bytes": 2 * coord["table"] * F32}, 1),
        ({"flops": 2 * coord["row_slots_stored"],
          "bytes": coord["row_slots_stored"] * (I32 + 2 * F32)
          + rows * F32}, 1),
        (objective(rows, coordinates), 1),
    ]
    for n, r, s, e, k in zip(passes, coord["rows_by_bucket"],
                             coord["stored_by_bucket"], coord["lanes"],
                             coord["widths"]):
        parts.append((bucket_pass(r, s, e, k), n))
    return {
        key: sum(part[key] * times for part, times in parts)
        for key in ("flops", "bytes")
    }


def job(counts: dict):
    """FLOPs and HBM bytes of one job, in total and per coordinate, and the
    sparse coordinates' bucket passes; None where the task left no list of
    coordinates or of updates."""
    coords = {c["name"]: c for c in counts.get("coordinates_work") or ()}
    if not coords or not counts.get("solver_work"):
        return None
    keys = ("flops", "bytes", "sparse_passes")
    by_coordinate = {name: dict.fromkeys(keys, 0.0) for name in coords}
    for name, iterations, inner in counts["solver_work"]:
        coord = coords[name]
        if coord["kind"] == "sparse":
            passes = inner[0]["sparse_re"]["passes"]
            one = sparse_update(coord, counts["rows"], passes, len(coords))
            one["sparse_passes"] = sum(passes)
        else:
            one = update(coord, counts["rows"], iterations, len(coords))
        for key in keys:
            by_coordinate[name][key] += one.get(key, 0)
    return {
        **{key: sum(v[key] for v in by_coordinate.values()) for key in keys},
        "by_coordinate": by_coordinate,
    }
