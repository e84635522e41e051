"""The work a GAME coordinate-descent job needs when its random effects are
entity-sharded over several chips: ``work_multi.py``'s count, in total over
the chips, plus the row exchange of every coordinate whose rows lie in
another partition than the canonical one.

An exchange moves one float32 a row from one partition to the other: on the
sending side every row's slot index is read, its element read and written
into the packed block; on the receiving side every row's slot index is read,
the arrived element read and written into place.  An update of such a
coordinate runs two exchanges (the residual offsets in, the scores out).
Only real rows count: the pads of a block are the layout's overhead
(``shard.row_pad_share``), not work the algorithm needs.

``counts["coordinates_work"]`` is ``work_multi``'s list; an exchanging
coordinate's entry also has ``"exchange_rows"`` (the rows one exchange moves:
the training rows).
"""

from __future__ import annotations

from chipbench import work_multi
from chipbench.work import F32, I32

EXCHANGES_PER_UPDATE = 2


def exchange(rows: int) -> dict:
    """One exchange of ``rows`` float32 elements between two partitions."""
    return {"flops": 0, "bytes": 2 * rows * (I32 + 2 * F32)}


def job(counts: dict):
    """FLOPs and HBM bytes of one job over all chips, in total, per
    coordinate and for the exchanges alone; None where ``work_multi`` reads
    nothing."""
    base = work_multi.job(counts)
    if base is None:
        return None
    rows = {c["name"]: int(c.get("exchange_rows") or 0)
            for c in counts["coordinates_work"]}
    by_coordinate = {k: dict(v) for k, v in base["by_coordinate"].items()}
    moved = 0.0
    for name, _ in counts["solver_iterations"]:
        one = exchange(rows[name])["bytes"] * EXCHANGES_PER_UPDATE
        by_coordinate[name]["bytes"] += one
        moved += one
    return {
        "flops": base["flops"],
        "bytes": base["bytes"] + moved,
        "exchange_bytes": moved,
        "by_coordinate": by_coordinate,
    }
