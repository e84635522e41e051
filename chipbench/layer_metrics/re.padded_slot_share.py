"""Padded (entity, row) slots of the bucketed random-effect designs that
hold no row, over all their slots: what every vmapped per-entity solve
computes and throws away.  From the ``active_rows`` and ``active_slots`` of
the program's ``game.design`` spans, which close at set-up (the task keeps
them in ``run.counts``).  Nothing on a checkout without the span."""
LAYER = "random-effect design"
UNIT = "%"
MOVES = "train.time_to_auc_s"


def read(run):
    spans = run.counts.get("design_spans") or []
    slots = sum(attrs.get("active_slots", 0) for _, attrs in spans)
    if not slots:
        return None
    rows = sum(attrs.get("active_rows", 0) for _, attrs in spans)
    return 100.0 * (1.0 - rows / slots)
