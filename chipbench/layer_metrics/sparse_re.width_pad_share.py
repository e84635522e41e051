"""Padded compact columns of the INDEX_MAP random effects that hold no
column of an entity's union, over all of them: 1 - real union columns /
padded compact columns (a bucket's lanes all take its widest lane's union,
rounded up to 128), from the program's ``game.index_map`` spans of set-up
(the task keeps them in ``run.counts``, as it keeps ``game.design``'s).
Every lane vector of a solve and of TRON's traffic runs at the padded width.
Nothing on a checkout without the span."""
LAYER = "random-effect design"
UNIT = "%"
MOVES = "train.time_to_auc_s"


def read(run):
    spans = run.counts.get("index_map_spans") or []
    padded = sum(attrs.get("padded_columns", 0) for _, attrs in spans)
    if not padded:
        return None
    real = sum(attrs.get("union_columns", 0) for _, attrs in spans)
    return 100.0 * (1.0 - real / padded)
