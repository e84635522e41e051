"""Rows of the entity-sharded layout that hold nothing: 1 - real rows /
padded rows over every random effect's row partition (each shard padded to
the fullest) and every exchange's blocks (each (source, destination) block
padded to the largest).  From the ``rows``, ``padded_rows``, ``shards``,
``exchange_block_rows`` and ``exchange_real_rows`` of the program's
``partition.entity_layout`` spans, which close at set-up (the task keeps
them in ``run.counts``).  Nothing on a checkout without those attributes."""
LAYER = "entity-shard layout"
UNIT = "%"
MOVES = "train.time_to_auc_s"


def read(run):
    real = padded = 0
    for _, attrs in run.counts.get("layout_spans") or []:
        if "padded_rows" not in attrs:
            return None
        real += attrs["rows"]
        padded += attrs["padded_rows"]
        if "exchange_block_rows" in attrs:
            real += attrs["exchange_real_rows"]
            padded += attrs["shards"] ** 2 * attrs["exchange_block_rows"]
    if not padded:
        return None
    return 100.0 * (1.0 - real / padded)
