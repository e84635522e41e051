"""Host wall of the entity-sharded layout at set-up: the program's
``partition.entity_layout`` spans (one a sharded random effect: its row
partition and exchange plan) and ``partition.coordinate`` spans (one a
sharded coordinate: the regroup of its design by owner shard and the
placement on the mesh) that end before the window, their union on each
thread.  Nothing on a checkout without both spans."""
LAYER = "entity-shard layout"
UNIT = "s"
MOVES = "setup_s"


def read(run):
    from chipbench import setup_spans

    return setup_spans.setup_seconds(
        run, ("partition.entity_layout", "partition.coordinate"))
