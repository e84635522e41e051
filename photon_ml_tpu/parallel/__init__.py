"""Device-mesh parallelism: the TPU replacement for Spark's cluster runtime.

The reference's distribution backend is Spark primitives — treeAggregate,
broadcast, shuffle (SURVEY §5.8, ``function/DiffFunction.scala:126-143``).
Here the backend is a ``jax.sharding.Mesh`` with XLA collectives over ICI:

  | Spark primitive            | here                                      |
  |----------------------------|-------------------------------------------|
  | treeAggregate(depth)       | psum over the 'data' mesh axis            |
  | broadcast(coefficients)    | replicated sharding (resident on device)  |
  | partitionBy(hash)          | even batch-axis sharding                  |
  | entity-partitioned RDDs    | 'entity' mesh axis for batched solves     |
  | join/cogroup by entityId   | device_put to entity shards at ingest     |
"""

from photon_ml_tpu.parallel.mesh import (
    batch_sharding,
    default_mesh,
    entity_sharding,
    make_entity_mesh,
    make_feature_mesh,
    make_game_mesh,
    make_host_device_mesh,
    make_mesh,
    replicated,
    shard_batch,
    shard_bucketed_design,
    shard_design,
)
from photon_ml_tpu.parallel.overlap import (
    collective_mode,
    feature_block_sum,
    overlap_chunks,
)
from photon_ml_tpu.parallel.heartbeat import (
    HeartbeatMonitor,
    InProcessHeartbeats,
    current_monitor,
    install_monitor,
)
from photon_ml_tpu.parallel.multihost import (
    CollectiveAbandoned,
    CollectiveResilience,
    CollectiveTimeout,
    allgather_host,
    allgather_strings,
    collective_resilience,
    configure_collective_resilience,
    fetch_replicated,
    global_entity_space,
    hierarchical_psum,
    initialize_multihost,
    make_global_array,
    make_global_batch,
    make_global_re_design,
    process_local_paths,
    process_local_rows,
    resilient_host_exchange,
)
from photon_ml_tpu.parallel.distributed import (
    distributed_train_glm,
    feature_sharded_train_glm,
    hierarchical_value_and_grad,
    shard_map_value_and_grad,
)

__all__ = [
    "make_mesh",
    "make_feature_mesh",
    "make_game_mesh",
    "make_entity_mesh",
    "make_host_device_mesh",
    "default_mesh",
    "collective_mode",
    "feature_block_sum",
    "overlap_chunks",
    "hierarchical_psum",
    "hierarchical_value_and_grad",
    "resilient_host_exchange",
    "batch_sharding",
    "entity_sharding",
    "replicated",
    "shard_batch",
    "shard_design",
    "shard_bucketed_design",
    "distributed_train_glm",
    "feature_sharded_train_glm",
    "shard_map_value_and_grad",
    "allgather_host",
    "allgather_strings",
    "fetch_replicated",
    "global_entity_space",
    "initialize_multihost",
    "make_global_array",
    "make_global_batch",
    "make_global_re_design",
    "process_local_paths",
    "process_local_rows",
    "CollectiveResilience",
    "CollectiveAbandoned",
    "CollectiveTimeout",
    "collective_resilience",
    "configure_collective_resilience",
    "HeartbeatMonitor",
    "InProcessHeartbeats",
    "current_monitor",
    "install_monitor",
]
