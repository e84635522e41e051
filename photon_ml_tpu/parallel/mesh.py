"""Mesh construction and sharding helpers.

Axis conventions for the whole framework (SURVEY §2.5, §5.7):
  'data'   — batch rows of the global (fixed-effect) problem; the analog of
             Spark example partitioning (``FixedEffectDataSet.scala:31``).
  'entity' — random-effect entity buckets; the analog of
             ``RandomEffectIdPartitioner`` placement (expert-parallel-like).

A 1D mesh uses just 'data'; GAME training uses ('data', 'entity') with the
same devices viewed both ways (the two phases alternate, they don't nest).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_ml_tpu.core.types import LabeledBatch

DATA_AXIS = "data"
ENTITY_AXIS = "entity"
FEATURE_AXIS = "feature"
# 2-D hierarchical reductions (docs/PARALLEL.md): 'host' is the slow
# (DCN, inter-host) axis, 'device' the fast (ICI, intra-host) one.
HOST_AXIS = "host"
DEVICE_AXIS = "device"


def make_mesh(
    n_data: Optional[int] = None, devices: Optional[Sequence] = None
) -> Mesh:
    """1D 'data' mesh over the given (default: all) devices."""
    devs = list(devices) if devices is not None else jax.devices()
    if n_data is None:
        n_data = len(devs)
    if n_data > len(devs):
        raise ValueError(
            f"mesh of {n_data} 'data' devices requested, have {len(devs)}"
        )
    return Mesh(np.asarray(devs[:n_data]), (DATA_AXIS,))


def make_game_mesh(
    n_data: int, n_entity: int, devices: Optional[Sequence] = None
) -> Mesh:
    """2D ('data', 'entity') mesh: fixed-effect solves shard over both axes
    flattened; random-effect bucket solves shard over 'entity'."""
    devs = list(devices) if devices is not None else jax.devices()
    if n_data * n_entity > len(devs):
        raise ValueError(
            f"mesh {n_data}x{n_entity} needs {n_data * n_entity} devices, "
            f"have {len(devs)}"
        )
    grid = np.asarray(devs[: n_data * n_entity]).reshape(n_data, n_entity)
    return Mesh(grid, (DATA_AXIS, ENTITY_AXIS))


def make_feature_mesh(
    n_data: int, n_feature: int, devices: Optional[Sequence] = None
) -> Mesh:
    """2D ('data', 'feature') mesh for the huge-d fixed-effect regime
    (SURVEY §5.7): rows shard over 'data', coefficient/feature columns
    over 'feature' — the TPU answer to the reference's off-heap coefficient
    index (``util/PalDBIndexMap.scala:43``), where w no longer fits
    replicated on one worker."""
    devs = list(devices) if devices is not None else jax.devices()
    if n_data * n_feature > len(devs):
        raise ValueError(
            f"mesh {n_data}x{n_feature} needs {n_data * n_feature} "
            f"devices, have {len(devs)}"
        )
    grid = np.asarray(devs[: n_data * n_feature]).reshape(n_data, n_feature)
    return Mesh(grid, (DATA_AXIS, FEATURE_AXIS))


def make_entity_mesh(
    n_entity: Optional[int] = None, devices: Optional[Sequence] = None
) -> Mesh:
    """1D 'entity' mesh for entity-sharded GAME descent: the SAME
    devices a 'data' mesh would use, viewed entity-wise — random-effect
    tables, their bucket lanes, and the entity-partitioned row space all
    shard over this one axis (docs/PARALLEL.md)."""
    devs = list(devices) if devices is not None else jax.devices()
    if n_entity is None:
        n_entity = len(devs)
    if n_entity > len(devs):
        raise ValueError(
            f"mesh of {n_entity} 'entity' devices requested, have "
            f"{len(devs)}"
        )
    return Mesh(np.asarray(devs[:n_entity]), (ENTITY_AXIS,))


def make_host_device_mesh(
    n_host: int, n_device: int, devices: Optional[Sequence] = None
) -> Mesh:
    """2D ('host', 'device') mesh for hierarchical two-level reductions
    (docs/PARALLEL.md): 'device' is the fast intra-host (ICI) axis,
    'host' the slow inter-host (DCN) one. On a real pod build it with
    each process's local devices forming one 'host' row; single-process
    it partitions the virtual CPU devices the same way so tier-1 drills
    the ICI-then-DCN reduction order without hardware."""
    devs = list(devices) if devices is not None else jax.devices()
    if n_host * n_device > len(devs):
        raise ValueError(
            f"mesh {n_host}x{n_device} needs {n_host * n_device} "
            f"devices, have {len(devs)}"
        )
    grid = np.asarray(devs[: n_host * n_device]).reshape(n_host, n_device)
    return Mesh(grid, (HOST_AXIS, DEVICE_AXIS))


def default_mesh() -> Mesh:
    return make_mesh()


def batch_sharding(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    """Shard the leading (row) axis over ALL mesh axes flattened; replicate
    the rest. On a 1D mesh that is plain 'data' sharding; on a GAME
    ('data', 'entity') mesh the fixed-effect batch still uses every device
    (the random-effect phase re-views the same devices entity-wise)."""
    return NamedSharding(
        mesh, P(tuple(mesh.axis_names), *([None] * (ndim - 1)))
    )


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def entity_sharding(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    """Shard the leading (entity) axis over 'entity'; replicate the rest."""
    return NamedSharding(mesh, P(ENTITY_AXIS, *([None] * (ndim - 1))))


def shard_design(design, mesh: Mesh):
    """Place a RandomEffectDesign entity-sharded over the 'entity' axis.
    The entity count must divide evenly (build with
    entity_multiple=mesh.shape['entity'])."""
    n_shards = mesh.shape[ENTITY_AXIS]
    if design.num_entities % n_shards != 0:
        raise ValueError(
            f"{design.num_entities} entities do not shard over "
            f"{n_shards} 'entity' devices; pad with entity_multiple"
        )
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, entity_sharding(mesh, np.ndim(x))),
        design,
    )


def shard_bucketed_design(design, mesh: Mesh):
    """Entity-shard every bucket of a BucketedRandomEffectDesign (and its
    lane->table index vectors). Returns a new container; the global
    coefficient table stays wherever the caller put it (usually
    replicated — scatters from sharded lanes insert the collectives)."""
    import dataclasses as _dc

    return _dc.replace(
        design,
        buckets=[shard_design(b, mesh) for b in design.buckets],
        entity_index=[
            jax.device_put(jnp.asarray(ei), entity_sharding(mesh, 1))
            for ei in design.entity_index
        ],
    )


def shard_batch(batch: LabeledBatch, mesh: Mesh) -> LabeledBatch:
    """Place a batch row-sharded over all mesh axes (pads rows to a
    multiple of the device count first — padding is masked, so invisible)."""
    n_shards = mesh.devices.size
    n = batch.batch_size
    padded = LabeledBatch.pad_to(batch, ((n + n_shards - 1) // n_shards) * n_shards)
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(
            x, batch_sharding(mesh, np.ndim(x))
        ),
        padded,
    )
