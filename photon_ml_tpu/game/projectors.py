"""Per-entity dimensionality reduction: the reference's ``projector/``.

Three projector types (``projector/ProjectorType.scala:20-30``):

  IDENTITY   — no-op.
  RANDOM=k   — shared Gaussian random projection matrix, N(0, 1/k) for
               projected dimension k, with an optional intercept
               passthrough row (``projector/ProjectionMatrix.scala:96-126``).
  INDEX_MAP  — per-entity compaction onto the union of feature indices
               actually active in that entity's data
               (``projector/IndexMapProjector.scala:44``,
               ``projector/IndexMapProjectorRDD.scala:113-120``).

On TPU a projection of a DENSE shard is a matmul (RANDOM) or a gather
(INDEX_MAP, :class:`IndexMapProjection`) applied to the padded (entities,
rows, dim) design once at ingest; coefficients are projected back to the
original space by the transpose operation
(``model/RandomEffectModelInProjectedSpace.scala:31-97``).

INDEX_MAP over a SPARSE shard (:class:`RaggedIndexMap`) keeps no dense axis
at all: every entity's coefficients live in a flat ragged table, each
bucket of entities at its own width, and back-projection writes
per-entity (column, value) lists.
"""

from __future__ import annotations

import dataclasses
from functools import cached_property
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.core.types import _pytree_dataclass
from photon_ml_tpu.game.data import COMPACT_BLOCK, RandomEffectDesign
from photon_ml_tpu.game.scoring import CompactReTable


@_pytree_dataclass
class RandomProjection:
    """Shared Gaussian projection (``ProjectionMatrix.scala:33-127``).

    matrix: (d, k) with entries N(0, 1/k); if intercept_index is set, that
    original dimension maps to a dedicated passthrough output column
    (the reference appends an identity row for the intercept).
    """

    matrix: jax.Array  # (d, k)

    @property
    def projected_dim(self) -> int:
        return self.matrix.shape[1]

    def project_features(self, features: jax.Array) -> jax.Array:
        """(..., d) -> (..., k)."""
        return features @ self.matrix

    def project_coefficients_back(self, coef: jax.Array) -> jax.Array:
        """(..., k) -> (..., d): w_orig = P w_proj so that
        x_orig . w_orig == (P^T x_orig) . w_proj."""
        return coef @ self.matrix.T


def build_random_projection(
    original_dim: int,
    projected_dim: int,
    seed: int = 0,
    intercept_index: Optional[int] = None,
    dtype=jnp.float32,
) -> RandomProjection:
    rng = np.random.default_rng(seed)
    k = projected_dim
    m = rng.normal(0.0, 1.0 / np.sqrt(k), size=(original_dim, k))
    if intercept_index is not None:
        # intercept passthrough: its own exclusive output column
        m = np.concatenate([m, np.zeros((original_dim, 1))], axis=1)
        m[intercept_index, :] = 0.0
        m[intercept_index, -1] = 1.0
    return RandomProjection(matrix=jnp.asarray(m, dtype))


@_pytree_dataclass
class IndexMapProjection:
    """Per-entity feature-index compaction.

    columns: (E, k) int32 — for each entity, the original feature indices
    kept (padded with -1). k = max active-feature count over entities.
    """

    columns: jax.Array

    @property
    def projected_dim(self) -> int:
        return self.columns.shape[1]

    def project_design(self, design: RandomEffectDesign) -> RandomEffectDesign:
        """(E, R, d) -> (E, R, k) by per-entity column gather."""
        safe = jnp.maximum(self.columns, 0)  # (E, k)
        gathered = jnp.take_along_axis(
            design.features, safe[:, None, :], axis=2
        )
        col_mask = (self.columns >= 0)[:, None, :]
        return dataclasses.replace(
            design, features=jnp.where(col_mask, gathered, 0.0)
        )

    def project_coefficients_back(
        self, table: jax.Array, original_dim: int
    ) -> jax.Array:
        """(E, k) -> (E, d): scatter back to original indices."""
        e, k = table.shape
        out = jnp.zeros((e, original_dim), table.dtype)
        safe = jnp.maximum(self.columns, 0)
        vals = jnp.where(self.columns >= 0, table, 0.0)
        return out.at[jnp.arange(e)[:, None], safe].add(vals)

    def project_row_features(
        self, features: jax.Array, entities: jax.Array
    ) -> jax.Array:
        """(n, d) rows -> (n, k) in each row's OWN entity's projected space
        (entity -1 rows produce zeros; they score 0 anyway)."""
        safe_e = jnp.maximum(entities, 0)
        cols = self.columns[safe_e]  # (n, k)
        safe_c = jnp.maximum(cols, 0)
        gathered = jnp.take_along_axis(features, safe_c, axis=1)
        keep = (cols >= 0) & (entities >= 0)[:, None]
        return jnp.where(keep, gathered, 0.0)


def columns_from_active_pairs(
    ent: np.ndarray, col: np.ndarray, d: int, num_entities: int
) -> np.ndarray:
    """(entity, feature) occurrence pairs -> (num_entities, k) per-entity
    sorted active-column table padded with -1, where k = max active-column
    count. O(nnz): the shared kernel of both INDEX_MAP builders."""
    pairs = np.unique(ent.astype(np.int64) * d + col.astype(np.int64))
    pair_ent = pairs // d
    pair_col = pairs % d
    _, starts, counts = np.unique(
        pair_ent, return_index=True, return_counts=True
    )
    k = max(int(counts.max()) if counts.size else 1, 1)
    cols = np.full((num_entities, k), -1, np.int64)
    slot = np.arange(pairs.size) - np.repeat(starts, counts)
    cols[pair_ent, slot] = pair_col
    return cols


def build_index_map_projection(
    design: RandomEffectDesign, dtype=jnp.int32
) -> IndexMapProjection:
    """Union of active feature indices per entity
    (``IndexMapProjectorRDD.scala:113-120``): a feature is kept for an
    entity iff it is nonzero in any of that entity's active rows.

    Design-tensor variant of ``projected.build_index_map_columns`` (which
    derives the same column sets straight from GameData); both share
    :func:`columns_from_active_pairs`."""
    feats = np.asarray(design.features)  # (E, R, d)
    mask = np.asarray(design.mask)
    e, _, d = feats.shape
    ent, row, col = np.nonzero(feats)
    keep = mask[ent, row] > 0
    cols = columns_from_active_pairs(ent[keep], col[keep], d, e)
    return IndexMapProjection(columns=jnp.asarray(cols, dtype))


@dataclasses.dataclass(frozen=True)
class RaggedIndexMap:
    """Per-entity INDEX_MAP compaction in RAGGED widths, host-side and
    static for a coordinate's life (``IndexMapProjectorRDD.scala:113-120``
    with no global width): bucket b holds ``lanes[b]`` lanes of width
    ``widths[b]``, the largest union of its lanes' active columns rounded
    up to a multiple of ``game.data.COMPACT_BLOCK``; its lanes are one (lanes[b], widths[b]) block
    of a flat coefficient table, at ``bases[b]``, lane l's coefficients at
    ``bases[b] + l * widths[b]``.

    ``columns`` (T,) is the original column of each flat slot (-1 on a
    padding slot past its lane's union), ``entities`` (T,) the entity of
    each (``num_entities`` on a sentinel lane and on padding). A lane's
    columns ascend."""

    widths: tuple
    lanes: tuple
    columns: np.ndarray
    entities: np.ndarray
    num_entities: int
    original_dim: int

    @property
    def bases(self) -> tuple:
        return tuple(int(b) for b in np.concatenate(
            [[0], np.cumsum(np.multiply(self.lanes, self.widths))])[:-1])

    @property
    def size(self) -> int:
        return int(self.columns.size)

    @staticmethod
    def from_unions(*, union, lanes, entity_index, pair_lane, pair_column,
                    pair_local, num_entities, original_dim) -> "RaggedIndexMap":
        """From every global lane's union size (``union``, the buckets'
        lanes end to end), the buckets' lane counts and lane -> entity
        maps, and each kept (lane, column) pair with its local id."""
        lane_base = np.concatenate([[0], np.cumsum(lanes)])[:-1]
        widths = []
        for base, count in zip(lane_base, lanes):
            widest = int(union[base:base + count].max(initial=0))
            widths.append(max(-(-widest // COMPACT_BLOCK), 1)
                          * COMPACT_BLOCK)
        sizes = np.multiply(lanes, widths)
        table_base = np.concatenate([[0], np.cumsum(sizes)])[:-1]
        bucket_of_lane = np.repeat(np.arange(len(lanes)), lanes)
        lane_in_bucket = np.arange(int(sum(lanes))) - lane_base[
            bucket_of_lane]
        lane_start = (table_base[bucket_of_lane] + lane_in_bucket
                      * np.asarray(widths)[bucket_of_lane])
        columns = np.full(int(sizes.sum()), -1, np.int32)
        columns[lane_start[pair_lane] + pair_local] = pair_column
        entities = np.full(columns.size, num_entities, np.int32)
        lane_entity = np.concatenate(entity_index)
        real = columns >= 0
        entities[real] = lane_entity[
            np.searchsorted(lane_start, np.flatnonzero(real), "right") - 1]
        return RaggedIndexMap(
            widths=tuple(widths), lanes=tuple(int(x) for x in lanes),
            columns=columns, entities=entities,
            num_entities=num_entities, original_dim=original_dim,
        )

    @cached_property
    def _pair_keys(self):
        """(sorted (entity, column) keys of the real slots, their flat
        positions), sorted once."""
        real = np.flatnonzero(self.columns >= 0)
        keys = (self.entities[real].astype(np.int64) * self.original_dim
                + self.columns[real])
        order = np.argsort(keys, kind="stable")
        return keys[order], real[order]

    def row_positions(self, entities, indices, values):
        """Every stored entry of padded-ELL rows ((n, s) ``indices`` /
        ``values``, a row's entity in ``entities``) as a position of the
        flat table: (positions (n, s) int32, values (n, s)); an entry
        outside its entity's union, a padding entry and every entry of a
        row of an unknown entity (-1) get position 0 and value 0, so that
        they score nothing (the reference's projected-space scoring)."""
        keys, positions = self._pair_keys
        ents = np.asarray(entities).astype(np.int64)
        ind = np.asarray(indices).astype(np.int64)
        want = ents[:, None] * self.original_dim + ind
        at = np.clip(np.searchsorted(keys, want), 0, max(keys.size - 1, 0))
        hit = ((ents[:, None] >= 0) & (ind < self.original_dim)
               & (keys[at] == want if keys.size else False))
        return (np.where(hit, positions[at] if keys.size else 0,
                         0).astype(np.int32),
                np.where(hit, values, 0).astype(np.asarray(values).dtype))

    def lists(self, table):
        """The flat table as per-entity sparse lists: (entities, columns,
        values), one entry a real slot, by entity then column."""
        table = np.asarray(table)
        keys, positions = self._pair_keys
        return (
            (keys // self.original_dim).astype(np.int32),
            (keys % self.original_dim).astype(np.int32),
            table[positions],
        )

    def compact(self, table) -> CompactReTable:
        """Back-projection (``RandomEffectModelInProjectedSpace.scala:
        31-97``) into a :class:`game.scoring.CompactReTable`: (E, k)
        ascending columns padded with the original width, their values
        padded with 0, k the largest union; never an (E, d) table."""
        ents, cols, vals = self.lists(table)
        return compact_from_lists(ents, cols, vals, self.num_entities,
                                  self.original_dim)


def compact_from_lists(entities, columns, values, num_entities: int,
                       original_dim: int) -> CompactReTable:
    """(entity, column, value) lists sorted by entity then column ->
    :class:`CompactReTable` of ``num_entities`` rows (pad column
    ``original_dim``, pad value 0)."""
    entities = np.asarray(entities, np.int64)
    counts = np.bincount(entities, minlength=num_entities)
    k = max(int(counts.max(initial=0)), 1)
    cols = np.full((num_entities, k), original_dim, np.int32)
    vals = np.zeros((num_entities, k), np.asarray(values).dtype)
    starts = np.concatenate([[0], np.cumsum(counts)])[:-1]
    slot = np.arange(entities.size) - starts[entities]
    cols[entities, slot] = columns
    vals[entities, slot] = values
    return CompactReTable(cols, vals)
