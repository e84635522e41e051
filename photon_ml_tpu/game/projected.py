"""Random-effect training in projected space.

Rebuild of ``algorithm/RandomEffectCoordinateInProjectedSpace.scala:26-120``
+ ``model/RandomEffectModelInProjectedSpace.scala:31-97``: the coordinate
solves every per-entity subproblem in a reduced k-dimensional space (shared
Gaussian RANDOM projection, per-entity INDEX_MAP compaction, or IDENTITY),
and coefficients are projected back to the original feature space at model
extraction so on-disk models never know projection existed.

TPU-first shape, over a DENSE shard: projection is applied ONCE to the
padded bucketed design at build time (a matmul or per-entity gather — not
a per-row RDD map), the inner :class:`RandomEffectCoordinate` is reused
unchanged on the projected tensors, and back-projection of the (E, k)
table is a single matmul / scatter.

Over a SPARSE shard (:class:`IndexMapRandomEffectCoordinate`), the regime
INDEX_MAP exists for (a wide bag, small per-entity unions), nothing is
dense: each bucket's lanes solve in their own compact columns at the
bucket's width (``game.data.build_index_map_design``), the coefficients
are one flat ragged table (``game.projectors.RaggedIndexMap``), and
back-projection gives per-entity (column, value) lists.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.game.coordinates import (
    CoordinateConfig,
    RandomEffectCoordinate,
    RandomEffectUpdateSummary,
    _design_offsets_maps,
    _make_index_map_update,
    _score_compact_rows,
)
from photon_ml_tpu.game.data import (
    BucketedRandomEffectDesign,
    GameData,
    IndexMapDesign,
    RandomEffectDesign,
    build_index_map_design,
)
from photon_ml_tpu.game.projectors import (
    IndexMapProjection,
    RandomProjection,
    build_random_projection,
)
from photon_ml_tpu.solvers.common import reason_histogram


def parse_projector_spec(spec: str) -> Tuple[str, Optional[int]]:
    """"IDENTITY" | "INDEX_MAP" | "RANDOM=<k>" -> (kind, k)
    (``projector/ProjectorType.scala:20-30``)."""
    s = spec.strip().upper()
    if s == "IDENTITY":
        return "IDENTITY", None
    if s == "INDEX_MAP":
        return "INDEX_MAP", None
    if s.startswith("RANDOM="):
        k = int(s.split("=", 1)[1])
        if k <= 0:
            raise ValueError(f"RANDOM projected dim must be positive: {spec}")
        return "RANDOM", k
    raise ValueError(
        f"unknown projector {spec!r}; expected IDENTITY, INDEX_MAP, or "
        "RANDOM=<k>"
    )


def build_index_map_columns(
    data: GameData,
    random_effect: str,
    shard: str,
    num_entities: int,
) -> IndexMapProjection:
    """Per-entity union of ACTIVE feature indices over all of the entity's
    rows of a DENSE shard (``IndexMapProjectorRDD.scala:113-120``),
    indexed by global entity id — usable against any bucketing of the
    same entities. O(nnz): works on the nonzero coordinates, never a
    dense (E, d) presence matrix. A sparse shard's random effect is
    :class:`IndexMapRandomEffectCoordinate`'s, in ragged widths."""
    from photon_ml_tpu.game.projectors import columns_from_active_pairs

    x = np.asarray(data.features[shard])
    eids = np.asarray(data.entity_ids[random_effect])
    rows, feat_cols = np.nonzero(x)
    ent = eids[rows]
    known = ent >= 0
    cols = columns_from_active_pairs(
        ent[known], feat_cols[known], x.shape[1], num_entities)
    return IndexMapProjection(columns=jnp.asarray(cols, jnp.int32))


def _project_design_bucket(
    projector, bucket: RandomEffectDesign, entity_index: np.ndarray,
    num_entities: int,
) -> RandomEffectDesign:
    if isinstance(projector, RandomProjection):
        return dataclasses.replace(
            bucket,
            features=projector.project_features(bucket.features),
        )
    # INDEX_MAP: gather this bucket's per-lane column tables (sentinel
    # lanes clip to entity num_entities-1's columns; their mask is 0 so the
    # garbage never enters a solve)
    cols = jnp.take(
        projector.columns, jnp.asarray(entity_index), axis=0, mode="clip"
    )  # (E_b, k)
    safe = jnp.maximum(cols, 0)
    gathered = jnp.take_along_axis(
        bucket.features, safe[:, None, :], axis=2
    )
    keep = (cols >= 0)[:, None, :]
    return dataclasses.replace(
        bucket, features=jnp.where(keep, gathered, 0.0)
    )


def project_design_and_rows(
    design: BucketedRandomEffectDesign,
    row_features: jax.Array,
    row_entities: jax.Array,
    projector,
):
    """The combo-invariant heavy lifting of a projected coordinate: project
    every bucket's design and the full row view ONCE. Cacheable across a
    reg-weight grid (projection depends on data, never on lambda)."""
    projected = BucketedRandomEffectDesign(
        buckets=[
            _project_design_bucket(projector, b, ei, design.num_entities)
            for b, ei in zip(design.buckets, design.entity_index)
        ],
        entity_index=design.entity_index,
        num_entities=design.num_entities,
    )
    if isinstance(projector, RandomProjection):
        proj_rows = projector.project_features(row_features)
    else:
        proj_rows = projector.project_row_features(
            row_features, row_entities
        )
    return projected, proj_rows


class ProjectedRandomEffectCoordinate:
    """A RandomEffectCoordinate whose solves happen in projected space.

    Drop-in member of a CoordinateDescent ``coordinates`` dict: exposes
    initial_params/update/score on the PROJECTED (E, k) table, plus
    :meth:`back_project` to map the trained table to original d-space for
    persistence (``RandomEffectModelInProjectedSpace.toRandomEffectModel``).
    """

    def __init__(
        self,
        design: BucketedRandomEffectDesign,
        row_features: jax.Array,  # (n, d) ORIGINAL-space scoring view
        row_entities: jax.Array,
        full_offsets_base: jax.Array,
        config: CoordinateConfig,
        projector: Union[RandomProjection, IndexMapProjection],
        original_dim: int,
        reg_weights: Optional[jax.Array] = None,
        prebuilt=None,  # (projected_design, projected_rows) from
        # :func:`project_design_and_rows` — reused across a lambda grid
    ):
        if isinstance(design, RandomEffectDesign):
            design = BucketedRandomEffectDesign(
                buckets=[design],
                entity_index=[
                    np.arange(design.num_entities, dtype=np.int32)
                ],
                num_entities=design.num_entities,
            )
        self.projector = projector
        self.original_dim = original_dim
        if prebuilt is not None:
            projected, proj_rows = prebuilt
        else:
            projected, proj_rows = project_design_and_rows(
                design, row_features, row_entities, projector
            )
        self.inner = RandomEffectCoordinate(
            design=projected,
            row_features=proj_rows,
            row_entities=row_entities,
            full_offsets_base=full_offsets_base,
            config=config,
            reg_weights=reg_weights,
        )

    def with_config(self, config: CoordinateConfig) -> "ProjectedRandomEffectCoordinate":
        """Same projected design/rows under a different optimization
        config — the grid-sweep reuse hook (designs and projections are
        combo-invariant; only the solver knobs change per combo).

        Per-entity reg weights: a UNIFORM vector (the default fill from
        the old config) is rebuilt from the new config's reg_weight; a
        CUSTOM per-entity vector is carried through unchanged — silently
        replacing it with the new uniform weight would discard the
        per-entity objectives the caller configured."""
        old = np.asarray(self.inner.reg_weights)
        uniform = np.allclose(old, self.inner.config.reg_weight)
        return ProjectedRandomEffectCoordinate(
            design=self.inner.design,
            row_features=self.inner.row_features,
            row_entities=self.inner.row_entities,
            full_offsets_base=self.inner.full_offsets_base,
            config=config,
            projector=self.projector,
            original_dim=self.original_dim,
            reg_weights=None if uniform else self.inner.reg_weights,
            prebuilt=(self.inner.design, self.inner.row_features),
        )

    @property
    def config(self) -> CoordinateConfig:
        """CoordinateDescent reads this for the objective's reg term — the
        L2 penalty applies to the projected table, exactly what the inner
        solves minimized."""
        return self.inner.config

    @property
    def num_entities(self) -> int:
        return self.inner.num_entities

    @property
    def dim(self) -> int:
        """Projected dimension (the solve space)."""
        return self.inner.dim

    def initial_params(self) -> jax.Array:
        return self.inner.initial_params()

    def update(self, table, partial_scores, key=None):
        return self.inner.update(table, partial_scores, key=key)

    def update_and_score(self, table, partial_scores, key=None):
        return self.inner.update_and_score(table, partial_scores, key=key)

    def update_step(self, table, partial_scores, key=None):
        return self.inner.update_step(table, partial_scores, key=key)

    def wrap_tracker(self, trackers):
        return self.inner.wrap_tracker(trackers)

    def fused_state(self):
        return self.inner.fused_state()

    def with_fused_state(self, state):
        import copy

        c = copy.copy(self)
        c.inner = self.inner.with_fused_state(state)
        return c

    def reg_term(self, table: jax.Array) -> jax.Array:
        return self.inner.reg_term(table)

    def score(self, table: jax.Array) -> jax.Array:
        return self.inner.score(table)

    def back_project(self, table: jax.Array) -> jax.Array:
        """(E, k) projected table -> (E, d) original-space coefficients
        (``RandomEffectModelInProjectedSpace.scala:31-97``)."""
        if isinstance(self.projector, RandomProjection):
            return self.projector.project_coefficients_back(table)
        return self.projector.project_coefficients_back(
            table, self.original_dim
        )


@dataclasses.dataclass
class IndexMapUpdateSummary(RandomEffectUpdateSummary):
    """The lazy tracker view of one :class:`IndexMapRandomEffectCoordinate`
    update: ``RandomEffectUpdateSummary``'s per-lane fields, plus each
    bucket's passes over its compact rows, fetched in the descent's one
    batched history transfer (``history_fetch`` / ``history_decode``) and
    handed to the update's record as ``inner_iterations``, one entry:
    ``{"lanes": {...}, "sparse_re": {"passes": [a bucket's passes, the
    most of any of its real lanes: its batched solve ran that many]}}``
    (``game.sparse_re.passes`` books their sum at ``materialize()``)."""

    def history_fetch(self):
        return tuple(
            (reason, iters, gnorm, passes)
            for reason, iters, gnorm, _, _, passes in self.pending
        )

    def history_decode(self, host):
        valid = [v for _, _, _, v, _, _ in self.pending]
        entity_ids = np.concatenate(
            [np.asarray(e)[v] for _, _, _, v, e, _ in self.pending])

        def lanes_of(field):
            return np.concatenate(
                [np.asarray(bucket[field])[v]
                 for bucket, v in zip(host, valid)])

        reason, iterations, grad_norms = (lanes_of(i) for i in range(3))
        passes = [
            int(np.asarray(bucket[3])[v].max(initial=0))
            for bucket, v in zip(host, valid)
        ]
        inner = [{
            "lanes": {
                "count": int(iterations.size),
                "solver_iterations": (
                    float(np.mean(iterations)) if iterations.size else 0.0),
                "convergence_histogram": reason_histogram(reason),
            },
            "sparse_re": {"passes": passes},
        }]
        return reason, iterations, grad_norms, entity_ids, inner

    def _materialize(self):
        if self.pending is not None:
            import jax as _jax

            (self._reason, self._iterations, self._grad_norms,
             self._entity_ids, _) = self.history_decode(
                _jax.device_get(self.history_fetch()))
            self.pending = None


class IndexMapRandomEffectCoordinate:
    """A random effect over a SPARSE shard through INDEX_MAP
    (``RandomEffectCoordinateInProjectedSpace.scala:26-120``): every
    entity solves in the compact space of the columns its active rows
    store, each bucket of entities at its own width, the whole coefficient
    set one flat ragged table (``game.projectors.RaggedIndexMap``).

    Drop-in member of a CoordinateDescent ``coordinates`` dict: its
    parameters are the flat (T,) table; :meth:`back_project` gives the
    per-entity (column, value) lists persistence and scoring read
    (``RandomEffectModelInProjectedSpace.toRandomEffectModel``), never an
    (E, d) table."""

    def __init__(
        self,
        design: IndexMapDesign,
        full_offsets_base: jax.Array,
        config: CoordinateConfig,
    ):
        if config.random_effect is None:
            raise ValueError("config lacks random_effect; wrong coordinate")
        self.design = design
        self.config = config
        self.full_offsets_base = full_offsets_base
        # traced, like the fixed effect's (``FixedEffectCoordinate``)
        self._reg_weight = config.reg_weight
        self._widths = design.index_map.widths
        self._update_all = _make_index_map_update(config)
        self._offsets_maps = _design_offsets_maps(design)
        self._valid_lanes = [
            np.asarray(ei) < design.num_entities
            for ei in design.entity_index
        ]
        self._score = jax.jit(_score_compact_rows)

    @classmethod
    def from_sparse_shard(
        cls,
        data: GameData,
        random_effect: str,
        shard: str,
        num_entities: int,
        config: CoordinateConfig,
        num_buckets: int = 4,
        active_cap: Optional[int] = None,
        entity_multiple: int = 1,
        seed: int = 0,
        dtype=None,
        feature_ratio: Optional[float] = None,
        min_support: int = 0,
    ) -> "IndexMapRandomEffectCoordinate":
        """Build the coordinate straight from a padded-ELL shard
        (``game.data.build_index_map_design``): host-side, once a run,
        O(nnz log nnz)."""
        dtype = dtype or jnp.float32
        design = build_index_map_design(
            data, random_effect, shard, num_entities,
            num_buckets=num_buckets, active_cap=active_cap,
            entity_multiple=entity_multiple, seed=seed, dtype=dtype,
            feature_ratio=feature_ratio, min_support=min_support,
        )
        return cls(
            design=design,
            full_offsets_base=jnp.asarray(data.offsets, dtype),
            config=config,
        )

    def with_config(self, config: CoordinateConfig) -> "IndexMapRandomEffectCoordinate":
        """The same design under another optimization config (the grid
        sweep's reuse)."""
        return IndexMapRandomEffectCoordinate(
            design=self.design,
            full_offsets_base=self.full_offsets_base,
            config=config,
        )

    @property
    def num_entities(self) -> int:
        return self.design.num_entities

    @property
    def dim(self) -> int:
        """The shard's original width (the space of the saved model)."""
        return self.design.index_map.original_dim

    def initial_params(self) -> jax.Array:
        return jnp.zeros(
            (self.design.index_map.size,),
            self.design.buckets[0].values.dtype,
        )

    def update(self, table, partial_scores, key=None):
        table, summary, _ = self.update_and_score(table, partial_scores, key)
        return table, summary

    def update_and_score(self, table, partial_scores, key=None):
        table, trackers, scores = self.update_step(
            table, partial_scores, key)
        return table, self.wrap_tracker(trackers), scores

    def update_step(self, table, partial_scores, key=None):
        """Trace-safe update + rescore (the fused pass's unit)."""
        return self._update_all(
            table,
            jnp.asarray(self._reg_weight, table.dtype),
            self.full_offsets_base + partial_scores,
            self._offsets_maps,
            tuple(self.design.buckets),
            self.design.row_slots,
            self.design.row_values,
            widths=self._widths,
        )

    def wrap_tracker(self, trackers: tuple) -> IndexMapUpdateSummary:
        return IndexMapUpdateSummary(pending=[
            (reason, iters, gnorm, valid, np.asarray(ei), passes)
            for (reason, iters, gnorm, passes), valid, ei in zip(
                trackers, self._valid_lanes, self.design.entity_index)
        ])

    def fused_state(self):
        """See ``FixedEffectCoordinate.fused_state``."""
        return (
            jnp.asarray(self._reg_weight, jnp.result_type(float)),
            self.full_offsets_base,
            self._offsets_maps,
            tuple(self.design.buckets),
            self.design.row_slots,
            self.design.row_values,
        )

    def with_fused_state(self, state):
        import copy

        c = copy.copy(self)
        (
            c._reg_weight,
            c.full_offsets_base,
            c._offsets_maps,
            buckets,
            row_slots,
            row_values,
        ) = state
        c.design = dataclasses.replace(
            self.design, buckets=list(buckets), row_slots=row_slots,
            row_values=row_values)
        return c

    def score(self, table: jax.Array) -> jax.Array:
        return self._score(
            table, self.design.row_slots, self.design.row_values)

    def reg_term(self, table: jax.Array) -> jax.Array:
        """The penalty the lanes' solves minimized, over the flat table
        (its padding slots are zeros)."""
        lam = jnp.asarray(self._reg_weight, table.dtype)
        l2 = lam * (1.0 - self.config.l1_ratio)
        l1 = lam * self.config.l1_ratio
        return 0.5 * l2 * jnp.vdot(table, table) + l1 * jnp.sum(
            jnp.abs(table))

    def back_project(self, table):
        """The flat table as per-entity (column, value) lists, a
        ``game.scoring.CompactReTable`` (host), columns ascending."""
        return self.design.index_map.compact(np.asarray(table))
