"""GAME: Generalized Additive Mixed Effects, TPU-first.

Rebuild of the reference's experimental heart (SURVEY §2.3): one global
*fixed-effect* GLM plus many per-entity *random-effect* GLMs trained by
block coordinate descent with residual score offsets
(``algorithm/CoordinateDescent.scala:39-198``).

Architecture vs the reference:
  - Scores are dense (n,) device arrays indexed by row — the reference's
    KeyValueScore RDD joins (``data/KeyValueScore.scala:60-85``) become
    plain array arithmetic.
  - Random effects hold an (entities, dim) coefficient table; scoring is an
    embedding-style gather (missing entity -> index -1 -> score 0, the
    reference's semantic at ``model/RandomEffectModel.scala:117-146``).
  - Per-entity training data is bucketed into padded (entities, rows, dim)
    tensors at ingest (``RandomEffectDataSet``'s grouping/capping,
    ``data/RandomEffectDataSet.scala:172-380``) and solved by ONE vmapped
    jitted solver call — the reference's millions of independent in-executor
    solves (``algorithm/RandomEffectCoordinate.scala:185-213``) with zero
    scheduling overhead.
  - Down-sampling keeps static shapes: dropped rows get weight 0 and kept
    negatives are re-weighted (``sampler/BinaryClassificationDownSampler``).
"""

from photon_ml_tpu.game.data import (
    BucketedRandomEffectDesign,
    EntityRowPartition,
    EntityShardAssignment,
    EntityShardLayout,
    GameData,
    RandomEffectDesign,
    RowExchangePlan,
    build_bucketed_random_effect_design,
    build_index_map_design,
    build_random_effect_design,
    entity_partition_game_data,
    entity_partition_rows,
    entity_shard_assignment,
    entity_shard_layouts,
    row_exchange_plan,
)
from photon_ml_tpu.game.coordinates import (
    CoordinateConfig,
    EntityShardedRandomEffectCoordinate,
    FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.game.descent import CoordinateDescent, GameModel
from photon_ml_tpu.game.factored import (
    FactoredConfig,
    FactoredParams,
    FactoredRandomEffectCoordinate,
    MatrixFactorizationModel,
)
from photon_ml_tpu.game.projected import (
    IndexMapRandomEffectCoordinate,
    ProjectedRandomEffectCoordinate,
    build_index_map_columns,
    parse_projector_spec,
)

__all__ = [
    "FactoredConfig",
    "FactoredParams",
    "FactoredRandomEffectCoordinate",
    "MatrixFactorizationModel",
    "IndexMapRandomEffectCoordinate",
    "ProjectedRandomEffectCoordinate",
    "build_index_map_columns",
    "parse_projector_spec",
    "GameData",
    "RandomEffectDesign",
    "BucketedRandomEffectDesign",
    "build_random_effect_design",
    "build_bucketed_random_effect_design",
    "build_index_map_design",
    "CoordinateConfig",
    "EntityRowPartition",
    "EntityShardAssignment",
    "EntityShardLayout",
    "RowExchangePlan",
    "EntityShardedRandomEffectCoordinate",
    "FixedEffectCoordinate",
    "RandomEffectCoordinate",
    "CoordinateDescent",
    "GameModel",
    "entity_partition_game_data",
    "entity_partition_rows",
    "entity_shard_assignment",
    "entity_shard_layouts",
    "row_exchange_plan",
]
