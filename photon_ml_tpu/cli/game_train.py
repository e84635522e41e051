"""GAME training driver.

Rebuild of ``cli/game/training/Driver.scala:47-541``: prepare per-shard
feature maps, convert Avro records to a GAME dataset (feature bags + entity
columns), build one coordinate per updating-sequence entry, train the
cartesian product of the per-coordinate reg-weight grids
(``Driver.scala:317-384``), log training objective and (optionally) a
validation metric after every coordinate update
(``CoordinateDescent.scala:173-189``), and save models under the
reference's output layout with BEST/ALL selection
(``Driver.scala:393-441``). Run as

    python -m photon_ml_tpu.cli.game_train --config params.json

or programmatically via :func:`run_game_training`.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.cli.config import (
    CoordinateSpec,
    GameDriverParams,
    load_params,
)
from photon_ml_tpu.cli.train import (
    prepare_output_dir,
    read_records,
    resolve_date_range,
)
from photon_ml_tpu.core.tasks import TaskType
from photon_ml_tpu.game import (
    CoordinateConfig,
    CoordinateDescent,
    FixedEffectCoordinate,
    GameModel,
    RandomEffectCoordinate,
    build_bucketed_random_effect_design,
)
from photon_ml_tpu.game.data import GameData
from photon_ml_tpu.game.factored import (
    FactoredConfig,
    FactoredRandomEffectCoordinate,
)
from photon_ml_tpu.game.projected import (
    IndexMapRandomEffectCoordinate,
    ProjectedRandomEffectCoordinate,
    build_index_map_columns,
    parse_projector_spec,
    project_design_and_rows,
)
from photon_ml_tpu.game.projectors import build_random_projection
from photon_ml_tpu.game.scoring import CompactReTable, score_game_data

from photon_ml_tpu.io.models import save_game_model
from photon_ml_tpu.io.vocab import FeatureVocabulary
from photon_ml_tpu.models.training import OptimizerType
from photon_ml_tpu.ops import metrics as metrics_mod
from photon_ml_tpu.utils.dates import expand_date_paths
from photon_ml_tpu.utils.logging import PhotonLogger, timed


def _coordinate_config(
    name: str, spec: CoordinateSpec, task: TaskType, reg_weight: float
) -> CoordinateConfig:
    return CoordinateConfig(
        shard=spec.shard,
        task=task,
        optimizer=OptimizerType[spec.optimizer],
        reg_weight=reg_weight,
        l1_ratio=spec.l1_ratio,
        max_iters=spec.max_iters,
        tolerance=spec.tolerance,
        down_sampling_rate=spec.down_sampling_rate,
        random_effect=spec.random_effect,
        active_cap=spec.active_cap,
        track_states=spec.track_states,
    )


def _validate_multiprocess_params(params: GameDriverParams) -> None:
    """Constraints of the multi-process GAME driver path. The supported
    surface is dense fixed effects + IDENTITY/factored random effects
    with num_buckets=1 — the entity-partitioned contract of
    ``make_global_re_design`` (the reference's RandomEffectIdPartitioner
    placement); everything else fails loudly instead of silently
    diverging across processes."""
    problems = []
    if params.validate_input:
        problems.append(
            "validate_input (validation rows would need the same entity "
            "partitioning; score offline with cli.score)"
        )
    if params.initial_model_dir:
        problems.append(
            "initial_model_dir (warm start: the loaded RE tables are "
            "remapped by POSITION into each process's local entity "
            "vocabulary before globalization, so coefficients would "
            "silently attach to the wrong entities; warm-start a "
            "single-process run or export per-partition models)"
        )
    if params.sparse_shards:
        problems.append("sparse_shards (the projected-sparse RE path is "
                        "per-process host work)")
    if params.checkpoint_every > 0 and not params.sharded_ckpt:
        problems.append(
            "checkpoint_every > 0 without sharded_ckpt (the whole-model "
            "save_checkpoint is single-writer: every process racing the "
            "same step dir would trample the tmp/swap protocol — set "
            "sharded_ckpt so each process writes only its shard, "
            "docs/MULTIHOST.md)"
        )
    for name, spec in params.coordinates.items():
        if spec.hot_columns:
            problems.append(f"coordinate {name!r}: hot_columns (the "
                            "hybrid row permutation is process-local)")
        if spec.random_effect is not None and spec.num_buckets != 1:
            problems.append(
                f"coordinate {name!r}: num_buckets != 1 (bucket shapes "
                "must agree across processes)"
            )
        if spec.projector:
            problems.append(f"coordinate {name!r}: projector")
    if problems:
        raise ValueError(
            "multi-process GAME training does not support: "
            + "; ".join(problems)
        )


def _ordered_entity_ids(re_key: str, vocab: dict) -> list:
    """One process's entity vocabulary, ordered by local index, for the
    string allgather that globalizes it. Ids must ALREADY be str: a
    silent str() coercion here would re-key the globalized vocabulary
    with different key types than a single-process run (int id 7 ->
    "7"), breaking warm-start/scoring lookups that carry the original
    type — so non-str ids fail loudly instead."""
    ordered = [None] * len(vocab)
    for raw, i in vocab.items():
        if not isinstance(raw, str):
            raise ValueError(
                f"random effect {re_key!r}: entity id {raw!r} is "
                f"{type(raw).__name__}, not str — multi-process GAME "
                "requires string entity ids (coerce them at ingest, "
                "before the vocabulary is built, so every process and "
                "every artifact agrees on key types)"
            )
        ordered[i] = raw
    return ordered


def _pad_game_data(data: GameData, n_target: int) -> GameData:
    """Pad to n_target rows with weight-0 / entity -1 filler rows so
    every process contributes identical shapes to the global arrays."""
    n = data.num_rows
    if n == n_target:
        return data
    pad = n_target - n
    return GameData(
        features={
            k: np.pad(np.asarray(v), ((0, pad), (0, 0)))
            for k, v in data.features.items()
        },
        labels=np.pad(data.labels, (0, pad)),
        offsets=np.pad(data.offsets, (0, pad)),
        weights=np.pad(data.weights, (0, pad)),  # pad rows weigh 0
        entity_ids={
            k: np.pad(v, (0, pad), constant_values=-1)
            for k, v in data.entity_ids.items()
        },
    )


def build_coordinates(
    params: GameDriverParams,
    data: GameData,
    task: TaskType,
    reg_combo: Dict[str, float],
    entity_counts: Dict[str, int],
    dtype=jnp.float64,
    shard_vocabs: Optional[Dict[str, FeatureVocabulary]] = None,
    design_cache: Optional[Dict[str, object]] = None,
    multiproc: Optional[dict] = None,
    entity_sharded: Optional[dict] = None,
):
    """One training coordinate per updating-sequence entry.

    ``design_cache`` (coordinate name -> built design) carries the
    combo-invariant bucketing/feature-selection work across a reg-weight
    grid — designs depend on data + data knobs, never on lambda.

    ``multiproc`` (multi-process runs): {"mesh", "row_base",
    "entity_spaces": re -> (E_global, entity_base),
    "local_entity_counts"} — local builds are globalized into
    mesh-spanning arrays (``parallel.multihost``).

    ``entity_sharded`` (docs/PARALLEL.md): {"mesh", "layouts": random
    effect -> :class:`game.data.EntityShardLayout`} —
    ``data`` is already in the CANONICAL entity-partitioned row order
    (the first random effect's); fixed-effect batches place row-sharded
    over the 'entity' mesh and every (plain) random-effect coordinate
    builds as an :class:`EntityShardedRandomEffectCoordinate` over its
    own layout (zero collectives in the canonical one's update, the row
    exchange in the others')."""
    coords = {}
    for name in params.updating_sequence:
        spec = params.coordinates[name]
        cfg = _coordinate_config(name, spec, task, reg_combo[name])
        if spec.random_effect is None:
            hybrid_pack = None
            if spec.hot_columns:
                # the hybrid re-pack is combo-invariant: build once per
                # grid sweep, like the random-effect designs
                cache_key = f"{name}\x00hybrid"
                if design_cache is not None and cache_key in design_cache:
                    hybrid_pack = design_cache[cache_key]
                else:
                    hybrid_pack = FixedEffectCoordinate.hybridize_batch(
                        data.fixed_effect_batch(spec.shard, dtype),
                        spec.hot_columns,
                    )
                    if design_cache is not None:
                        design_cache[cache_key] = hybrid_pack
            fe_batch = (
                data.fixed_effect_batch(spec.shard, dtype)
                if hybrid_pack is None
                else hybrid_pack[0]
            )
            if multiproc is not None:
                from photon_ml_tpu.parallel import make_global_batch

                fe_batch = make_global_batch(fe_batch, multiproc["mesh"])
            if entity_sharded is not None:
                from photon_ml_tpu.parallel.mesh import batch_sharding

                _mesh = entity_sharded["mesh"]
                fe_batch = jax.tree_util.tree_map(
                    lambda x: jax.device_put(
                        x, batch_sharding(_mesh, np.ndim(x))
                    ),
                    fe_batch,
                )
            coords[name] = FixedEffectCoordinate(
                fe_batch, cfg, hybrid_pack=hybrid_pack
            )
        else:
            from photon_ml_tpu.ops import sparse as sparse_ops

            # an entity-sharded random effect's design and per-row inputs
            # live in ITS row order; its offsets stay canonical
            es_layout = (
                entity_sharded["layouts"][spec.random_effect]
                if entity_sharded is not None
                else None
            )
            own = data if es_layout is None else es_layout.data
            if sparse_ops.is_sparse(own.features[spec.shard]):
                # wide-sparse random effect: INDEX_MAP in ragged compact
                # columns straight from the ELL (config.validate()
                # guarantees the projector)
                cache_key = f"{name}\x00sparse_projected"
                if design_cache is not None and cache_key in design_cache:
                    coords[name] = design_cache[cache_key].with_config(cfg)
                else:
                    coord = IndexMapRandomEffectCoordinate.from_sparse_shard(
                        data,
                        spec.random_effect,
                        spec.shard,
                        entity_counts[spec.random_effect],
                        cfg,
                        num_buckets=spec.num_buckets,
                        active_cap=spec.active_cap,
                        dtype=dtype,
                        feature_ratio=spec.feature_ratio,
                        min_support=spec.min_support,
                    )
                    if design_cache is not None:
                        design_cache[cache_key] = coord
                    coords[name] = coord
                continue
            if design_cache is not None and name in design_cache:
                design = design_cache[name]
            else:
                design_options = dict(
                    num_buckets=spec.num_buckets,
                    active_cap=spec.active_cap,
                    dtype=dtype,
                    feature_ratio=spec.feature_ratio,
                    min_support=spec.min_support,
                )
                if es_layout is not None:
                    # kept off the first chip: the coordinate shards it
                    design = es_layout.bucketed_design(
                        spec.random_effect, spec.shard, **design_options
                    )
                else:
                    design = build_bucketed_random_effect_design(
                        data,
                        spec.random_effect,
                        spec.shard,
                        (
                            multiproc["local_entity_counts"][
                                spec.random_effect
                            ]
                            if multiproc is not None
                            else entity_counts[spec.random_effect]
                        ),
                        **design_options,
                    )
                if multiproc is not None:
                    from photon_ml_tpu.parallel import (
                        make_global_re_design,
                    )

                    e_glob, e_base = multiproc["entity_spaces"][
                        spec.random_effect
                    ]
                    design = make_global_re_design(
                        design,
                        multiproc["mesh"],
                        e_glob,
                        e_base,
                        multiproc["row_base"],
                    )
                if design_cache is not None:
                    design_cache[name] = design
            if es_layout is not None:
                # host arrays: the coordinate puts each shard's rows
                # straight on its chip
                row_features = np.asarray(own.features[spec.shard], dtype)
                row_entities = np.asarray(own.entity_ids[spec.random_effect])
                offsets_base = np.asarray(data.offsets, dtype)
            elif multiproc is None:
                row_features = jnp.asarray(data.features[spec.shard], dtype)
                row_entities = jnp.asarray(
                    data.entity_ids[spec.random_effect]
                )
                offsets_base = jnp.asarray(data.offsets, dtype)
            else:
                from photon_ml_tpu.parallel import make_global_array

                mesh = multiproc["mesh"]
                _, e_base = multiproc["entity_spaces"][spec.random_effect]
                ents = np.asarray(data.entity_ids[spec.random_effect])
                row_features = make_global_array(
                    np.asarray(data.features[spec.shard], dtype), mesh
                )
                row_entities = make_global_array(
                    np.where(ents >= 0, ents + e_base, -1).astype(
                        np.int32
                    ),
                    mesh,
                )
                offsets_base = make_global_array(
                    np.asarray(data.offsets, dtype), mesh
                )
            if spec.latent_dim is not None:
                if spec.projector:
                    raise ValueError(
                        f"coordinate {name!r}: latent_dim (factored) and "
                        "projector are mutually exclusive"
                    )
                latent_cfg = dataclasses.replace(
                    cfg,
                    optimizer=(
                        OptimizerType[spec.latent_optimizer]
                        if spec.latent_optimizer is not None
                        else cfg.optimizer
                    ),
                    reg_weight=(
                        spec.latent_reg_weight
                        if spec.latent_reg_weight is not None
                        else cfg.reg_weight
                    ),
                    max_iters=(
                        spec.latent_max_iters
                        if spec.latent_max_iters is not None
                        else cfg.max_iters
                    ),
                    tolerance=(
                        spec.latent_tolerance
                        if spec.latent_tolerance is not None
                        else cfg.tolerance
                    ),
                    tron_max_cg=spec.latent_max_cg,
                )
                coords[name] = FactoredRandomEffectCoordinate(
                    design=design,
                    row_features=row_features,
                    row_entities=row_entities,
                    full_offsets_base=offsets_base,
                    re_config=cfg,
                    factored=FactoredConfig(
                        latent_dim=spec.latent_dim,
                        num_inner_iterations=spec.num_inner_iterations,
                        latent_factor_config=latent_cfg,
                    ),
                )
                continue
            kind, k = (
                parse_projector_spec(spec.projector)
                if spec.projector
                else ("IDENTITY", None)
            )
            if kind == "IDENTITY":
                if entity_sharded is not None:
                    from photon_ml_tpu.game import (
                        EntityShardedRandomEffectCoordinate,
                    )

                    coords[name] = EntityShardedRandomEffectCoordinate(
                        design=design,
                        row_features=row_features,
                        row_entities=row_entities,
                        full_offsets_base=offsets_base,
                        config=cfg,
                        mesh=entity_sharded["mesh"],
                        assignment=es_layout.assignment,
                        partition=es_layout.partition,
                    )
                else:
                    coords[name] = RandomEffectCoordinate(
                        design=design,
                        row_features=row_features,
                        row_entities=row_entities,
                        full_offsets_base=offsets_base,
                        config=cfg,
                    )
            else:
                d_orig = data.features[spec.shard].shape[1]
                cache_key = f"{name}\x00projected"
                if design_cache is not None and cache_key in design_cache:
                    projector, prebuilt = design_cache[cache_key]
                else:
                    if kind == "RANDOM":
                        # intercept passthrough row: per-entity base rates
                        # stay exactly representable
                        # (``ProjectionMatrix.scala:96-126``)
                        icpt = (
                            shard_vocabs[spec.shard].intercept_index
                            if shard_vocabs and spec.shard in shard_vocabs
                            else None
                        )
                        projector = build_random_projection(
                            d_orig, k, seed=0, intercept_index=icpt,
                            dtype=dtype,
                        )
                    else:  # INDEX_MAP
                        projector = build_index_map_columns(
                            data,
                            spec.random_effect,
                            spec.shard,
                            entity_counts[spec.random_effect],
                        )
                    prebuilt = project_design_and_rows(
                        design, row_features, row_entities, projector
                    )
                    if design_cache is not None:
                        design_cache[cache_key] = (projector, prebuilt)
                coords[name] = ProjectedRandomEffectCoordinate(
                    design=design,
                    row_features=row_features,
                    row_entities=row_entities,
                    full_offsets_base=offsets_base,
                    config=cfg,
                    projector=projector,
                    original_dim=d_orig,
                    prebuilt=prebuilt,
                )
    return coords


def materialize_original_space(model: GameModel, coords: Dict) -> GameModel:
    """Back-project any projected coordinate's table so the model is in
    original feature space (``RandomEffectModelInProjectedSpace.scala:31-97``
    — persistence and scoring never see projected coefficients; an
    INDEX_MAP coordinate over a sparse shard gives per-entity (column,
    value) lists, a ``CompactReTable``, never an (E, d) table), and
    bridge entity-SHARDED tables from their stored (shard-major, padded)
    layout back to the global entity order (docs/PARALLEL.md)."""
    from photon_ml_tpu.game import EntityShardedRandomEffectCoordinate

    def bridge(n, p):
        c = coords.get(n)
        if isinstance(
            c, (ProjectedRandomEffectCoordinate,
                IndexMapRandomEffectCoordinate)
        ):
            return c.back_project(p)
        if isinstance(c, EntityShardedRandomEffectCoordinate):
            return jnp.asarray(c.global_table(p))
        return p

    params = {n: bridge(n, p) for n, p in model.params.items()}
    return dataclasses.replace(model, params=params)


@dataclasses.dataclass
class GameTrainingRun:
    params: GameDriverParams
    shard_vocabs: Dict[str, FeatureVocabulary]
    entity_vocabs: Dict[str, dict]
    # one entry per grid combo: (combo, model, history, validation metric)
    sweep: List[dict]
    best_index: int
    output_dirs: List[str]


def run_game_training(params) -> GameTrainingRun:
    """Entry point: config load, log file, fault-drill arming, the
    observability envelope (tracer + metrics dumper + profiler window),
    and the preemption handler lifecycle around the actual training
    body."""
    from photon_ml_tpu import obs
    from photon_ml_tpu.resilience import GracefulShutdown, arm_from_env

    params = load_params(params, GameDriverParams)
    params.validate()
    prepare_output_dir(params.output_dir, params.overwrite or params.resume)
    logger = PhotonLogger(
        os.path.join(params.output_dir, "log-message.txt"),
        level=params.log_level,
    )
    armed = arm_from_env()
    if armed:
        logger.warn(
            f"{armed} fault-injection spec(s) armed from PHOTON_FAULTS — "
            "this is a resilience drill, not a production run"
        )
    shutdown = GracefulShutdown(logger)
    if params.graceful_shutdown:
        shutdown.install()
    # metrics.json lands in trace_dir when tracing, else next to
    # log-message.txt when periodic snapshots were asked for
    metrics_path = None
    if params.trace_dir is None and (
        params.metrics_every > 0 or params.convergence_report
    ):
        metrics_path = os.path.join(params.output_dir, "metrics.json")
    conv_tracker = None
    if params.convergence_report:
        # decode every coordinate update's per-entity convergence even
        # without a tracer; the aggregated run report lands next to the
        # models (fleet events additionally hit events.jsonl when
        # tracing)
        conv_tracker = obs.install_convergence_tracker()
    # multi-host resilience envelope (docs/MULTIHOST.md): watchdog policy
    # on every host collective + a pod heartbeat monitor whose losses
    # surface at pass boundaries as the distinct host-loss exit
    from photon_ml_tpu.parallel import (
        configure_collective_resilience,
        install_monitor,
    )
    from photon_ml_tpu.parallel.heartbeat import HeartbeatMonitor

    prev_resilience = configure_collective_resilience(
        timeout_s=params.collective_timeout_s
    )
    # collective strategy (docs/PARALLEL.md): trace-time env state —
    # pin process-wide before any solve traces
    if params.collective_mode is not None:
        from photon_ml_tpu.parallel.overlap import COLLECTIVE_MODE_ENV

        os.environ[COLLECTIVE_MODE_ENV] = params.collective_mode
    monitor = None
    if params.heartbeat_s > 0:
        monitor = HeartbeatMonitor(interval_s=params.heartbeat_s).start()
        install_monitor(monitor)
        logger.info(
            f"pod heartbeat monitor: every {params.heartbeat_s}s over "
            f"{monitor.process_count} process(es)"
        )
    try:
        with obs.observe(
            trace_dir=params.trace_dir,
            metrics_path=metrics_path,
            metrics_every=params.metrics_every,
            profile_dir=params.profile_dir,
            hbm_every_s=params.hbm_every,
            process_name="photon_ml_tpu.game_train",
            flight_dir=params.flight_dir,
        ):
            return _run_game_training(params, logger, shutdown)
    finally:
        if params.quality_fingerprint:
            # idempotent: normally uninstalled right after train ingest;
            # covers the ingest-raised path so no collector leaks
            obs.quality.uninstall_fingerprint_collector()
        configure_collective_resilience(
            prev_resilience.timeout_s, prev_resilience.retries
        )
        if monitor is not None:
            install_monitor(None)
            monitor.stop()
        if conv_tracker is not None:
            try:
                path = conv_tracker.dump(
                    os.path.join(
                        params.output_dir, "convergence-report.json"
                    )
                )
                logger.info(f"wrote convergence report to {path}")
            except OSError:
                pass
            obs.uninstall_convergence_tracker()
        shutdown.uninstall()
        logger.close()


def _current_heartbeat():
    """The process-wide heartbeat monitor installed by the resilience
    envelope in :func:`run_game_training` (None when heartbeat_s = 0)."""
    from photon_ml_tpu.parallel import current_monitor

    return current_monitor()


def _run_game_training(
    params: GameDriverParams, logger: PhotonLogger, shutdown
) -> GameTrainingRun:
    from photon_ml_tpu.cli.train import driver_dtype, log_driver_runtime

    task = TaskType[params.task]
    dtype = driver_dtype(params.precision)
    logger.info(
        f"GAME training driver: task={params.task} "
        f"sequence={params.updating_sequence} iters={params.num_iterations}"
    )

    # ---- multi-process runtime (the reference's fake-cluster / YARN
    # regimes, ``DriverGameIntegTest.scala:343-400``): join when
    # configured; each process ingests its file split, designs assemble
    # into mesh-global arrays -------------------------------------------
    from photon_ml_tpu.parallel import initialize_multihost

    initialize_multihost()  # no-op when unconfigured / already joined
    log_driver_runtime(logger, params.precision)
    # gate on process_count alone: a launcher may have initialized the
    # distributed runtime itself, and a False here while process_count>1
    # would make every process silently ingest the FULL input
    multi = jax.process_count() > 1
    if multi:
        _validate_multiprocess_params(params)
        # the runtime usually joined BEFORE the observe() envelope
        # installed this process's tracer (cli main joins first, by
        # design), so re-emit the barrier-stamped clock.sync here where
        # the tracer can record it — the anchor `photon-obs merge`
        # aligns the per-host shards on
        from photon_ml_tpu.parallel.multihost import emit_pod_sync

        emit_pod_sync()

    # ---- prepare feature maps + dataset ---------------------------------
    # quality fingerprint (docs/OBSERVABILITY.md "Quality & drift"): the
    # io paths feed the installed collector per-shard per ingest chunk;
    # installed for the TRAIN ingest only (validation rows are a
    # different distribution and must not blur the baseline)
    from photon_ml_tpu.obs import quality as quality_mod

    fingerprint = None
    if params.quality_fingerprint:
        fingerprint = quality_mod.install_fingerprint_collector()
    with timed(logger, "prepare data"):
        from photon_ml_tpu.io.ingest import IngestSource

        date_range = resolve_date_range(params)
        train_paths = expand_date_paths(params.train_input, date_range)
        if multi:
            from photon_ml_tpu.parallel import process_local_paths

            train_paths = process_local_paths(train_paths)
        source = IngestSource(train_paths, params.field_names)

        shard_ids = {
            spec.shard for spec in params.coordinates.values()
        }
        shard_vocabs: Dict[str, FeatureVocabulary] = {}
        fallback_shards = []
        fallback_vocab = None
        for shard in shard_ids:
            feature_file = params.feature_shards.get(shard)
            if feature_file:
                shard_vocabs[shard] = FeatureVocabulary.load(feature_file)
            else:
                fallback_shards.append(shard)
                if fallback_vocab is None:
                    fallback_vocab = source.build_vocab(
                        add_intercept=params.add_intercept
                    )
                shard_vocabs[shard] = fallback_vocab
        if multi and fallback_shards:
            raise ValueError(
                f"multi-process GAME requires a feature_shards file for "
                f"every shard (got none for {sorted(fallback_shards)}): "
                "the from-records fallback vocabulary is built from each "
                "process's local rows and would diverge across processes"
            )
        if len(fallback_shards) > 1:
            # The from-records fallback is the FULL feature space, so these
            # shards collapse into identical bags — unlike the reference's
            # partitioned feature sections. Surface it loudly.
            logger.warn(
                f"shards {sorted(fallback_shards)} have no feature_shards "
                "file and all fall back to the full from-records vocabulary; "
                "they will share an identical feature space"
            )
        entity_keys = sorted(
            {
                spec.random_effect
                for spec in params.coordinates.values()
                if spec.random_effect is not None
            }
        )
        if params.streamed_ingest:
            # bounded parallel decode through the ingest pipeline —
            # identical GameData to the one-shot read (docs/INGEST.md)
            data, entity_vocabs, _uids, _present = (
                source.game_data_streamed(
                    shard_vocabs, entity_keys,
                    sparse_shards=set(params.sparse_shards),
                    chunk_mb=params.ingest_chunk_mb,
                    decode_threads=params.decode_threads,
                    prefetch_depth=params.prefetch_depth,
                    stage_timeout_s=params.stage_timeout_s,
                    epoch_policy=params.epoch_policy,
                )
            )
        else:
            data, entity_vocabs, _uids, _present = source.game_data(
                shard_vocabs, entity_keys,
                sparse_shards=set(params.sparse_shards),
            )
        logger.info(f"read {len(data.labels)} training records")
        if fingerprint is not None:
            # train ingest done — stop collecting before validation io
            quality_mod.uninstall_fingerprint_collector()
            logger.info(
                f"quality fingerprint: {fingerprint.rows} rows sketched "
                f"over shards {sorted(fingerprint.shards)}"
            )
        entity_counts = {k: len(v) for k, v in entity_vocabs.items()}
        logger.info(
            f"shards: { {s: len(v) for s, v in shard_vocabs.items()} } "
            f"entities: {entity_counts}"
        )

        multiproc = None
        if multi:
            from photon_ml_tpu.parallel import (
                allgather_host,
                allgather_strings,
                global_entity_space,
                make_mesh,
            )

            mesh = make_mesh()  # every device across every process
            n_local = data.num_rows
            n_all = allgather_host(np.asarray([n_local], np.int64))
            n_target = (
                -(-int(n_all.max()) // jax.local_device_count())
                * jax.local_device_count()
            )
            data = _pad_game_data(data, n_target)
            row_base = n_target * jax.process_index()
            local_entity_counts = dict(entity_counts)
            entity_spaces = {
                k: global_entity_space(c)
                for k, c in sorted(entity_counts.items())
            }
            entity_counts = {k: es[0] for k, es in entity_spaces.items()}
            # globalize entity vocabularies: each process indexed ITS
            # entities 0..E_p-1; the global table row for raw id r on
            # process p is entity_base_p + local index
            for k in sorted(entity_vocabs):
                vocab = entity_vocabs[k]
                all_raw = allgather_strings(_ordered_entity_ids(k, vocab))
                if len(set(all_raw)) != len(all_raw):
                    from collections import Counter

                    dups = [
                        r for r, c in Counter(all_raw).items() if c > 1
                    ]
                    raise ValueError(
                        f"random effect {k!r}: entity ids "
                        f"{sorted(dups)[:5]}{'...' if len(dups) > 5 else ''}"
                        f" appear on more than one process — multi-process"
                        " GAME requires ENTITY-PARTITIONED input splits "
                        "(every entity's rows in exactly one process's "
                        "files), like the reference's "
                        "RandomEffectIdPartitioner placement"
                    )
                entity_vocabs[k] = {r: i for i, r in enumerate(all_raw)}
            multiproc = {
                "mesh": mesh,
                "row_base": row_base,
                "entity_spaces": entity_spaces,
                "local_entity_counts": local_entity_counts,
            }
            logger.info(
                f"multi-process GAME: {jax.process_count()} processes x "
                f"{jax.local_device_count()} local devices; "
                f"rows/process {n_target} (padded from {n_local}), "
                f"global entities {entity_counts}"
            )

        vdata = None
        if params.validate_input:
            vdata, _, _, _ = IngestSource(
                expand_date_paths(params.validate_input, date_range),
                params.field_names,
            ).game_data(
                shard_vocabs, entity_keys, entity_vocabs=entity_vocabs,
                sparse_shards=set(params.sparse_shards),
            )
            logger.info(f"read {len(vdata.labels)} validation records")

    # ---- entity-sharded layout (docs/PARALLEL.md) -----------------------
    entity_sharded = None
    if params.entity_shards > 1:
        if multi:
            raise ValueError(
                "entity_shards is the single-process entity mesh; "
                "multi-process runs shard entities via the multiproc "
                "path (one process per host)"
            )
        if params.entity_shards > jax.device_count():
            raise ValueError(
                f"entity_shards={params.entity_shards} exceeds "
                f"{jax.device_count()} visible devices"
            )
        from photon_ml_tpu.game import entity_shard_layouts
        from photon_ml_tpu.parallel.mesh import make_entity_mesh

        es_mesh = make_entity_mesh(
            params.entity_shards,
            devices=jax.devices()[: params.entity_shards],
        )
        # one layout step a random effect, in update order: the first
        # one's partition is the canonical row order (``data`` from here
        # on), every other gets its own partition and the exchange plan
        # to and from the canonical one
        sharded_res = {}
        for name in params.updating_sequence:
            spec = params.coordinates[name]
            if spec.random_effect is not None:
                sharded_res.setdefault(spec.random_effect, set()).add(
                    spec.shard
                )
        layouts = entity_shard_layouts(
            data,
            {re_key: entity_counts[re_key] for re_key in sharded_res},
            params.entity_shards,
            sharded_res,
        )
        for re_key, layout in layouts.items():
            plan = layout.partition.exchange
            logger.info(
                f"entity-sharded descent, {re_key}: "
                f"{params.entity_shards} shards, "
                f"{layout.assignment.rows_per_shard} entities/shard, "
                f"{layout.partition.rows_per_shard} rows/shard, "
                + (
                    "the canonical row order"
                    if plan is None
                    else f"exchange blocks of {plan.block_rows} rows"
                )
            )
        data = next(iter(layouts.values())).data
        entity_sharded = {"mesh": es_mesh, "layouts": layouts}

    # ---- grid sweep ------------------------------------------------------
    shards_by_coord = {
        n: params.coordinates[n].shard for n in params.updating_sequence
    }
    res_by_coord = {
        n: params.coordinates[n].random_effect
        for n in params.updating_sequence
    }
    # entity-keyed checkpoint shards (docs/MULTIHOST.md): each random-
    # effect coordinate's table rows are labeled with the ordered entity
    # ids of its (globalized) vocabulary, so a sharded checkpoint can be
    # restored onto a different process count or entity order by KEY
    ckpt_entity_keys = None
    if params.sharded_ckpt:
        ckpt_entity_keys = {}
        for n, re_key in res_by_coord.items():
            if re_key is None:
                continue
            vocab = entity_vocabs[re_key]
            ordered = [None] * len(vocab)
            for raw, i in vocab.items():
                ordered[i] = raw
            if entity_sharded is not None:
                # the device table is stored SHARD-MAJOR (padded); label
                # its rows in that order so checkpoint shards carry the
                # keys the restore re-keys by (pad rows keyed uniquely)
                ordered = entity_sharded["layouts"][
                    re_key
                ].assignment.stored_entity_keys(ordered)
            ckpt_entity_keys[n] = ordered

    def validation_metric(model: GameModel) -> float:
        margins = score_game_data(
            model.params, shards_by_coord, res_by_coord, vdata
        ) + jnp.asarray(vdata.offsets)
        labels = jnp.asarray(vdata.labels)
        weights = jnp.asarray(vdata.weights)
        if task.is_classifier:
            return float(
                metrics_mod.area_under_roc_curve(labels, margins, weights)
            )
        if task == TaskType.POISSON_REGRESSION:
            return -float(
                metrics_mod.total_poisson_loss(labels, margins, weights)
            )
        return -float(
            metrics_mod.root_mean_squared_error(labels, margins, weights)
        )

    # warm-start tables from a previously saved model
    # (``ModelTraining.scala:95-141``'s warm-start semantics on the GAME
    # driver): rows remap by raw entity id into THIS run's entity vocab
    warm_params: Dict[str, np.ndarray] = {}
    if params.initial_model_dir:
        from photon_ml_tpu.io.models import load_game_model

        coord_vocabs = {
            n: shard_vocabs[shards_by_coord[n]]
            for n in params.updating_sequence
        }
        init_evocabs = {
            n: entity_vocabs[res_by_coord[n]]
            for n in params.updating_sequence
            if res_by_coord[n] is not None
        }
        loaded, _, _, _ = load_game_model(
            params.initial_model_dir, coord_vocabs, init_evocabs
        )
        for n, p in loaded.items():
            if n in params.coordinates:
                warm_params[n] = p
        logger.info(
            f"warm-starting coordinates {sorted(warm_params)} from "
            f"{params.initial_model_dir}"
        )

    sweep: List[dict] = []
    design_cache: Dict[str, object] = {}
    grid_combos = list(params.grid())
    # Hyperparameter parallelism (SURVEY §2.5.6): grid entries share
    # every shape — only reg weights differ — so when warm starts /
    # per-update validation / checkpointing aren't in play, ALL combos
    # train simultaneously through one vmapped sweep instead of
    # sequential runs (``descent.run_grid``).
    from photon_ml_tpu.ops import sparse as _sparse_ops

    vmappable = (
        len(grid_combos) > 1
        and params.entity_shards <= 1
        and vdata is None
        and not warm_params
        and params.checkpoint_every <= 0
        and multiproc is None
        # the guard needs per-update host objectives; lanes can't branch
        and not params.divergence_guard
        # coordinate kinds are statically known from the specs: factored
        # (latent_dim), projected (projector), and sparse-projected
        # coordinates don't expose fused_state_for_reg — decide BEFORE
        # paying a full build that the hasattr check would throw away
        and all(
            spec.latent_dim is None
            and not spec.projector
            and not (
                spec.random_effect is not None
                and _sparse_ops.is_sparse(data.features[spec.shard])
            )
            for spec in params.coordinates.values()
        )
    )
    if vmappable:
        coords = build_coordinates(
            params, data, task, grid_combos[0], entity_counts,
            dtype=dtype, shard_vocabs=shard_vocabs,
            design_cache=design_cache,
        )
        vmappable = all(
            hasattr(c, "fused_state_for_reg") for c in coords.values()
        )
        if vmappable:
            from photon_ml_tpu.game.descent import run_grid

            with timed(
                logger, f"train grid x{len(grid_combos)} (vmapped)"
            ):
                cd = CoordinateDescent(
                    coordinates=coords,
                    labels=jnp.asarray(data.labels, dtype),
                    base_offsets=jnp.asarray(data.offsets, dtype),
                    weights=jnp.asarray(data.weights, dtype),
                    task=task,
                )
                models, histories = run_grid(
                    cd, grid_combos, params.num_iterations
                )
            for combo, model, hist in zip(grid_combos, models, histories):
                for h in hist:
                    logger.info(
                        f"combo={combo} iter={h.iteration} "
                        f"coord={h.coordinate} "
                        f"objective={h.objective:.6g}"
                    )
                sweep.append(
                    {
                        "combo": combo,
                        "model": materialize_original_space(model, coords),
                        "history": hist,
                        "validation_metric": None,
                    }
                )
    seq_combos = [] if vmappable else grid_combos
    for combo_index, combo in enumerate(seq_combos):
        with timed(logger, f"train combo {combo}"):
            coords = build_coordinates(
                params, data, task, combo, entity_counts, dtype=dtype,
                shard_vocabs=shard_vocabs, design_cache=design_cache,
                multiproc=multiproc, entity_sharded=entity_sharded,
            )
            initial_model = None
            if warm_params:
                init = {}
                for n in params.updating_sequence:
                    p = warm_params.get(n)
                    coord = coords[n]
                    from photon_ml_tpu.game import (
                        EntityShardedRandomEffectCoordinate as _ESRE,
                    )

                    plain_coord = not isinstance(
                        coord,
                        (ProjectedRandomEffectCoordinate,
                         IndexMapRandomEffectCoordinate),
                    ) and not hasattr(coord, "factored")
                    if (
                        p is not None
                        and not hasattr(p, "gamma")
                        and isinstance(coord, _ESRE)
                    ):
                        # global-order saved table -> stored shard-major
                        # layout, placed entity-sharded
                        stored = coord.assignment.table_from_global(
                            np.asarray(p, dtype)
                        )
                        init[n] = jax.device_put(
                            jnp.asarray(stored),
                            coord.initial_params().sharding,
                        )
                        continue
                    if p is not None and not hasattr(p, "gamma") and plain_coord:
                        init[n] = jnp.asarray(np.asarray(p), dtype)
                        continue
                    if (
                        p is not None
                        and hasattr(p, "gamma")
                        and hasattr(coord, "factored")
                        and np.asarray(p.gamma).shape[1]
                        == coord.factored.latent_dim
                    ):
                        init[n] = type(p)(
                            gamma=jnp.asarray(np.asarray(p.gamma), dtype),
                            projection=jnp.asarray(
                                np.asarray(p.projection), dtype
                            ),
                        )
                        continue
                    if p is not None:
                        logger.warn(
                            f"coordinate {n}: saved params do not match the "
                            "coordinate kind/latent dim; cold-starting it"
                        )
                    init[n] = coord.initial_params()
                initial_model = GameModel(params=init)
            if multiproc is not None:
                from photon_ml_tpu.parallel import make_global_array

                _mk = lambda x: make_global_array(
                    np.asarray(x, dtype), multiproc["mesh"]
                )
                labels_arr = _mk(data.labels)
                offsets_arr = _mk(data.offsets)
                weights_arr = _mk(data.weights)
            elif entity_sharded is not None:
                from photon_ml_tpu.parallel.mesh import batch_sharding

                _mesh = entity_sharded["mesh"]
                _put = lambda x: jax.device_put(
                    jnp.asarray(x, dtype), batch_sharding(_mesh, 1)
                )
                labels_arr = _put(data.labels)
                offsets_arr = _put(data.offsets)
                weights_arr = _put(data.weights)
            else:
                labels_arr = jnp.asarray(data.labels, dtype)
                offsets_arr = jnp.asarray(data.offsets, dtype)
                weights_arr = jnp.asarray(data.weights, dtype)
            cd = CoordinateDescent(
                coordinates=coords,
                labels=labels_arr,
                base_offsets=offsets_arr,
                weights=weights_arr,
                task=task,
            )
            # validation (like persistence) always sees original-space
            # coefficients; projected tables are back-projected first
            vfn = (
                (
                    lambda model, _coords=coords: validation_metric(
                        materialize_original_space(model, _coords)
                    )
                )
                if (vdata is not None and params.validate_per_coordinate)
                else None
            )
            # keyed by grid INDEX: reg-weight strings are not unique
            # (duplicate weights are supported sweep candidates)
            ckpt_dir = (
                os.path.join(
                    params.output_dir, "checkpoints", f"combo-{combo_index}"
                )
                if params.checkpoint_every > 0
                else None
            )
            model, history = cd.run(
                params.num_iterations,
                initial_model=initial_model,
                validation_fn=vfn,
                checkpoint_dir=ckpt_dir,
                checkpoint_every=max(params.checkpoint_every, 1),
                resume=params.resume,
                divergence_guard=params.divergence_guard,
                # polled at pass boundaries: SIGTERM/SIGINT finishes the
                # pass, checkpoints, and falls through to the break below
                stop_check=shutdown,
                # device-resident multi-pass descent: K passes per
                # dispatch with in-program convergence/guard detection
                # (checkpoints + preemption land on dispatch boundaries)
                passes_per_dispatch=params.passes_per_dispatch,
                convergence_tolerance=params.convergence_tolerance,
                # pod resilience (docs/MULTIHOST.md): per-process shard
                # writes + entity-keyed restore, and the pass-boundary
                # heartbeat poll that turns a dead peer into a final
                # shard set + distinct exit instead of a hang
                sharded_checkpoints=params.sharded_ckpt,
                entity_keys=ckpt_entity_keys,
                heartbeat=_current_heartbeat(),
                # lifecycle retrain: convergence-healthy coordinates
                # carry their warm start bit-identical (never updated)
                freeze=params.freeze_coordinates or None,
            )
            frozen_events = [
                h for h in history if getattr(h, "event", None) == "frozen"
            ]
            for h in frozen_events:
                logger.warn(
                    f"combo={combo} iter={h.iteration} coordinate "
                    f"{h.coordinate!r} FROZEN by the divergence guard; "
                    "remaining coordinates kept training"
                )
            for h in history:
                logger.info(
                    f"combo={combo} iter={h.iteration} coord={h.coordinate} "
                    f"objective={h.objective:.6g}"
                    + (
                        f" validation={h.validation_metric:.6g}"
                        if h.validation_metric is not None
                        else ""
                    )
                    + (
                        f" ({h.seconds:.2f}s/pass)"
                        if h.seconds is not None
                        else ""
                    )
                )
            if multiproc is not None:
                # every process fetches the identical full host params
                # (global shards reshard to replicated first), so model
                # writers below need no process gating
                from photon_ml_tpu.parallel import fetch_replicated

                model = GameModel(
                    {
                        n: jax.tree_util.tree_map(
                            lambda a: np.asarray(fetch_replicated(a)), p
                        )
                        for n, p in model.params.items()
                    }
                )
            model = materialize_original_space(model, coords)
            if vfn is not None:
                final_metric = history[-1].validation_metric
            elif vdata is not None:
                final_metric = validation_metric(model)
            else:
                final_metric = None
            sweep.append(
                {
                    "combo": combo,
                    "model": model,
                    "history": history,
                    "validation_metric": final_metric,
                }
            )
            if shutdown.requested:
                logger.warn(
                    f"preempted during combo {combo}: final checkpoint + "
                    f"resumable marker written under {ckpt_dir}; re-run "
                    "with resume=true to continue"
                )
                break

    # best = highest validation metric (metrics are oriented so higher is
    # better); without validation data the last combo wins, like the
    # reference's fallback
    if vdata is not None:
        best_index = int(
            np.argmax([s["validation_metric"] for s in sweep])
        )
    else:
        best_index = len(sweep) - 1
    logger.info(
        f"best combo: {sweep[best_index]['combo']} "
        f"(validation={sweep[best_index]['validation_metric']})"
    )

    # ---- save models (``Driver.scala:393-441`` output modes) ------------
    # Multi-process: every process holds the identical fetched model, but
    # processes typically share one output_dir — concurrent
    # open-truncate-writes of the same files race, so only process 0
    # writes (the others return the same in-memory GameTrainingRun).
    # A preempted run saves nothing: its durable artifact is the
    # checkpoint + marker, and the resumed run does the saving.
    save_process = (
        (not multi) or jax.process_index() == 0
    ) and not shutdown.requested
    output_dirs: List[str] = []
    with timed(logger, "save models"):
        if (
            fingerprint is not None
            and fingerprint.rows > 0
            and save_process
        ):
            # margin sketch: the best model's score distribution over
            # its own training rows (offsets included — the space
            # serving scores live in); one scoring pass, the baseline
            # the serving DriftMonitor compares live scores against
            margins = score_game_data(
                sweep[best_index]["model"].params,
                shards_by_coord,
                res_by_coord,
                data,
                dtype=dtype,
            ) + jnp.asarray(data.offsets, dtype)
            fingerprint.observe_margins(
                np.asarray(margins), np.asarray(data.weights)
            )
        to_save: List[int] = []
        if not save_process:
            pass  # non-zero process: model already fetched, writes skipped
        elif params.model_output_mode == "BEST":
            to_save = [best_index]
        elif params.model_output_mode == "ALL":
            to_save = list(range(len(sweep)))
        for rank, idx in enumerate(to_save):
            entry = sweep[idx]
            subdir = (
                os.path.join(params.output_dir, "best")
                if params.model_output_mode == "BEST"
                else os.path.join(params.output_dir, "all", str(idx))
            )
            save_params = {
                # FactoredParams pass through whole (latent wire format),
                # per-entity lists as they are
                n: p if hasattr(p, "gamma") or isinstance(
                    p, CompactReTable) else np.asarray(p)
                for n, p in entry["model"].params.items()
            }
            save_shards = shards_by_coord
            save_res = res_by_coord
            save_evocabs = {
                n: entity_vocabs[res_by_coord[n]]
                for n in params.updating_sequence
                if res_by_coord[n] is not None
            }
            if params.collapse_output:
                from photon_ml_tpu.io.models import collapse_game_model

                save_params, save_shards, save_res, save_evocabs = (
                    collapse_game_model(
                        save_params, save_shards, save_res, save_evocabs
                    )
                )
                logger.info(
                    f"collapsed to coordinates {sorted(save_params)}"
                )
            save_game_model(
                subdir,
                params=save_params,
                shards=save_shards,
                vocabs={
                    n: shard_vocabs[save_shards[n]] for n in save_params
                },
                entity_vocabs=save_evocabs,
                random_effects=save_res,
                task=task,
            )
            with open(os.path.join(subdir, "model-spec.json"), "w") as f:
                json.dump(
                    {
                        "combo": entry["combo"],
                        "validation_metric": entry["validation_metric"],
                        "task": params.task,
                        "updating_sequence": params.updating_sequence,
                    },
                    f,
                    indent=2,
                )
            if fingerprint is not None and fingerprint.rows > 0:
                # written BEFORE write_model_manifest below, so the
                # baseline is covered by the export's integrity digest
                # and hot-reloads atomically with the model
                fingerprint.save(subdir)
            output_dirs.append(subdir)
        if save_process:
            for shard, vocab in shard_vocabs.items():
                vocab.save(
                    os.path.join(
                        params.output_dir, f"feature-index-{shard}.txt"
                    )
                )
        if save_process and output_dirs:
            # sha256 manifest over the whole export (models + vocabs): the
            # serving registry verifies it before hot-reloading, so a
            # partially-written or tampered export can never serve
            from photon_ml_tpu.io.models import write_model_manifest

            write_model_manifest(params.output_dir)

    return GameTrainingRun(
        params=params,
        shard_vocabs=shard_vocabs,
        entity_vocabs=entity_vocabs,
        sweep=sweep,
        best_index=best_index,
        output_dirs=output_dirs,
    )


def main(argv=None) -> None:
    p = argparse.ArgumentParser(
        prog="photon_ml_tpu.cli.game_train",
        description="Train GAME (fixed + random effects) models.",
    )
    p.add_argument("--config", required=True, help="JSON GameDriverParams")
    p.add_argument("--overwrite", action="store_true", default=None)
    p.add_argument(
        "--trace-dir", default=None,
        help="emit a Chrome trace-event JSON + events.jsonl + metrics.json "
        "under this directory (docs/OBSERVABILITY.md)",
    )
    p.add_argument(
        "--metrics-every", type=float, default=None,
        help="seconds between periodic metrics.json registry snapshots "
        "(0 = final snapshot only)",
    )
    p.add_argument(
        "--profile-dir", default=None,
        help="capture a jax.profiler trace of the run here",
    )
    p.add_argument(
        "--hbm-every", type=float, default=None,
        help="seconds between live HBM counter-track samples while "
        "tracing (0 disables; no-op without device memory stats)",
    )
    p.add_argument(
        "--flight-dir", default=None,
        help="crash flight recorder output directory: flight-<reason>"
        ".json dumps on divergence/preemption/crash (default: "
        "--trace-dir)",
    )
    p.add_argument(
        "--convergence-report", action="store_true", default=None,
        help="decode the solvers' device-side tapes: per-coordinate "
        "fleet convergence summaries every pass (convergence.* metrics "
        "+ events) and <output-dir>/convergence-report.json",
    )
    p.add_argument(
        "--passes-per-dispatch", type=int, default=None,
        help="device-resident multi-pass descent: run up to K "
        "coordinate-descent passes per XLA dispatch (ceil(P/K) "
        "dispatches for P passes; K caps the checkpoint granularity)",
    )
    p.add_argument(
        "--convergence-tolerance", type=float, default=None,
        help="with K > 1: in-program objective-tolerance early exit "
        "between passes (0 disables)",
    )
    p.add_argument(
        "--streamed-ingest", action="store_true", default=None,
        help="decode the training input through the streaming ingest "
        "pipeline (bounded parallel decode — docs/INGEST.md)",
    )
    p.add_argument(
        "--ingest-chunk-mb", type=float, default=None,
        help="ingest pipeline: target decoded-chunk size in MB "
        "(default 64)",
    )
    p.add_argument(
        "--decode-threads", type=int, default=None,
        help="ingest pipeline: concurrent decode workers (0 = auto; "
        "PHOTON_DECODE_THREADS override honored)",
    )
    p.add_argument(
        "--prefetch-depth", type=int, default=None,
        help="ingest pipeline: chunks decode may run ahead of the "
        "consumer (default 2)",
    )
    p.add_argument(
        "--stage-timeout-s", type=float, default=None,
        help="ingest pipeline watchdog: cancel+retry a decode attempt "
        "stalled past this many seconds (default: off)",
    )
    p.add_argument(
        "--epoch-policy", choices=["fail", "skip"], default=None,
        help="exhausted ingest retries: fail the run (default) or "
        "skip-and-log the lost group (docs/ROBUSTNESS.md)",
    )
    p.add_argument(
        "--heartbeat-s", type=float, default=None,
        help="pod heartbeat interval in seconds (0 = off): a peer "
        "missing 3 intervals is declared lost — survivors write a "
        "final checkpoint shard set and exit with the distinct "
        "host-loss code (docs/MULTIHOST.md)",
    )
    p.add_argument(
        "--collective-timeout-s", type=float, default=None,
        help="watchdog deadline on host-side collectives: a stalled "
        "exchange times out, retries with backoff, and emits straggler "
        "attribution instead of wedging the pod (default: no watchdog)",
    )
    p.add_argument(
        "--sharded-ckpt", action="store_true", default=None,
        help="per-process sharded checkpoints: each process writes "
        "shard-<p>-of-<P> + process 0 publishes a quorum manifest; "
        "entity-keyed shards restore onto a different world size "
        "(required for checkpointing on a pod — docs/MULTIHOST.md)",
    )
    p.add_argument(
        "--no-quality-fingerprint", dest="quality_fingerprint",
        action="store_false", default=None,
        help="skip the train-data quality fingerprint "
        "(quality-fingerprint.json in every export subdir — the "
        "serving drift-detection baseline; docs/OBSERVABILITY.md)",
    )
    p.add_argument(
        "--entity-shards", type=int, default=None,
        help="entity-sharded GAME descent over an N-device 'entity' "
        "mesh (shard_map: the random-effect table, bucket lanes, and "
        "entity-partitioned rows all shard; ZERO collectives in the "
        "random-effect update — docs/PARALLEL.md). 0/1 = off",
    )
    p.add_argument(
        "--collective-mode", choices=("fused", "overlap"), default=None,
        help="collective reduction strategy (docs/PARALLEL.md): "
        "'overlap' (default) = row-balanced blocking + chunked "
        "reduce-scatter/all-gather pipeline; 'fused' = the single "
        "trailing all-reduce equivalence oracle",
    )
    p.add_argument(
        "--warm-from-watch-root", default=None, metavar="DIR",
        help="lifecycle warm start: resolve initial_model_dir to the "
        "newest manifest-bearing export under this serving watch root "
        "(entity-keyed warm start from whatever is live — "
        "docs/LIFECYCLE.md; photon-retrain drives this automatically)",
    )
    args = p.parse_args(argv)
    # after parse_args: --help / bad flags must not initialize
    # the accelerator backend or touch the cache directory.
    # JOIN FIRST: jax.distributed.initialize must run before anything
    # touches the backend
    from photon_ml_tpu.parallel import initialize_multihost
    from photon_ml_tpu.utils import enable_compilation_cache

    initialize_multihost()
    enable_compilation_cache()
    with open(args.config) as f:
        base = json.load(f)
    if args.overwrite is not None:
        base["overwrite"] = args.overwrite
    if args.trace_dir is not None:
        base["trace_dir"] = args.trace_dir
    if args.metrics_every is not None:
        base["metrics_every"] = args.metrics_every
    if args.profile_dir is not None:
        base["profile_dir"] = args.profile_dir
    if args.hbm_every is not None:
        base["hbm_every"] = args.hbm_every
    if args.flight_dir is not None:
        base["flight_dir"] = args.flight_dir
    if args.convergence_report is not None:
        base["convergence_report"] = args.convergence_report
    if args.passes_per_dispatch is not None:
        base["passes_per_dispatch"] = args.passes_per_dispatch
    if args.convergence_tolerance is not None:
        base["convergence_tolerance"] = args.convergence_tolerance
    if args.streamed_ingest is not None:
        base["streamed_ingest"] = args.streamed_ingest
    if args.ingest_chunk_mb is not None:
        base["ingest_chunk_mb"] = args.ingest_chunk_mb
    if args.decode_threads is not None:
        base["decode_threads"] = args.decode_threads
    if args.prefetch_depth is not None:
        base["prefetch_depth"] = args.prefetch_depth
    if args.stage_timeout_s is not None:
        base["stage_timeout_s"] = args.stage_timeout_s
    if args.epoch_policy is not None:
        base["epoch_policy"] = args.epoch_policy
    if args.heartbeat_s is not None:
        base["heartbeat_s"] = args.heartbeat_s
    if args.collective_timeout_s is not None:
        base["collective_timeout_s"] = args.collective_timeout_s
    if args.sharded_ckpt is not None:
        base["sharded_ckpt"] = args.sharded_ckpt
    if args.quality_fingerprint is not None:
        base["quality_fingerprint"] = args.quality_fingerprint
    if args.entity_shards is not None:
        base["entity_shards"] = args.entity_shards
    if args.collective_mode is not None:
        base["collective_mode"] = args.collective_mode
    if args.warm_from_watch_root is not None:
        from photon_ml_tpu.lifecycle.orchestrator import (
            latest_version_dir,
        )

        warm = latest_version_dir(args.warm_from_watch_root)
        if warm is None:
            p.error(
                "--warm-from-watch-root: no manifest-bearing export "
                f"under {args.warm_from_watch_root}"
            )
        base["initial_model_dir"] = warm
    try:
        run_game_training(base)
    except BaseException as e:
        from photon_ml_tpu.resilience import (
            HOST_LOSS_EXIT_CODE,
            is_host_loss,
        )

        # host loss has a DISTINCT exit contract: the final shard set is
        # on disk, so a cluster manager should restart (same or smaller
        # world size) rather than treat this as a code failure
        if is_host_loss(e):
            print(
                f"host loss: {e} — exiting {HOST_LOSS_EXIT_CODE} "
                "(restart resumes from the sharded checkpoint)",
                file=sys.stderr,
            )
            sys.exit(HOST_LOSS_EXIT_CODE)
        raise


if __name__ == "__main__":
    main()
