"""Typed driver configuration.

One typed config system replacing the reference's three tiers (SURVEY §5.6):
scopt CLI flags (``PhotonMLCmdLineParser.scala``, ``Params.scala:36-183``),
the per-coordinate string mini-DSLs
(``GLMOptimizationConfiguration.scala:32-80``,
``RandomEffectDataConfiguration.scala:71-118``), and the GAME grid arrays
(semicolon-separated configs cartesian-multiplied at
``cli/game/training/Driver.scala:317-384``). Semantics preserved — grids,
updating sequences, output modes — as dataclasses loadable from JSON, with
every knob also overridable as a CLI flag by the driver mains.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Dict, List, Optional, Sequence, Tuple

from photon_ml_tpu.core.normalization import NormalizationType
from photon_ml_tpu.core.tasks import TaskType
from photon_ml_tpu.core.validators import DataValidationType
from photon_ml_tpu.models.training import GLMTrainingConfig, OptimizerType
from photon_ml_tpu.ops.objective import RegularizationContext

MODEL_OUTPUT_MODES = ("ALL", "BEST", "NONE")


def _validate_pod_resilience(params) -> None:
    """Shared knob validation for the multi-host resilience surface
    (both drivers carry the same three fields — docs/MULTIHOST.md)."""
    if params.heartbeat_s < 0:
        raise ValueError(
            f"heartbeat_s must be >= 0 (0 = off), got {params.heartbeat_s}"
        )
    if (
        params.collective_timeout_s is not None
        and params.collective_timeout_s <= 0
    ):
        raise ValueError(
            f"collective_timeout_s must be > 0 (or null = no watchdog), "
            f"got {params.collective_timeout_s}"
        )


@dataclasses.dataclass
class GLMDriverParams:
    """Core GLM train-driver knobs (``Params.scala:36-183``)."""

    train_input: List[str]
    output_dir: str
    task: str = "LOGISTIC_REGRESSION"
    optimizer: str = "LBFGS"
    reg_type: str = "L2"
    reg_weights: List[float] = dataclasses.field(default_factory=lambda: [1.0])
    elastic_net_alpha: float = 0.5
    normalization: str = "NONE"
    max_iters: int = 80
    tolerance: float = 1e-7
    add_intercept: bool = True
    sparse: bool = False
    # stream the (dense) dataset to the device through the ingest
    # pipeline (io.pipeline: parallel decode, ring staging, async
    # prefetch) — host decode / host->device transfer / compile
    # overlap, and peak host memory is the staging ring instead of the
    # whole dataset (docs/INGEST.md)
    streamed_ingest: bool = False
    # OUT-OF-CORE training: the design exceeds HBM. Decode+stage once
    # into host-resident chunks and stream every objective pass through
    # the fused per-chunk programs (models.training.train_glm_streamed;
    # exact full-dataset objective, <=1e-10 vs in-core). Requires
    # normalization NONE, dense features, TRON/LBFGS, single device.
    out_of_core: bool = False
    # ingest-pipeline knobs (docs/INGEST.md): target decoded-chunk MB
    # (file-group planning + uniform staged row blocks), decode workers
    # (0 = auto, PHOTON_DECODE_THREADS honored), and how many chunks
    # decode/staging may run ahead of the consumer
    ingest_chunk_mb: float = 64.0
    decode_threads: int = 0
    prefetch_depth: int = 2
    # pipeline supervision (docs/ROBUSTNESS.md): per-stage watchdog
    # deadline in seconds (a decode/stage/transfer attempt stalled past
    # it is cancelled and re-run through the retry seam; 0/None = off),
    # and what an EXHAUSTED retry budget does to the epoch — "fail"
    # raises, "skip" logs+counts the lost group and continues
    stage_timeout_s: Optional[float] = None
    epoch_policy: str = "fail"
    # with sparse=True: densify the hottest columns into an MXU slab and
    # keep only the power-law tail in the ELL scatter path (ops.sparse
    # HybridFeatures). 0 = off, -1 = auto (count-threshold split), N > 0 =
    # exactly-N hottest columns.
    hot_columns: int = 0
    validate_input: List[str] = dataclasses.field(default_factory=list)
    data_validation: str = "VALIDATE_FULL"
    feature_file: Optional[str] = None  # pinned vocabulary (one key per line)
    constraint_file: Optional[str] = None  # coefficient bounds JSON
    date_range: Optional[str] = None  # "yyyymmdd-yyyymmdd"
    date_range_days_ago: Optional[str] = None  # "N-M"
    # Avro field-name set of the input records
    # (``avro/FieldNamesType.scala:20``): TRAINING_EXAMPLE | RESPONSE_PREDICTION
    field_names: str = "TRAINING_EXAMPLE"
    model_output_mode: str = "ALL"
    overwrite: bool = False
    compute_variances: bool = False
    # evaluate every optimizer iteration's model snapshot on the validation
    # data (``Driver.scala:293-347`` validatePerIteration + ModelTracker)
    validate_per_iteration: bool = False
    # warm-start: directory of a previous GLM run; its best-model.avro (or
    # an explicit .avro path) seeds every solve (``ModelTraining.scala:95-141``)
    initial_model_dir: Optional[str] = None
    log_level: str = "DEBUG"
    # model diagnostics (HL, error independence, importances) -> HTML
    # report + DIAGNOSED stage; requires validate_input
    diagnostics: bool = False
    # additionally run the EXPENSIVE training diagnostics: learning-curve
    # refits + bootstrap CIs (``Params.trainingDiagnosticsEnabled``)
    training_diagnostics: bool = False
    # float64 matches the reference's double-precision solves; degrades
    # to float32 when x64 is disabled (the default) — the driver logs the
    # dtype actually used at start
    precision: str = "float64"
    # device mesh for the solve: {"data": N} row-shards the batch (GSPMD
    # psum aggregation), {"data": N, "feature": M} additionally shards the
    # coefficient axis (the huge-d regime). None = single-device.
    mesh_shape: Optional[Dict[str, int]] = None
    # emit a jax.profiler trace of the train phase under
    # <output_dir>/profile (TensorBoard-loadable) — SURVEY §5.1
    profile: bool = False
    # fail at the first NaN-producing op inside training — SURVEY §5.2
    debug_nans: bool = False
    # observability (docs/OBSERVABILITY.md): span tracer output directory
    # (Chrome trace-event JSON + events.jsonl + metrics.json), periodic
    # metrics-registry snapshot interval in seconds (0 = final-only), and
    # a jax.profiler capture window around the whole run (unlike
    # `profile`, which captures only the train phase)
    trace_dir: Optional[str] = None
    metrics_every: float = 0.0
    profile_dir: Optional[str] = None
    # live HBM telemetry sample interval (seconds) while tracing; 0
    # disables. No-op on platforms without device.memory_stats()
    hbm_every: float = 0.5
    # crash flight recorder (obs.flight): ``flight-<reason>.json`` dumps
    # land here on preemption / crash. Defaults to trace_dir when
    # tracing; set explicitly to record flights without a full trace
    flight_dir: Optional[str] = None
    # convergence-health layer (obs.convergence): decode every solve's
    # device-side tapes into convergence.* metrics + events and write
    # <output_dir>/convergence-report.json — works with or without
    # --trace-dir (the decode syncs; pipelined solves pay nothing when
    # off)
    convergence_report: bool = False
    # regularization-path execution: "scan" (default) runs the whole
    # descending-lambda warm-started path as ONE device-resident XLA
    # dispatch (models/training._build_path_solver); "loop" keeps the
    # reference-shaped host loop of one dispatch per lambda
    path_mode: str = "scan"
    # multi-host resilience (docs/MULTIHOST.md): pod heartbeat interval
    # in seconds (0 = off; peers missing 3 intervals are declared lost
    # and the run exits with the distinct host-loss code), a watchdog
    # deadline for host-side collectives (None = block forever, the
    # pre-existing behavior), and per-process sharded checkpoint writes
    heartbeat_s: float = 0.0
    collective_timeout_s: Optional[float] = None
    sharded_ckpt: bool = False
    # model-quality observability (docs/OBSERVABILITY.md "Quality &
    # drift"): accumulate per-feature/label/margin sketches over ingest
    # and export <output_dir>/quality-fingerprint.json — the baseline
    # `photon-obs drift` and the serving DriftMonitor compare against
    quality_fingerprint: bool = True
    # collective reduction strategy for mesh solves (docs/PARALLEL.md):
    # None = the PHOTON_COLLECTIVE_MODE env default ("overlap":
    # row-balanced blocking + chunked reduce-scatter/all-gather
    # pipeline); "fused" = the PR-5 single trailing all-reduce oracle
    collective_mode: Optional[str] = None

    def validate(self) -> None:
        if not self.train_input:
            raise ValueError("train_input is required")
        if self.collective_mode is not None and self.collective_mode not in (
            "fused",
            "overlap",
        ):
            raise ValueError(
                f"collective_mode must be 'fused' or 'overlap', got "
                f"{self.collective_mode!r}"
            )
        if self.model_output_mode not in MODEL_OUTPUT_MODES:
            raise ValueError(
                f"model_output_mode must be one of {MODEL_OUTPUT_MODES}"
            )
        if self.date_range and self.date_range_days_ago:
            raise ValueError(
                "date_range and date_range_days_ago are mutually exclusive"
            )
        if self.hot_columns and not self.sparse:
            raise ValueError("hot_columns requires sparse=True")
        if self.ingest_chunk_mb <= 0:
            raise ValueError(
                f"ingest_chunk_mb must be > 0, got {self.ingest_chunk_mb}"
            )
        if self.decode_threads < 0:
            raise ValueError(
                f"decode_threads must be >= 0 (0 = auto), got "
                f"{self.decode_threads}"
            )
        if self.prefetch_depth < 1:
            raise ValueError(
                f"prefetch_depth must be >= 1, got {self.prefetch_depth}"
            )
        if self.stage_timeout_s is not None and self.stage_timeout_s < 0:
            raise ValueError(
                f"stage_timeout_s must be >= 0, got {self.stage_timeout_s}"
            )
        if self.epoch_policy not in ("fail", "skip"):
            raise ValueError(
                f"epoch_policy must be 'fail' or 'skip', got "
                f"{self.epoch_policy!r}"
            )
        if self.out_of_core:
            if self.sparse:
                raise ValueError(
                    "out_of_core streams dense uniform chunks; sparse "
                    "designs decode in-core (padded-ELL width is global)"
                )
            if self.streamed_ingest:
                raise ValueError(
                    "out_of_core subsumes streamed_ingest (chunks stay "
                    "host-side instead of assembling on device); pick one"
                )
            if self.normalization != "NONE":
                raise ValueError(
                    "out_of_core requires normalization NONE (the "
                    "whitening summary would need its own streaming pass)"
                )
            if self.optimizer == "NEWTON":
                raise ValueError(
                    "NEWTON materializes the explicit Hessian from the "
                    "in-core design; out_of_core supports TRON/LBFGS"
                )
            if self.mesh_shape:
                raise ValueError(
                    "out_of_core is single-device for now (chunk "
                    "streaming does not partition across a mesh)"
                )
            if self.diagnostics or self.validate_per_iteration:
                raise ValueError(
                    "diagnostics/validate_per_iteration need the in-core "
                    "training batch; not available with out_of_core"
                )
        if self.hot_columns and self.mesh_shape:
            raise ValueError(
                "hot_columns (hybrid features) is single-device for now: "
                "the bucketed cold segments have unequal row counts, "
                "which the row-sharded mesh path does not partition"
            )
        if self.hot_columns and self.optimizer == "NEWTON":
            raise ValueError(
                "NEWTON materializes the exact Hessian from dense "
                "features; hot_columns (hybrid) is not supported"
            )
        if self.training_diagnostics and not self.diagnostics:
            raise ValueError(
                "training_diagnostics requires diagnostics=True"
            )
        if self.validate_per_iteration and not self.validate_input:
            raise ValueError(
                "validate_per_iteration requires validate_input"
            )
        if self.mesh_shape is not None:
            unknown = set(self.mesh_shape) - {"data", "feature"}
            if unknown:
                raise ValueError(
                    f"mesh_shape axes must be 'data'/'feature': {unknown}"
                )
            if any(
                not isinstance(v, int) or v < 1
                for v in self.mesh_shape.values()
            ):
                raise ValueError(
                    f"mesh_shape sizes must be integers >= 1: "
                    f"{self.mesh_shape}"
                )
            # feature sharding composes with sparse (column-blocked ELL),
            # normalization, and box constraints since r4 — the blocked
            # layout re-threads their (d,)-vectors
            # (parallel/distributed.feature_sharded_train_glm); only the
            # hybrid container stays single-device (checked above)
        if self.diagnostics and not self.validate_input:
            raise ValueError(
                "diagnostics requires validate_input (the model diagnostics "
                "run against validation data, Driver.scala:424-474)"
            )
        _validate_pod_resilience(self)
        self.to_training_config().validate()

    def to_training_config(self) -> GLMTrainingConfig:
        return GLMTrainingConfig(
            task=TaskType[self.task],
            optimizer=OptimizerType[self.optimizer],
            reg_weights=tuple(self.reg_weights),
            regularization=RegularizationContext(
                self.reg_type, alpha=self.elastic_net_alpha
            )
            if self.reg_type != "NONE"
            else RegularizationContext("NONE"),
            normalization=NormalizationType[self.normalization],
            max_iters=self.max_iters,
            tolerance=self.tolerance,
            compute_variances=self.compute_variances,
            track_models=self.validate_per_iteration,
            path_mode=self.path_mode,
            # set by the driver once the vocabulary exists
            intercept_index=None,
        )


@dataclasses.dataclass
class CoordinateSpec:
    """One GAME coordinate's optimization + data knobs — the typed analog
    of "maxIter,tol,lambda,downSampleRate,optimizer,regType" plus the data
    config DSL. ``reg_weights`` is a GRID axis: the driver trains the
    cartesian product over all coordinates' grids
    (``cli/game/training/Driver.scala:317-320``)."""

    shard: str  # feature bag id
    random_effect: Optional[str] = None  # metadataMap key; None = fixed
    optimizer: str = "TRON"
    reg_weights: List[float] = dataclasses.field(default_factory=lambda: [50.0])
    l1_ratio: float = 0.0
    max_iters: int = 20
    tolerance: float = 1e-5
    down_sampling_rate: Optional[float] = None
    active_cap: Optional[int] = None
    num_buckets: int = 4
    projector: Optional[str] = None  # RANDOM=<k> | INDEX_MAP | IDENTITY
    # per-entity Pearson feature selection: keep at most
    # ceil(ratio * numSamples_e) features per entity
    # (``RandomEffectDataConfiguration.numFeaturesToSamplesRatioUpperBound``)
    feature_ratio: Optional[float] = None
    # per-entity support filter: a feature survives iff stored in >= this
    # many of the entity's active rows; applied BEFORE the Pearson ranking
    # (``LocalDataSet.filterFeaturesBySupport``, LocalDataSet.scala:80-109)
    min_support: int = 0
    # factored random effect (w_e = B gamma_e): set latent_dim to enable
    # (``MFOptimizationConfiguration`` "numInnerIter,latentDim" + the
    # latent-matrix sub-config of the reference's triple-config string)
    latent_dim: Optional[int] = None
    num_inner_iterations: int = 1
    # the latent-matrix sub-config's own optimizer ("re-config;latent-config;
    # mf-config": the shared B is a GLM of its own): LBFGS | TRON; default:
    # the coordinate's optimizer
    latent_optimizer: Optional[str] = None
    latent_reg_weight: Optional[float] = None  # default: reg weight
    latent_max_iters: Optional[int] = None  # default: max_iters
    latent_tolerance: Optional[float] = None  # default: tolerance
    # TRON's CG iterations an outer iteration of the latent-matrix solve
    # (default: the solver's 20); under latent_tolerance 0 every CG runs
    # exactly this many Hessian-vector passes
    latent_max_cg: Optional[int] = None
    # fixed-effect coordinates on a SPARSE shard: densify the N hottest
    # columns into the MXU slab (-1 = auto), ops.sparse.to_hybrid applied
    # coordinate-locally (the row permutation never leaves the coordinate)
    hot_columns: int = 0
    # record per-iteration solver tapes (values/grad norms/radius/step)
    # inside this coordinate's solves — the obs/convergence.py decode
    # surface. Costs (entities, max_iters+1) carry state on vmapped
    # random effects, so off by default; fleet summaries work without it
    track_states: bool = False


@dataclasses.dataclass
class GameDriverParams:
    """GAME train-driver knobs (``cli/game/training/Params.scala:81-292``)."""

    train_input: List[str]
    output_dir: str
    coordinates: Dict[str, CoordinateSpec]
    updating_sequence: List[str]
    task: str = "LOGISTIC_REGRESSION"
    num_iterations: int = 1
    validate_input: List[str] = dataclasses.field(default_factory=list)
    validate_per_coordinate: bool = True
    feature_shards: Dict[str, Optional[str]] = dataclasses.field(
        default_factory=dict
    )  # shard id -> feature list file (None = build from train data)
    add_intercept: bool = True
    date_range: Optional[str] = None
    date_range_days_ago: Optional[str] = None
    field_names: str = "TRAINING_EXAMPLE"
    model_output_mode: str = "BEST"
    overwrite: bool = False
    log_level: str = "DEBUG"
    precision: str = "float64"
    # checkpoint the full training state every N outer iterations
    # (0 = disabled); resume=True continues a previous run in-place
    checkpoint_every: int = 0
    resume: bool = False
    # roll back + damped-retry non-finite coordinate updates, freezing a
    # coordinate that keeps failing so the rest of the model trains on
    # (docs/ROBUSTNESS.md). Forces the per-update dispatch loop.
    divergence_guard: bool = False
    # install SIGTERM/SIGINT handlers that finish the current pass, write
    # a final checkpoint + resumable marker, and exit cleanly — the TPU
    # preemption contract (docs/ROBUSTNESS.md)
    graceful_shutdown: bool = True
    # warm-start: root of a previously saved GAME model (best/ or all/<i>)
    initial_model_dir: Optional[str] = None
    # lifecycle retrain (docs/LIFECYCLE.md): coordinates to EXCLUDE from
    # updates — they carry their warm-started params bit-identical and
    # still score. The retrain orchestrator sets this from convergence
    # health so only unhealthy coordinates pay for a refit. Forces the
    # per-update dispatch loop (same mechanics as guard-frozen
    # coordinates); requires initial_model_dir (freezing a cold-started
    # coordinate would serve zeros).
    freeze_coordinates: List[str] = dataclasses.field(default_factory=list)
    # merge coordinates sharing (effect type, shard) by coefficient
    # addition at save (``ModelProcessingUtils.collapseGameModel``)
    collapse_output: bool = False
    # shards stored as padded-ELL sparse matrices (the wide fixed-effect
    # bag regime). Sparse shards serve plain fixed-effect coordinates
    # only: per-entity designs gather dense rows.
    sparse_shards: List[str] = dataclasses.field(default_factory=list)
    # decode the training input through the streaming ingest pipeline
    # (io.pipeline: bounded parallel decode; identical GameData to the
    # one-shot read — docs/INGEST.md) with the same three knobs as the
    # GLM driver
    streamed_ingest: bool = False
    ingest_chunk_mb: float = 64.0
    decode_threads: int = 0
    prefetch_depth: int = 2
    # pipeline supervision (docs/ROBUSTNESS.md): stage watchdog deadline
    # (seconds; 0/None = off) and the exhausted-retry epoch policy
    # ("fail" | "skip")
    stage_timeout_s: Optional[float] = None
    epoch_policy: str = "fail"
    # observability (docs/OBSERVABILITY.md): span tracer output directory
    # (Chrome trace-event JSON + events.jsonl + metrics.json), periodic
    # metrics-registry snapshot interval in seconds (0 = final-only), and
    # a jax.profiler capture window around the run
    trace_dir: Optional[str] = None
    metrics_every: float = 0.0
    profile_dir: Optional[str] = None
    # live HBM telemetry sample interval (seconds) while tracing; 0
    # disables. No-op on platforms without device.memory_stats()
    hbm_every: float = 0.5
    # crash flight recorder (obs.flight): ``flight-<reason>.json`` dumps
    # land here on divergence rollback / preemption / crash. Defaults to
    # trace_dir when tracing; set explicitly to record flights without a
    # full trace (a ring-only tracer is installed)
    flight_dir: Optional[str] = None
    # convergence-health layer (obs.convergence): per-coordinate fleet
    # summaries (iterations histogram, non-converged entities, worst-k
    # by final grad norm) recorded every pass + a run-level
    # <output_dir>/convergence-report.json — works with or without
    # --trace-dir
    convergence_report: bool = False
    # device-resident multi-pass descent (K): with the fused whole-pass
    # mode, run up to K coordinate-descent passes per XLA dispatch
    # (game/descent.CoordinateDescent._superpass_fn) — a run of P passes
    # costs ceil(P/K) dispatches. Checkpoint / preemption / divergence-
    # guard semantics hold at dispatch boundaries: K is the checkpoint
    # granularity (the chunk shrinks to land on checkpoint_every).
    passes_per_dispatch: int = 1
    # in-program objective-tolerance early exit for K > 1: stop when the
    # training objective moves less than tol * |objective at dispatch
    # entry| between consecutive passes. 0 disables (every requested
    # pass runs — the reference behavior).
    convergence_tolerance: float = 0.0
    # multi-host resilience (docs/MULTIHOST.md): pod heartbeat interval
    # in seconds (0 = off; a peer missing 3 intervals is declared lost —
    # survivors write a final shard set and exit HOST_LOSS_EXIT_CODE),
    # a watchdog deadline on host-side collectives (None = block
    # forever), and per-process sharded checkpoints (REQUIRED for
    # checkpoint_every > 0 on a pod: the whole-model writer is
    # single-process; entity-keyed shards restore onto a different
    # world size)
    heartbeat_s: float = 0.0
    collective_timeout_s: Optional[float] = None
    sharded_ckpt: bool = False
    # model-quality observability: sketch the GAME ingest (per-shard
    # features, labels, entity top-k) plus the best model's training
    # margins, and export quality-fingerprint.json into every model
    # export subdir (next to model-manifest.json, manifest-covered) —
    # the baseline the serving DriftMonitor hot-loads with the model
    quality_fingerprint: bool = True
    # entity-sharded GAME descent (docs/PARALLEL.md): shard the random-
    # effect table, its bucket lanes, and the (entity-partitioned) row
    # space over an N-device 'entity' mesh via shard_map — zero
    # collectives in the random-effect update; only the fixed-effect
    # coordinate reduces. 0/1 = off. Requires exactly one PLAIN
    # (identity, dense-shard) random-effect coordinate; ownership
    # follows the sharded-checkpoint round-robin rule, so --sharded-ckpt
    # composes entity-keyed (restore at any width re-keys rows).
    entity_shards: int = 0
    # collective reduction strategy (docs/PARALLEL.md): None = the
    # PHOTON_COLLECTIVE_MODE env default ("overlap": row-balanced
    # blocking + chunked reduce-scatter/all-gather pipeline); "fused" =
    # the PR-5 single trailing all-reduce, kept as the equivalence
    # oracle
    collective_mode: Optional[str] = None

    def validate(self) -> None:
        if not self.train_input:
            raise ValueError("train_input is required")
        if not self.updating_sequence:
            raise ValueError("updating_sequence is required")
        if self.freeze_coordinates:
            unknown = set(self.freeze_coordinates) - set(self.coordinates)
            if unknown:
                raise ValueError(
                    f"freeze_coordinates names unknown coordinates: "
                    f"{sorted(unknown)}"
                )
            if not self.initial_model_dir:
                raise ValueError(
                    "freeze_coordinates requires initial_model_dir "
                    "(a frozen cold start would serve zeros)"
                )
        if self.collective_mode is not None and self.collective_mode not in (
            "fused",
            "overlap",
        ):
            raise ValueError(
                f"collective_mode must be 'fused' or 'overlap', got "
                f"{self.collective_mode!r}"
            )
        if self.entity_shards < 0:
            raise ValueError(
                f"entity_shards must be >= 0, got {self.entity_shards}"
            )
        if self.entity_shards > 1:
            # every PLAIN random effect shards, each in its own row
            # partition (docs/PARALLEL.md); the other kinds hold state
            # the shard-local update does not carry
            plain_res = [
                n
                for n, c in self.coordinates.items()
                if c.random_effect is not None
                and c.latent_dim is None
                and not c.projector
                and c.shard not in set(self.sparse_shards)
            ]
            other_res = [
                n
                for n, c in self.coordinates.items()
                if c.random_effect is not None and n not in plain_res
            ]
            if not plain_res or other_res:
                raise ValueError(
                    "entity_shards shards PLAIN random-effect coordinates "
                    "(identity projector, dense shard), any number of "
                    "them, and needs at least one; factored, projected "
                    "and sparse-shard random effects cannot be entity-"
                    f"sharded; got plain={plain_res} other={other_res}"
                )
        sparse = set(self.sparse_shards)
        for name, spec in self.coordinates.items():
            uses_sparse = spec.shard in sparse
            entityish = (
                spec.random_effect is not None
                or spec.latent_dim is not None
                or spec.projector
            )
            # a WIDE random effect rides a sparse shard through INDEX_MAP
            # projection (per-entity active unions are small even when d
            # is huge — ``RandomEffectCoordinateInProjectedSpace.scala``);
            # everything else per-entity still needs dense rows
            sparse_re_ok = (
                spec.random_effect is not None
                and spec.latent_dim is None
                and (spec.projector or "").strip().upper() == "INDEX_MAP"
            )
            if uses_sparse and entityish and not sparse_re_ok:
                raise ValueError(
                    f"coordinate {name!r} uses sparse shard "
                    f"{spec.shard!r} but random/factored/projected "
                    "effects need dense per-row features (EXCEPT a "
                    "random effect with projector INDEX_MAP, which "
                    "solves in each entity's compact column space)"
                )
            if spec.latent_optimizer is not None and spec.latent_dim is None:
                raise ValueError(
                    f"coordinate {name!r}: latent_optimizer is the "
                    "factored coordinate's latent-matrix optimizer; set "
                    "latent_dim to factor the random effect"
                )
            if spec.hot_columns and (entityish or not uses_sparse):
                raise ValueError(
                    f"coordinate {name!r}: hot_columns applies to "
                    "fixed-effect coordinates on a shard listed in "
                    "sparse_shards"
                )
            if spec.hot_columns and spec.optimizer == "NEWTON":
                raise ValueError(
                    f"coordinate {name!r}: NEWTON materializes the exact "
                    "Hessian from dense features; hot_columns (hybrid) "
                    "is not supported"
                )
        for name in self.updating_sequence:
            if name not in self.coordinates:
                raise ValueError(
                    f"updating_sequence names unknown coordinate {name!r}"
                )
        if self.model_output_mode not in MODEL_OUTPUT_MODES:
            raise ValueError(
                f"model_output_mode must be one of {MODEL_OUTPUT_MODES}"
            )
        fixed = [
            n
            for n, c in self.coordinates.items()
            if c.random_effect is None
        ]
        if len(fixed) > 1:
            raise ValueError(
                f"at most one fixed-effect coordinate supported, got {fixed}"
            )
        if self.collapse_output:
            factored = [
                n
                for n, c in self.coordinates.items()
                if c.latent_dim is not None
            ]
            if factored:
                raise ValueError(
                    f"collapse_output cannot merge factored coordinates "
                    f"{factored} (ModelProcessingUtils.scala:235-236); "
                    "failing before training rather than at save"
                )
        if self.resume and self.checkpoint_every <= 0:
            raise ValueError(
                "resume=True requires checkpoint_every > 0; without "
                "checkpoints a resumed run would silently retrain from "
                "scratch over the existing output directory"
            )
        if self.passes_per_dispatch < 1:
            raise ValueError(
                f"passes_per_dispatch must be >= 1, got "
                f"{self.passes_per_dispatch}"
            )
        if self.ingest_chunk_mb <= 0:
            raise ValueError(
                f"ingest_chunk_mb must be > 0, got {self.ingest_chunk_mb}"
            )
        if self.decode_threads < 0:
            raise ValueError(
                f"decode_threads must be >= 0 (0 = auto), got "
                f"{self.decode_threads}"
            )
        if self.prefetch_depth < 1:
            raise ValueError(
                f"prefetch_depth must be >= 1, got {self.prefetch_depth}"
            )
        if self.stage_timeout_s is not None and self.stage_timeout_s < 0:
            raise ValueError(
                f"stage_timeout_s must be >= 0, got {self.stage_timeout_s}"
            )
        if self.epoch_policy not in ("fail", "skip"):
            raise ValueError(
                f"epoch_policy must be 'fail' or 'skip', got "
                f"{self.epoch_policy!r}"
            )
        if self.convergence_tolerance < 0:
            raise ValueError(
                f"convergence_tolerance must be >= 0, got "
                f"{self.convergence_tolerance}"
            )
        _validate_pod_resilience(self)

    def grid(self) -> List[Dict[str, float]]:
        """Cartesian product over each coordinate's reg-weight grid
        (``Driver.scala:317-320``): a list of {coordinate: reg_weight}."""
        import itertools

        names = list(self.updating_sequence)
        axes = [self.coordinates[n].reg_weights for n in names]
        return [dict(zip(names, combo)) for combo in itertools.product(*axes)]


@dataclasses.dataclass
class ScoringParams:
    """Scoring-driver knobs (``cli/game/scoring/Params.scala``)."""

    input: List[str]
    model_dir: str
    output_dir: str
    model_kind: str = "game"  # "glm" | "game"
    # explicit .avro model file (glm only) — overrides the best-model.avro /
    # models/ resolution inside model_dir
    model_path: Optional[str] = None
    task: str = "LOGISTIC_REGRESSION"
    evaluate: bool = False  # requires labels in the input
    sparse: bool = False
    # GAME only: shards stored sparse (must match how the model was
    # trained structurally — fixed-effect shards only)
    sparse_shards: List[str] = dataclasses.field(default_factory=list)
    date_range: Optional[str] = None
    date_range_days_ago: Optional[str] = None
    field_names: str = "TRAINING_EXAMPLE"
    overwrite: bool = False
    log_level: str = "DEBUG"

    def validate(self) -> None:
        if not self.input:
            raise ValueError("input is required")
        if self.model_kind not in ("glm", "game"):
            raise ValueError("model_kind must be 'glm' or 'game'")


def _from_dict(cls, data: dict):
    """Build a params dataclass from a JSON dict, with nested
    CoordinateSpec parsing and unknown-key rejection."""
    fields = {f.name: f for f in dataclasses.fields(cls)}
    unknown = set(data) - set(fields)
    if unknown:
        raise ValueError(f"unknown {cls.__name__} keys: {sorted(unknown)}")
    kwargs = dict(data)
    if cls is GameDriverParams and "coordinates" in kwargs:
        kwargs["coordinates"] = {
            name: spec
            if isinstance(spec, CoordinateSpec)
            else _from_dict(CoordinateSpec, spec)
            for name, spec in kwargs["coordinates"].items()
        }
    return cls(**kwargs)


def load_params(source, cls):
    """Load driver params from a dict or a JSON file path."""
    if isinstance(source, cls):
        return source
    if isinstance(source, dict):
        return _from_dict(cls, source)
    with open(source) as f:
        return _from_dict(cls, json.load(f))
