"""Core GLM training driver.

Rebuild of the reference's staged train pipeline (``Driver.scala:76-570``):
INIT (config + output guard + logger) -> PREPROCESSED (Avro ingest, feature
indexing, data validation, feature summarization, normalization) -> TRAINED
(descending-lambda sweep with warm starts) -> VALIDATED (named metrics per
lambda, best-model selection) -> model/text/summary outputs. Run as

    python -m photon_ml_tpu.cli.train --config params.json [--flag value ...]

or programmatically via :func:`run_glm_training`.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
from typing import Dict, List, Optional

import numpy as np

from photon_ml_tpu.cli.config import GLMDriverParams, load_params
from photon_ml_tpu.cli.stages import DriverStage, StageTracker
from photon_ml_tpu.core.tasks import TaskType
from photon_ml_tpu.core.types import LabeledBatch
from photon_ml_tpu.core.validators import DataValidationType, sanity_check_data
from photon_ml_tpu.io.avro import read_avro_dir, read_avro_file
from photon_ml_tpu.io.models import save_glm_model
from photon_ml_tpu.io.vocab import FeatureVocabulary
from photon_ml_tpu.models.selection import select_best_model
from photon_ml_tpu.models.training import TrainedModel, train_glm
from photon_ml_tpu.ops import metrics as metrics_mod
from photon_ml_tpu.ops.stats import summarize_features
from photon_ml_tpu.utils.dates import DateRange, expand_date_paths
from photon_ml_tpu.utils.logging import PhotonLogger, timed


def driver_dtype(precision: str):
    """float64 when requested AND enabled; float32 otherwise —
    :func:`log_driver_runtime` says which at driver start."""
    import jax
    import jax.numpy as jnp

    if precision == "float64" and jax.config.jax_enable_x64:
        return jnp.float64
    return jnp.float32


def log_driver_runtime(logger, precision: Optional[str] = None) -> None:
    """Log once, at driver start, where and how this run executes: the
    device, the dtype the solves actually use, and the Avro codec ingest
    takes and why."""
    import jax

    from photon_ml_tpu.io import native

    dev = jax.devices()[0]
    logger.info(
        f"device: platform={dev.platform} device_kind={dev.device_kind} "
        f"count={jax.device_count()}"
    )
    if precision is not None:
        used = np.dtype(driver_dtype(precision)).name
        note = (
            ""
            if used == precision
            else f" (precision={precision!r} requested; jax_enable_x64 "
            "is off)"
        )
        logger.info(f"solve dtype: {used}{note}")
    logger.info(native.codec_report())


def read_records(paths: List[str]) -> List[dict]:
    """Read TrainingExampleAvro records from files and/or directories."""
    records: List[dict] = []
    for p in paths:
        if os.path.isdir(p):
            _, recs = read_avro_dir(p)
        else:
            _, recs = read_avro_file(p)
        records.extend(recs)
    if not records:
        raise ValueError(f"no records found in {paths}")
    return records


def _hybridize(batch, params, logger):
    """Split an ELL batch into the dense-hot + bucketed sparse-cold
    representation (``ops.sparse.HybridFeatures``): the power-law head
    rides the MXU, the tail keeps the scatter path at near-zero
    padding. The batch's row-aligned fields are
    permuted to the hybrid's stored order (training is row-order
    invariant)."""
    import dataclasses as _dc

    from photon_ml_tpu.ops.sparse import stored_cold_entries, to_hybrid

    hf = to_hybrid(batch.features, hot_columns=params.hot_columns)
    perm = np.asarray(hf.row_perm)
    widths = [seg.nnz_per_row for seg in hf.cold_segments]
    logger.info(
        f"hybrid split: {hf.dense.shape[1]} hot columns densified, "
        f"{stored_cold_entries(hf)} entries stay sparse over "
        f"{len(widths)} row buckets (widths {widths})"
    )
    return _dc.replace(
        batch,
        features=hf,
        labels=batch.labels[perm],
        offsets=batch.offsets[perm],
        weights=batch.weights[perm],
        mask=batch.mask[perm],
    )


def resolve_date_range(params) -> Optional[DateRange]:
    if params.date_range:
        return DateRange.from_dates(params.date_range)
    if params.date_range_days_ago:
        return DateRange.from_days_ago(params.date_range_days_ago)
    return None


def prepare_output_dir(path: str, overwrite: bool) -> None:
    """Refuse a pre-existing output directory unless overwriting — the
    reference's guard rail (``Driver.scala:520-526``)."""
    if os.path.exists(path):
        if not overwrite:
            raise FileExistsError(
                f"output dir {path} exists; pass overwrite to replace"
            )
    else:
        os.makedirs(path)


def write_model_text(
    path: str, means: np.ndarray, vocab: FeatureVocabulary
) -> None:
    """Plain-text model (``GLMSuite.writeModelsInText``,
    ``GLMSuite.scala:355-400``): one "name\\tterm\\tvalue" line per nonzero
    coefficient (intercept always written)."""
    with open(path, "w", encoding="utf-8") as f:
        for i, v in enumerate(np.asarray(means)):
            if v == 0.0 and i != vocab.intercept_index:
                continue
            name, term = vocab.name_term(i)
            f.write(f"{name}\t{term}\t{float(v)}\n")


def write_feature_summary(
    path: str, summary, vocab: FeatureVocabulary
) -> None:
    """Per-feature summary TSV (the reference writes a feature-summary
    output from the same statistics, ``GLMSuite.scala:402+``)."""
    cols = ("mean", "variance", "min", "max", "norm_l1", "norm_l2",
            "mean_abs", "num_nonzeros")
    arrays = {c: np.asarray(getattr(summary, c)) for c in cols}
    with open(path, "w", encoding="utf-8") as f:
        f.write("name\tterm\t" + "\t".join(cols) + "\n")
        for i in range(len(vocab)):
            name, term = vocab.name_term(i)
            f.write(
                f"{name}\t{term}\t"
                + "\t".join(str(float(arrays[c][i])) for c in cols)
                + "\n"
            )


@dataclasses.dataclass
class GLMTrainingRun:
    """Everything a caller (or test) needs to inspect a completed run."""

    params: GLMDriverParams
    stages: List[DriverStage]
    vocab: FeatureVocabulary
    models: List[TrainedModel]
    best: Optional[TrainedModel]
    best_index: Optional[int]
    # positional, aligned with `models` (duplicate lambdas stay distinct)
    validation_metrics: List[Dict[str, float]]
    num_training_rows: int
    num_features: int
    summary: object


def run_glm_training(params) -> GLMTrainingRun:
    """Entry point: config load + the observability envelope (span
    tracer, periodic metrics snapshots, profiler window) around the
    actual driver body."""
    from photon_ml_tpu import obs

    params = load_params(params, GLMDriverParams)
    params.validate()
    # the output-dir guard must fire BEFORE the observe() envelope: a
    # metrics.json path inside output_dir makes the envelope mkdir it,
    # which the guard would then misread as a pre-existing run
    prepare_output_dir(params.output_dir, params.overwrite)
    metrics_path = None
    if params.trace_dir is None and (
        params.metrics_every > 0 or params.convergence_report
    ):
        metrics_path = os.path.join(params.output_dir, "metrics.json")
    conv_tracker = None
    if params.convergence_report:
        # per-solve tape decode even without a tracer (obs.convergence);
        # the aggregated report lands next to the models below
        conv_tracker = obs.install_convergence_tracker()
    # multi-host resilience envelope (docs/MULTIHOST.md): a watchdog
    # deadline on every host collective (mesh solves globalize metadata
    # through allgather_host) and a pod heartbeat monitor feeding the
    # pod.heartbeat.* gauges + the watchdog's straggler attribution
    from photon_ml_tpu.parallel import (
        configure_collective_resilience,
        install_monitor,
    )
    from photon_ml_tpu.parallel.heartbeat import HeartbeatMonitor

    prev_resilience = configure_collective_resilience(
        timeout_s=params.collective_timeout_s
    )
    # collective strategy (docs/PARALLEL.md): the knob is trace-time
    # env state (ops.sparse reads it while building mesh reductions), so
    # the driver pins it process-wide before any solve traces
    if params.collective_mode is not None:
        from photon_ml_tpu.parallel.overlap import COLLECTIVE_MODE_ENV

        os.environ[COLLECTIVE_MODE_ENV] = params.collective_mode
    monitor = None
    if params.heartbeat_s > 0:
        monitor = HeartbeatMonitor(interval_s=params.heartbeat_s).start()
        install_monitor(monitor)
    try:
        with obs.observe(
            trace_dir=params.trace_dir,
            metrics_path=metrics_path,
            metrics_every=params.metrics_every,
            profile_dir=params.profile_dir,
            hbm_every_s=params.hbm_every,
            process_name="photon_ml_tpu.train",
            flight_dir=params.flight_dir,
        ):
            return _run_glm_training(params)
    finally:
        if params.quality_fingerprint:
            # idempotent: normally uninstalled right after train ingest;
            # this covers the ingest-raised path so no collector leaks
            # into the next in-process run
            obs.quality.uninstall_fingerprint_collector()
        configure_collective_resilience(
            prev_resilience.timeout_s, prev_resilience.retries
        )
        if monitor is not None:
            install_monitor(None)
            monitor.stop()
        if conv_tracker is not None:
            try:
                conv_tracker.dump(
                    os.path.join(
                        params.output_dir, "convergence-report.json"
                    )
                )
            except OSError:
                pass
            obs.uninstall_convergence_tracker()


def _run_glm_training(params: GLMDriverParams) -> GLMTrainingRun:
    # output dir already prepared by run_glm_training (before the
    # observe envelope could create it)
    tracker = StageTracker()
    logger = PhotonLogger(
        os.path.join(params.output_dir, "log-message.txt"),
        level=params.log_level,
    )
    logger.info(f"GLM training driver: task={params.task} "
                f"optimizer={params.optimizer} reg={params.reg_type} "
                f"lambdas={params.reg_weights}")
    log_driver_runtime(logger, params.precision)

    # ---- PREPROCESS ------------------------------------------------------
    with timed(logger, "preprocess"):
        from photon_ml_tpu.io.ingest import IngestSource

        date_range = resolve_date_range(params)
        train_paths = expand_date_paths(params.train_input, date_range)
        source = IngestSource(train_paths, params.field_names)

        if params.feature_file:
            vocab = FeatureVocabulary.load(params.feature_file)
        else:
            vocab = source.build_vocab(add_intercept=params.add_intercept)
        logger.info(f"feature space: {len(vocab)} columns "
                    f"(intercept={vocab.intercept_index})")

        # quality fingerprint: the io paths feed the installed collector
        # per ingest chunk (docs/OBSERVABILITY.md "Quality & drift");
        # installed for the TRAIN ingest only — validation rows are a
        # different distribution and must not blur the baseline
        from photon_ml_tpu.obs import quality as quality_mod

        fingerprint = None
        if params.quality_fingerprint:
            fingerprint = quality_mod.install_fingerprint_collector()

        task = TaskType[params.task]
        batch = None
        design = None
        summary = None
        if params.out_of_core:
            # decode + stage ONCE into host-resident uniform chunks;
            # every objective pass will stream them (docs/INGEST.md)
            from photon_ml_tpu.io.pipeline import (
                IngestPipeline,
                PipelineConfig,
                StreamedDesign,
            )

            with IngestPipeline(
                source.files,
                [vocab],
                label_field=source.label_field,
                config=PipelineConfig(
                    chunk_mb=params.ingest_chunk_mb,
                    decode_threads=params.decode_threads,
                    prefetch_depth=params.prefetch_depth,
                    stage_timeout_s=params.stage_timeout_s or None,
                    epoch_policy=params.epoch_policy,
                ),
            ) as pipe:
                design = StreamedDesign.from_pipeline(
                    pipe,
                    dtype=np.dtype(driver_dtype(params.precision)),
                )
            logger.info(
                f"out-of-core design: {design.n} rows x {design.d} "
                f"columns in {design.num_chunks} chunks of "
                f"{design.rows_per_chunk} rows "
                f"({design.bytes_per_epoch / 1e9:.2f} GB/epoch streamed); "
                "sanity checks and the feature summary need the in-core "
                "batch and are skipped"
            )
        elif params.streamed_ingest:
            if params.sparse:
                raise ValueError(
                    "streamed_ingest is dense-only (padded-ELL width is "
                    "a global property; decode sparse inputs whole)"
                )
            batch, _uids, _present = source.labeled_batch_streamed(
                vocab,
                dtype=driver_dtype(params.precision),
                chunk_mb=params.ingest_chunk_mb,
                decode_threads=params.decode_threads,
                prefetch_depth=params.prefetch_depth,
                stage_timeout_s=params.stage_timeout_s,
                epoch_policy=params.epoch_policy,
            )
        else:
            batch, _uids, _present = source.labeled_batch(
                vocab, sparse=params.sparse,
                dtype=driver_dtype(params.precision),
            )
        if batch is not None:
            logger.info(f"read {batch.labels.shape[0]} training records")
            if params.sparse and params.hot_columns:
                batch = _hybridize(batch, params, logger)
            sanity_check_data(
                batch, task, DataValidationType[params.data_validation]
            )
            summary = summarize_features(batch)
            write_feature_summary(
                os.path.join(params.output_dir, "feature-summary.tsv"),
                summary,
                vocab,
            )
        if fingerprint is not None:
            # train ingest is done — stop collecting (validation ingest
            # below must not enter the baseline)
            quality_mod.uninstall_fingerprint_collector()
            logger.info(
                f"quality fingerprint: {fingerprint.rows} rows sketched"
            )
    tracker.advance(DriverStage.PREPROCESSED)

    # ---- TRAIN -----------------------------------------------------------
    tracker.assert_at_least(DriverStage.PREPROCESSED)
    from photon_ml_tpu.utils.debug import debug_nans, profile_trace

    with timed(logger, "train"), profile_trace(
        os.path.join(params.output_dir, "profile") if params.profile else None
    ), debug_nans(params.debug_nans):
        cfg = dataclasses.replace(
            params.to_training_config(),
            intercept_index=vocab.intercept_index,
        )
        if params.constraint_file:
            from photon_ml_tpu.io.constraints import load_constraint_bounds

            lb, ub = load_constraint_bounds(params.constraint_file, vocab)
            cfg = dataclasses.replace(
                cfg, lower_bounds=lb, upper_bounds=ub
            )
        initial = None
        if params.initial_model_dir:
            from photon_ml_tpu.io.models import load_glm_model

            init_path = params.initial_model_dir
            if os.path.isdir(init_path):
                best = os.path.join(init_path, "best-model.avro")
                if os.path.exists(best):
                    init_path = best
                else:
                    # no-validation runs write only models/; accept a sole
                    # model there, refuse ambiguity (like cli/score.py)
                    mdir = os.path.join(init_path, "models")
                    candidates = (
                        sorted(
                            f for f in os.listdir(mdir)
                            if f.endswith(".avro")
                        )
                        if os.path.isdir(mdir)
                        else []
                    )
                    if len(candidates) != 1:
                        raise FileNotFoundError(
                            f"no best-model.avro in {init_path} and "
                            f"{len(candidates)} candidates in models/ — "
                            "point initial_model_dir at a specific .avro"
                        )
                    init_path = os.path.join(mdir, candidates[0])
            # coefficients remap by (name, term), so a drifted vocabulary
            # still warm-starts correctly (unknown features drop, new
            # features start at 0)
            initial, _ = load_glm_model(init_path, vocab)
            logger.info(f"warm-starting from {init_path}")
        if design is not None:
            # out-of-core: every objective pass streams the host chunks
            # through the fused per-chunk programs; the unmodified
            # TRON/LBFGS loops see the exact full-dataset objective
            from photon_ml_tpu.models.training import train_glm_streamed

            logger.info(
                f"out-of-core solve over {design.num_chunks} streamed "
                "chunks"
            )
            models = list(
                train_glm_streamed(
                    design, cfg, initial_coefficients=initial
                )
            )
        elif params.mesh_shape:
            # mesh-sharded solve: 'data' row-shards (GSPMD psum), adding
            # 'feature' also shards the coefficient axis (huge-d regime);
            # device-count validation lives in the mesh constructors
            from photon_ml_tpu.parallel import (
                distributed_train_glm,
                feature_sharded_train_glm,
                make_feature_mesh,
                make_mesh,
            )

            n_data = params.mesh_shape.get("data", 1)
            n_feat = params.mesh_shape.get("feature", 1)
            logger.info(f"mesh solve over {params.mesh_shape}")
            if n_feat > 1:
                models = list(
                    feature_sharded_train_glm(
                        batch,
                        cfg,
                        make_feature_mesh(n_data, n_feat),
                        initial_coefficients=initial,
                    )
                )
            else:
                models = list(
                    distributed_train_glm(
                        batch,
                        cfg,
                        make_mesh(n_data),
                        initial_coefficients=initial,
                    )
                )
        else:
            models = list(
                train_glm(batch, cfg, initial_coefficients=initial)
            )
        for tm in models:
            logger.info(
                f"lambda={tm.reg_weight}: iters={int(tm.result.iterations)} "
                f"value={float(tm.result.value):.6g}"
            )
    tracker.advance(DriverStage.TRAINED)

    # ---- VALIDATE --------------------------------------------------------
    best = None
    best_index = None
    validation_metrics: List[Dict[str, float]] = []
    if params.validate_input:
        tracker.assert_at_least(DriverStage.TRAINED)
        with timed(logger, "validate"):
            vbatch, _vuids, _vpresent = IngestSource(
                expand_date_paths(params.validate_input, date_range),
                params.field_names,
            ).labeled_batch(
                vocab, sparse=params.sparse,
                dtype=driver_dtype(params.precision),
            )
            if params.sparse and params.hot_columns:
                vbatch = _hybridize(vbatch, params, logger)
            for tm in models:
                margins = tm.model.compute_margin(
                    vbatch.features, vbatch.offsets
                )
                validation_metrics.append(
                    metrics_mod.evaluate(
                        task,
                        vbatch.labels,
                        margins,
                        vbatch.effective_weights(),
                    )
                )
            best, _scores = select_best_model(models, vbatch)
            best_index = next(
                i for i, tm in enumerate(models) if tm is best
            )
            logger.info(
                f"best lambda={best.reg_weight} (model #{best_index}, "
                f"metrics={validation_metrics[best_index]})"
            )
            if params.validate_per_iteration:
                # ModelTracker snapshots -> per-iteration validation
                # metrics (``Driver.scala:293-347``)
                per_iter: Dict[str, List[Dict[str, float]]] = {}
                for i, tm in enumerate(models):
                    if tm.result.w_history is None:
                        continue
                    # masked_history truncates the ModelTracker buffer
                    # past `iterations` (the entries-are-garbage
                    # contract, solvers/common.SolverResult)
                    hist = tm.result.masked_history()[2]
                    rows = []
                    for it in range(hist.shape[0]):
                        margins = (
                            vbatch.features @ hist[it] + vbatch.offsets
                        )
                        m = metrics_mod.evaluate(
                            task,
                            vbatch.labels,
                            margins,
                            vbatch.effective_weights(),
                        )
                        rows.append(m)
                        logger.info(
                            f"lambda={tm.reg_weight} iteration={it}: {m}"
                        )
                    per_iter[f"{i}_lambda_{tm.reg_weight:g}"] = rows
                with open(
                    os.path.join(
                        params.output_dir, "per-iteration-metrics.json"
                    ),
                    "w",
                ) as f:
                    json.dump(per_iter, f, indent=2)
        tracker.advance(DriverStage.VALIDATED)

    # ---- DIAGNOSE (``Driver.scala:424-474``) -----------------------------
    if params.diagnostics:
        tracker.assert_at_least(DriverStage.VALIDATED)
        with timed(logger, "diagnose"):
            from photon_ml_tpu.diagnostics.driver import (
                build_diagnostic_report,
            )
            from photon_ml_tpu.diagnostics.html import render_html

            report = build_diagnostic_report(
                params_dict=dataclasses.asdict(params),
                models=models,
                validation_metrics=validation_metrics,
                train_batch=batch,
                validation_batch=vbatch,
                vocab=vocab,
                summary=summary,
                training_config=cfg,
                training_diagnostics=params.training_diagnostics,
            )
            report_path = os.path.join(
                params.output_dir, "model-diagnostic.html"
            )
            with open(report_path, "w", encoding="utf-8") as f:
                f.write(render_html(report))
            logger.info(f"wrote diagnostic report to {report_path}")
        tracker.advance(DriverStage.DIAGNOSED)

    # ---- OUTPUT ----------------------------------------------------------
    with timed(logger, "write models"):
        if fingerprint is not None and fingerprint.rows > 0:
            # margin sketch: the shipped model's score distribution on
            # its own training data — what the serving DriftMonitor
            # compares live score distributions against. In-core only
            # (the out-of-core design holds no host batch to score).
            if batch is not None and models:
                chosen = best if best is not None else models[0]
                margins = chosen.model.compute_margin(
                    batch.features, batch.offsets
                )
                fingerprint.observe_margins(
                    np.asarray(margins),
                    np.asarray(batch.effective_weights()),
                )
            fp_path = fingerprint.save(params.output_dir)
            logger.info(f"wrote quality fingerprint to {fp_path}")
        vocab.save(os.path.join(params.output_dir, "feature-index.txt"))
        if params.model_output_mode != "NONE":
            to_write = (
                [best]
                if params.model_output_mode == "BEST" and best is not None
                else models
            )
            mdir = os.path.join(params.output_dir, "models")
            os.makedirs(mdir, exist_ok=True)
            for i, tm in enumerate(to_write):
                stem = os.path.join(mdir, f"{i}_lambda_{tm.reg_weight:g}")
                save_glm_model(
                    stem + ".avro", tm.model.coefficients, vocab, task
                )
                write_model_text(
                    stem + ".txt", tm.model.coefficients.means, vocab
                )
            if best is not None:
                save_glm_model(
                    os.path.join(params.output_dir, "best-model.avro"),
                    best.model.coefficients,
                    vocab,
                    task,
                )
        if validation_metrics:
            with open(
                os.path.join(params.output_dir, "validation-metrics.json"), "w"
            ) as f:
                json.dump(
                    {
                        f"{i}_lambda_{tm.reg_weight:g}": m
                        for i, (tm, m) in enumerate(
                            zip(models, validation_metrics)
                        )
                    },
                    f,
                    indent=2,
                )
    logger.close()

    return GLMTrainingRun(
        params=params,
        stages=tracker.history,
        vocab=vocab,
        models=models,
        best=best,
        best_index=best_index,
        validation_metrics=validation_metrics,
        num_training_rows=(
            design.n if design is not None else int(batch.labels.shape[0])
        ),
        num_features=len(vocab),
        summary=summary,
    )


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="photon_ml_tpu.cli.train",
        description="Train GLMs (logistic/linear/Poisson/smoothed-hinge) "
        "over a regularization path.",
    )
    p.add_argument("--config", help="JSON file of GLMDriverParams")
    p.add_argument("--train-input", nargs="+")
    p.add_argument("--validate-input", nargs="+")
    p.add_argument("--output-dir")
    p.add_argument("--task")
    p.add_argument("--optimizer")
    p.add_argument("--reg-type")
    p.add_argument("--reg-weights", nargs="+", type=float)
    p.add_argument("--normalization")
    p.add_argument("--max-iters", type=int)
    p.add_argument("--tolerance", type=float)
    p.add_argument("--sparse", action="store_true", default=None)
    p.add_argument(
        "--hot-columns", type=int, default=None,
        help="with --sparse: densify the N hottest columns (-1 = auto)",
    )
    p.add_argument(
        "--streamed-ingest", action="store_true", default=None,
        help="stream the dense dataset to the device through the ingest "
        "pipeline (parallel decode, ring staging, async prefetch; host "
        "memory stays the staging ring — docs/INGEST.md)",
    )
    p.add_argument(
        "--out-of-core", action="store_true", default=None,
        help="out-of-core training: keep the design host-side in "
        "uniform chunks and stream every objective pass through the "
        "fused per-chunk programs (exact full-dataset objective; "
        "TRON/LBFGS, normalization NONE — docs/INGEST.md)",
    )
    p.add_argument(
        "--ingest-chunk-mb", type=float, default=None,
        help="ingest pipeline: target decoded-chunk size in MB (file-"
        "group planning + uniform staged row blocks; default 64)",
    )
    p.add_argument(
        "--decode-threads", type=int, default=None,
        help="ingest pipeline: concurrent decode workers (0 = auto; "
        "PHOTON_DECODE_THREADS override honored)",
    )
    p.add_argument(
        "--prefetch-depth", type=int, default=None,
        help="ingest pipeline: chunks decode/staging may run ahead of "
        "the consumer; also sizes the staging ring (default 2)",
    )
    p.add_argument(
        "--stage-timeout-s", type=float, default=None,
        help="ingest pipeline watchdog: a decode/stage/transfer attempt "
        "stalled past this many seconds is cancelled and re-run through "
        "the retry seam (default: off — docs/ROBUSTNESS.md)",
    )
    p.add_argument(
        "--epoch-policy", choices=["fail", "skip"], default=None,
        help="what an exhausted ingest retry budget does to the epoch: "
        "fail (default) raises; skip logs+counts the lost group and "
        "continues with fewer rows",
    )
    p.add_argument("--overwrite", action="store_true", default=None)
    p.add_argument("--diagnostics", action="store_true", default=None)
    p.add_argument(
        "--training-diagnostics", action="store_true", default=None
    )
    p.add_argument("--profile", action="store_true", default=None)
    p.add_argument("--debug-nans", action="store_true", default=None)
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
        help="capture a jax.profiler trace of the WHOLE run here "
        "(--profile captures only the train phase)",
    )
    p.add_argument(
        "--hbm-every", type=float, default=None,
        help="seconds between live HBM counter-track samples while "
        "tracing (0 disables; no-op without device memory stats)",
    )
    p.add_argument(
        "--flight-dir", default=None,
        help="crash flight recorder output directory: flight-<reason>"
        ".json dumps on preemption/crash (default: --trace-dir)",
    )
    p.add_argument(
        "--convergence-report", action="store_true", default=None,
        help="decode each solve's device-side tapes (reason / rate / "
        "plateau / per-iteration curves) into convergence.* metrics + "
        "events and <output-dir>/convergence-report.json",
    )
    p.add_argument(
        "--path-mode", choices=("scan", "loop"), default=None,
        help="regularization-path execution: 'scan' (default) runs the "
        "whole descending-lambda path as ONE device-resident dispatch; "
        "'loop' keeps the host loop of one dispatch per lambda",
    )
    p.add_argument(
        "--heartbeat-s", type=float, default=None,
        help="pod heartbeat interval in seconds (0 = off): feeds the "
        "pod.heartbeat.* liveness gauges and the collective watchdog's "
        "straggler attribution (docs/MULTIHOST.md)",
    )
    p.add_argument(
        "--collective-timeout-s", type=float, default=None,
        help="watchdog deadline on host-side collectives: a stalled "
        "exchange times out, retries with backoff, and emits straggler "
        "attribution instead of wedging the pod (default: no watchdog)",
    )
    p.add_argument(
        "--no-quality-fingerprint", dest="quality_fingerprint",
        action="store_false", default=None,
        help="skip the train-data quality fingerprint (per-feature/"
        "label/margin sketches written to quality-fingerprint.json; "
        "the drift-detection baseline — docs/OBSERVABILITY.md)",
    )
    p.add_argument(
        "--sharded-ckpt", action="store_true", default=None,
        help="per-process sharded checkpoint writes for any durability "
        "point this driver reaches (parity with game_train; the GLM "
        "path itself has no mid-run checkpoint cadence yet — "
        "docs/MULTIHOST.md)",
    )
    p.add_argument(
        "--collective-mode", dest="collective_mode",
        choices=("fused", "overlap"), default=None,
        help="collective reduction strategy for mesh solves "
        "(docs/PARALLEL.md): 'overlap' (default) row-balances blocked "
        "sparse designs and chunks the feature-space reduction into a "
        "reduce-scatter/all-gather pipeline that hides under the next "
        "row block's compute; 'fused' pins the single trailing "
        "all-reduce — the equivalence oracle",
    )
    p.add_argument(
        "--warm-from-watch-root", default=None, metavar="DIR",
        help="lifecycle warm start: resolve initial_model_dir to the "
        "newest manifest-bearing export under this serving watch root "
        "(the descending-lambda path then warm-starts from whatever "
        "is live — docs/LIFECYCLE.md; photon-retrain drives this "
        "automatically)",
    )
    return p


def params_from_args(args, cls) -> dict:
    base = {}
    if args.config:
        with open(args.config) as f:
            base = json.load(f)
    for key, value in vars(args).items():
        if key in ("config", "warm_from_watch_root") or value is None:
            continue
        base[key] = value
    warm_root = getattr(args, "warm_from_watch_root", None)
    if warm_root is not None:
        from photon_ml_tpu.lifecycle.orchestrator import (
            latest_version_dir,
        )

        warm = latest_version_dir(warm_root)
        if warm is None:
            raise ValueError(
                "--warm-from-watch-root: no manifest-bearing export "
                f"under {warm_root}"
            )
        base["initial_model_dir"] = warm
    return base


def main(argv=None) -> None:
    args = build_arg_parser().parse_args(argv)
    # after parse_args: --help / bad flags must not initialize the
    # accelerator backend or touch the cache directory
    from photon_ml_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    try:
        run_glm_training(params_from_args(args, GLMDriverParams))
    except BaseException as e:
        import sys

        from photon_ml_tpu.resilience import (
            HOST_LOSS_EXIT_CODE,
            is_host_loss,
        )

        # distinct exit contract: a dead peer (collective timeout past
        # its retry budget, heartbeat loss) means "restart me", not
        # "my code failed" (docs/MULTIHOST.md)
        if is_host_loss(e):
            print(
                f"host loss: {e} — exiting {HOST_LOSS_EXIT_CODE}",
                file=sys.stderr,
            )
            sys.exit(HOST_LOSS_EXIT_CODE)
        raise


if __name__ == "__main__":
    main()
