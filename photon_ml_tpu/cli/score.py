"""Scoring driver: load a trained model, score Avro data, write ScoredItems.

Rebuild of ``cli/game/scoring/Driver.scala:40-254``: load the GAME model
directory (or a single GLM model file), convert input records, score (total
= sum of sub-model scores + offset), write ScoringResultAvro records, and
optionally evaluate AUC / RMSE when labels are present (:166-185). Run as

    python -m photon_ml_tpu.cli.score --config params.json

or programmatically via :func:`run_scoring`.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.cli.config import ScoringParams, load_params
from photon_ml_tpu.cli.train import (
    log_driver_runtime,
    prepare_output_dir,
    resolve_date_range,
)
from photon_ml_tpu.core.tasks import TaskType
from photon_ml_tpu.game.scoring import score_game_data
from photon_ml_tpu.io.avro import write_avro_file
from photon_ml_tpu.io.models import load_glm_model
from photon_ml_tpu.io.schemas import SCORING_RESULT_SCHEMA
from photon_ml_tpu.io.vocab import FeatureVocabulary
from photon_ml_tpu.ops import metrics as metrics_mod
from photon_ml_tpu.utils.dates import expand_date_paths
from photon_ml_tpu.utils.logging import PhotonLogger, timed


@dataclasses.dataclass
class ScoringRun:
    params: ScoringParams
    scores: np.ndarray
    labels: Optional[np.ndarray]
    metrics: Dict[str, float]
    output_path: str


# moved to io.models so the online engine shares it; alias kept for callers
from photon_ml_tpu.io.models import resolve_game_dirs as _resolve_game_dirs


def write_scored_items(
    out_path: str,
    scores: np.ndarray,
    uids: np.ndarray,
    labels: np.ndarray,
    label_present: np.ndarray,
) -> int:
    """ScoringResultAvro output, natively encoded straight from the score
    arrays when the C++ codec is available (no per-record dicts), Python
    codec otherwise. Both paths write an empty-string uid as null (the
    native pool encoding cannot distinguish them, and ingest already
    normalizes "" to absent)."""
    n = len(scores)
    try:
        from photon_ml_tpu.io.native import native_available, write_columnar_avro

        if native_available():
            write_columnar_avro(
                out_path,
                SCORING_RESULT_SCHEMA,
                {
                    "predictionScore": scores,
                    "uid": uids,
                    "label": (labels, label_present),
                    "metadataMap": None,
                },
                n,
            )
            return n
    except Exception:  # noqa: BLE001 — fall back, but never silently
        import logging

        logging.getLogger("photon_ml_tpu").warning(
            "native Avro writer failed (%s); falling back to the Python "
            "codec for %s",
            sys.exc_info()[1],
            out_path,
        )
    write_avro_file(
        out_path,
        SCORING_RESULT_SCHEMA,
        [
            {
                "predictionScore": float(s),
                "uid": None if (u is None or u == "") else str(u),
                "label": float(l) if p else None,
                "metadataMap": None,
            }
            for s, u, l, p in zip(scores, uids, labels, label_present)
        ],
    )
    return n


def run_scoring(params) -> ScoringRun:
    params = load_params(params, ScoringParams)
    params.validate()
    prepare_output_dir(params.output_dir, params.overwrite)
    logger = PhotonLogger(
        os.path.join(params.output_dir, "log-message.txt"),
        level=params.log_level,
    )
    task = TaskType[params.task]
    date_range = resolve_date_range(params)
    from photon_ml_tpu.io.ingest import IngestSource

    source = IngestSource(
        expand_date_paths(params.input, date_range), params.field_names
    )
    logger.info(f"scoring records with {params.model_kind} "
                f"model from {params.model_dir}")
    log_driver_runtime(logger)

    with timed(logger, "score"):
        if params.model_kind == "glm":
            vocab = FeatureVocabulary.load(
                os.path.join(params.model_dir, "feature-index.txt")
            )
            if params.model_path:
                model_path = params.model_path
                if not os.path.exists(model_path):
                    raise FileNotFoundError(
                        f"model_path {model_path!r} does not exist"
                    )
            else:
                model_path = os.path.join(params.model_dir, "best-model.avro")
            if not os.path.exists(model_path):
                mdir = os.path.join(params.model_dir, "models")
                candidates = sorted(
                    f for f in os.listdir(mdir) if f.endswith(".avro")
                )
                if len(candidates) != 1:
                    raise FileNotFoundError(
                        f"no best-model.avro in {params.model_dir} and "
                        f"{len(candidates)} candidates in models/ — set "
                        "model_path to the .avro you want scored (an "
                        "arbitrary lambda would be silently scored "
                        f"otherwise): {candidates}"
                    )
                logger.warn(
                    f"best-model.avro absent; using the only model in "
                    f"models/: {candidates[0]}"
                )
                model_path = os.path.join(mdir, candidates[0])
            coefficients, model_task = load_glm_model(model_path, vocab)
            if model_task is not None:
                task = model_task
            batch, uids, label_present = source.labeled_batch(
                vocab, sparse=params.sparse, dtype=jnp.float64,
                allow_null_labels=True,
            )
            from photon_ml_tpu.ops.sparse import matvec

            margins = (
                matvec(batch.features, jnp.asarray(coefficients.means, jnp.float64))
                + batch.offsets
            )
            labels = np.asarray(batch.labels)
            weights = np.asarray(batch.effective_weights())
        else:
            # GAME directory layout; shard vocabs saved next to the model.
            # load_game_model_auto (io/models.py, shared with the online
            # serving engine) resolves dirs, loads coordinates, and merges
            # entity vocabularies per random-effect TYPE.
            from photon_ml_tpu.io.models import load_game_model_auto

            (
                model_params,
                shards,
                random_effects,
                shard_vocabs,
                re_vocabs,
            ) = load_game_model_auto(params.model_dir)
            entity_keys = sorted(re_vocabs)
            data, _, uids, label_present = source.game_data(
                shard_vocabs,
                entity_keys,
                entity_vocabs=re_vocabs,
                allow_null_labels=True,
                sparse_shards=set(params.sparse_shards),
            )
            # Pad to the serving engine's power-of-two buckets: ragged
            # final batches would otherwise compile a fresh executable per
            # distinct row count; padded rows carry zero features and
            # entity -1, and are sliced off host-side.
            from photon_ml_tpu.serving.engine import bucket_size, pad_game_data

            n = data.num_rows
            padded = pad_game_data(data, bucket_size(n))
            margins = np.asarray(
                score_game_data(
                    model_params, shards, random_effects, padded
                )
                + jnp.asarray(padded.offsets)
            )[:n]
            labels = np.asarray(data.labels)
            weights = np.asarray(data.weights)

        scores = np.asarray(margins, np.float64)

    # ---- write ScoredItems (``ScoredItem.scala`` / scoring Driver) -------
    out_path = os.path.join(params.output_dir, "scores", "part-00000.avro")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    has_labels = bool(label_present.any())
    n_out = write_scored_items(out_path, scores, uids, labels, label_present)
    logger.info(f"wrote {n_out} scored items to {out_path}")

    # ---- optional evaluation (:166-185) ----------------------------------
    eval_metrics: Dict[str, float] = {}
    if params.evaluate:
        if not has_labels:
            raise ValueError("evaluate=True but input records carry no labels")
        ev_labels, ev_scores, ev_weights = labels, scores, weights
        if not label_present.all():
            # unlabeled rows carry a coerced 0.0 label — drop them from
            # the evaluation arrays entirely (this is a host-side metric
            # pass, so the dynamic shape is fine)
            logger.warn(
                f"{int((~label_present).sum())} of {len(label_present)} records "
                "have no label; excluding them from evaluation"
            )
            ev_labels = labels[label_present]
            ev_scores = scores[label_present]
            ev_weights = weights[label_present]
        eval_metrics = metrics_mod.evaluate(
            task,
            jnp.asarray(ev_labels),
            jnp.asarray(ev_scores),
            jnp.asarray(ev_weights),
        )
        with open(os.path.join(params.output_dir, "metrics.json"), "w") as f:
            json.dump(eval_metrics, f, indent=2)
        logger.info(f"evaluation: {eval_metrics}")
    logger.close()

    return ScoringRun(
        params=params,
        scores=scores,
        labels=labels if has_labels else None,
        metrics=eval_metrics,
        output_path=out_path,
    )


def main(argv=None) -> None:
    p = argparse.ArgumentParser(
        prog="photon_ml_tpu.cli.score",
        description="Score data with a trained GLM or GAME model.",
    )
    p.add_argument("--config", required=True, help="JSON ScoringParams")
    p.add_argument("--overwrite", action="store_true", default=None)
    args = p.parse_args(argv)
    # after parse_args: --help / bad flags must not initialize
    # the accelerator backend or touch the cache directory
    from photon_ml_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    with open(args.config) as f:
        base = json.load(f)
    if args.overwrite is not None:
        base["overwrite"] = args.overwrite
    run_scoring(base)


if __name__ == "__main__":
    main()
