"""Model persistence, wire-compatible with the reference.

GLM models: one BayesianLinearModelAvro record (means + optional variances
as (name, term, value) triples) — ``avro/AvroUtils.scala:53-225`` +
``avro/model/ModelProcessingUtils.scala``.

GAME models: the reference's HDFS directory layout
(``ModelProcessingUtils.scala:39-86``):

    <root>/fixed-effect/<coordinate>/{id-info, coefficients/part-00000.avro}
    <root>/random-effect/<coordinate>/{id-info, coefficients/part-00000.avro}

fixed-effect coefficients hold ONE record; random-effect files hold one
record per entity with modelId = the raw entity key. id-info records the
feature-shard id (and random-effect type for RE coordinates).

A random effect trained over a sparse shard through INDEX_MAP is held as
per-entity (column, value) lists (``game.scoring.CompactReTable``), never
an (entities, d) table: it is written from the lists, record for record
the same layout, and its id-info carries ``coefficientLayout=entity-sparse``
so that the loader hands the lists back the same way.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from photon_ml_tpu.core.tasks import TaskType
from photon_ml_tpu.core.types import Coefficients
from photon_ml_tpu.io.avro import read_avro_file, write_avro_file
from photon_ml_tpu.io.schemas import BAYESIAN_LINEAR_MODEL_SCHEMA
from photon_ml_tpu.io.vocab import FeatureVocabulary

# reference loss-function class names (BayesianLinearModelAvro.lossFunction)
_LOSS_CLASS = {
    TaskType.LOGISTIC_REGRESSION: "com.linkedin.photon.ml.function.LogisticLossFunction",
    TaskType.LINEAR_REGRESSION: "com.linkedin.photon.ml.function.SquaredLossFunction",
    TaskType.POISSON_REGRESSION: "com.linkedin.photon.ml.function.PoissonLossFunction",
    TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM: "com.linkedin.photon.ml.function.SmoothedHingeLossFunction",
}
_CLASS_LOSS = {v: k for k, v in _LOSS_CLASS.items()}


def _coefficients_to_record(
    model_id: str,
    means: np.ndarray,
    variances: Optional[np.ndarray],
    vocab: FeatureVocabulary,
    task: Optional[TaskType],
    sparsify: bool = True,
) -> dict:
    def triples(vec):
        out = []
        for i, v in enumerate(vec):
            if sparsify and v == 0.0 and i != vocab.intercept_index:
                continue
            name, term = vocab.name_term(i)
            out.append({"name": name, "term": term, "value": float(v)})
        return out

    return {
        "modelId": model_id,
        "means": triples(means),
        "variances": None if variances is None else triples(variances),
        "lossFunction": _LOSS_CLASS.get(task) if task else None,
    }


def _compact_to_records(compact, vocab, index_to_id, task):
    """One record an entity of a ``CompactReTable``: its stored columns'
    (name, term, value) triples, exact zeros left out as
    :func:`_coefficients_to_record` leaves them."""
    cols = np.asarray(compact.columns)
    vals = np.asarray(compact.values)
    d = len(vocab)
    records = []
    for e in range(cols.shape[0]):
        keep = (cols[e] < d) & (vals[e] != 0.0)
        means = []
        for i, v in zip(cols[e][keep], vals[e][keep]):
            n, t = vocab.name_term(int(i))
            means.append({"name": n, "term": t, "value": float(v)})
        records.append({
            "modelId": str(index_to_id.get(e, e)),
            "means": means,
            "variances": None,
            "lossFunction": _LOSS_CLASS.get(task) if task else None,
        })
    return records


def _records_to_compact(records, vocab, evocab):
    """The inverse of :func:`_compact_to_records`: a ``CompactReTable``
    over ``evocab``'s rows, columns ascending, from the records' triples;
    never a dense (entities, d) table."""
    from photon_ml_tpu.game.projectors import compact_from_lists

    ents, cols, vals = [], [], []
    for rec in records:
        raw = rec["modelId"]
        e = evocab.get(raw, evocab.get(_maybe_int(raw)))
        if e is None:
            continue
        for t in rec["means"]:
            idx = vocab.get(t["name"], t["term"])
            if idx is not None:
                ents.append(e)
                cols.append(idx)
                vals.append(t["value"])
    ents = np.asarray(ents, np.int64)
    cols = np.asarray(cols, np.int64)
    order = np.lexsort((cols, ents))
    return compact_from_lists(
        ents[order], cols[order], np.asarray(vals, np.float64)[order],
        len(evocab), len(vocab))


def remap_compact_rows(compact, own: dict, shared: dict, d: int):
    """:func:`remap_entity_rows` for a ``CompactReTable``: its rows
    re-indexed into ``shared`` (a missing entity holds no column)."""
    from photon_ml_tpu.game.scoring import CompactReTable

    if shared == own:
        return compact
    cols = np.asarray(compact.columns)
    vals = np.asarray(compact.values)
    src = np.fromiter(own.values(), np.int64, count=len(own))
    dst = np.asarray([shared[raw] for raw in own], np.int64)
    out_c = np.full((len(shared), cols.shape[1]), d, cols.dtype)
    out_v = np.zeros((len(shared), vals.shape[1]), vals.dtype)
    out_c[dst], out_v[dst] = cols[src], vals[src]
    return CompactReTable(out_c, out_v)


def _record_to_coefficients(
    rec: dict, vocab: FeatureVocabulary
) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    d = len(vocab)
    means = np.zeros(d)
    for t in rec["means"]:
        idx = vocab.get(t["name"], t["term"])
        if idx is not None:
            means[idx] = t["value"]
    variances = None
    if rec.get("variances"):
        variances = np.zeros(d)
        for t in rec["variances"]:
            idx = vocab.get(t["name"], t["term"])
            if idx is not None:
                variances[idx] = t["value"]
    return means, variances


def save_glm_model(
    path: str,
    coefficients: Coefficients,
    vocab: FeatureVocabulary,
    task: Optional[TaskType] = None,
    model_id: str = "",
):
    means = np.asarray(coefficients.means)
    variances = (
        None
        if coefficients.variances is None
        else np.asarray(coefficients.variances)
    )
    write_avro_file(
        path,
        BAYESIAN_LINEAR_MODEL_SCHEMA,
        [_coefficients_to_record(model_id, means, variances, vocab, task)],
    )


def load_glm_model(
    path: str, vocab: FeatureVocabulary
) -> Tuple[Coefficients, Optional[TaskType]]:
    import jax.numpy as jnp

    _, records = read_avro_file(path)
    if len(records) != 1:
        raise ValueError(f"{path}: expected 1 model record, got {len(records)}")
    means, variances = _record_to_coefficients(records[0], vocab)
    task = _CLASS_LOSS.get(records[0].get("lossFunction"))
    return (
        Coefficients(
            means=jnp.asarray(means),
            variances=None if variances is None else jnp.asarray(variances),
        ),
        task,
    )


# ---------------------------------------------------------------------------
# Model-export integrity manifests (the serving hot-reload gate)
# ---------------------------------------------------------------------------

MODEL_MANIFEST = "model-manifest.json"


class ModelIntegrityError(Exception):
    """A model export failed sha256 manifest verification — partially
    written, tampered with, or missing its manifest entirely."""


_MODEL_KINDS = ("fixed-effect", "random-effect", "factored-random-effect")

# id-info's ``coefficientLayout`` of a random effect saved as per-entity
# (column, value) lists
ENTITY_SPARSE = "entity-sparse"


def _manifest_files(root: str) -> List[str]:
    """Model-BEARING files under an export root: coordinate directories
    (at any nesting — ``best/``, ``all/<i>/``), feature-index vocabularies,
    and model-spec.json. Volatile run artifacts riding along in a training
    output dir (logs, checkpoints, metrics) are deliberately outside the
    integrity boundary — they keep changing after the export is sealed."""
    out = []
    for dirpath, _, files in os.walk(root):
        for name in sorted(files):
            if name == MODEL_MANIFEST:
                continue
            rel = os.path.relpath(os.path.join(dirpath, name), root)
            parts = rel.split(os.sep)
            if (
                any(p in _MODEL_KINDS for p in parts[:-1])
                or (name.startswith("feature-index-") and name.endswith(".txt"))
                or name == "model-spec.json"
            ):
                out.append(rel)
    return sorted(out)


def write_model_manifest(root: str) -> str:
    """Walk a model export directory and record a sha256 digest per file in
    ``<root>/model-manifest.json`` — the same integrity scheme as training
    checkpoints (:mod:`photon_ml_tpu.io.checkpoint`). The serving registry
    refuses to hot-reload an export whose digests do not verify, so a
    partially-written or torn export can never serve."""
    from photon_ml_tpu.io.checkpoint import sha256_file

    digests = {
        rel: sha256_file(os.path.join(root, rel))
        for rel in _manifest_files(root)
    }
    if not digests:
        raise ValueError(
            f"{root}: no model files to manifest (an empty manifest would "
            "verify vacuously and defeat the serving integrity gate)"
        )
    path = os.path.join(root, MODEL_MANIFEST)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"created": time.time(), "digests": digests}, f, indent=2)
    os.replace(tmp, path)  # atomic: a reader never sees a torn manifest
    return path


def verify_model_manifest(root: str, require: bool = True) -> Dict[str, str]:
    """Verify every digest in ``<root>/model-manifest.json`` against the
    files on disk. Raises :class:`ModelIntegrityError` on a missing file or
    digest mismatch — and on a missing manifest when ``require`` (files the
    manifest does not list are ignored: logs and metrics riding along in
    the export directory are not integrity-bearing). Returns the verified
    ``{relpath: digest}`` map."""
    from photon_ml_tpu.io.checkpoint import sha256_file

    path = os.path.join(root, MODEL_MANIFEST)
    if not os.path.exists(path):
        if require:
            raise ModelIntegrityError(f"{root}: no {MODEL_MANIFEST}")
        return {}
    try:
        with open(path) as f:
            manifest = json.load(f)
        digests = manifest["digests"]
    except (OSError, json.JSONDecodeError, KeyError) as e:
        raise ModelIntegrityError(f"{path}: unreadable manifest ({e})") from e
    for rel, want in digests.items():
        fpath = os.path.join(root, rel)
        if not os.path.exists(fpath):
            raise ModelIntegrityError(f"{root}: missing {rel}")
        got = sha256_file(fpath)
        if got != want:
            raise ModelIntegrityError(
                f"{root}: {rel} digest mismatch "
                f"(manifest {want[:12]}…, file {got[:12]}…)"
            )
    return digests


# ---------------------------------------------------------------------------
# GAME model directories
# ---------------------------------------------------------------------------


def save_game_model(
    root: str,
    params: Dict[str, np.ndarray],
    shards: Dict[str, str],
    vocabs: Dict[str, FeatureVocabulary],
    entity_vocabs: Dict[str, dict],
    random_effects: Dict[str, Optional[str]],
    task: Optional[TaskType] = None,
):
    """params: coordinate -> (d,) fixed or (E, d) random-effect table, or
    a random effect's per-entity lists (``game.scoring.CompactReTable``,
    written as ``coefficientLayout=entity-sparse``).
    shards: coordinate -> feature shard id; vocabs: coordinate -> vocab;
    entity_vocabs: coordinate -> {raw_id: index} for RE coordinates;
    random_effects: coordinate -> RE type name or None (fixed)."""
    from photon_ml_tpu.game.scoring import CompactReTable

    for name, table in params.items():
        if _is_factored(table):
            _save_factored_coordinate(
                root, name, table, shards[name],
                random_effects.get(name), entity_vocabs.get(name, {}),
                vocabs[name],
            )
            continue
        compact = isinstance(table, CompactReTable)
        if not compact:
            table = np.asarray(table)
        re_type = random_effects.get(name)
        kind = "fixed-effect" if re_type is None else "random-effect"
        cdir = os.path.join(root, kind, name)
        os.makedirs(os.path.join(cdir, "coefficients"), exist_ok=True)
        with open(os.path.join(cdir, "id-info"), "w") as f:
            f.write(f"featureShardId={shards[name]}\n")
            if re_type is not None:
                f.write(f"randomEffectType={re_type}\n")
            if compact:
                f.write(f"coefficientLayout={ENTITY_SPARSE}\n")
        vocab = vocabs[name]
        if compact:
            records = _compact_to_records(
                table, vocab,
                {v: k for k, v in entity_vocabs[name].items()}, task)
        elif re_type is None:
            records = [
                _coefficients_to_record(name, table, None, vocab, task)
            ]
        else:
            index_to_id = {
                v: k for k, v in entity_vocabs[name].items()
            }
            records = [
                _coefficients_to_record(
                    str(index_to_id.get(e, e)), table[e], None, vocab, task
                )
                for e in range(table.shape[0])
            ]
        write_avro_file(
            os.path.join(cdir, "coefficients", "part-00000.avro"),
            BAYESIAN_LINEAR_MODEL_SCHEMA,
            records,
        )


def load_game_model(
    root: str,
    vocabs: Dict[str, FeatureVocabulary],
    entity_vocabs: Optional[Dict[str, dict]] = None,
):
    """Returns (params, shards, random_effects, entity_vocabs) mirroring
    save_game_model. Unknown coordinates on disk are loaded by directory
    name. The returned entity_vocabs maps each random-effect coordinate to
    its {raw_id: row} table mapping — when the caller didn't supply one, the
    mapping is constructed from record order and MUST be used to index the
    table (row order on disk is not otherwise meaningful)."""
    params: Dict[str, np.ndarray] = {}
    shards: Dict[str, str] = {}
    random_effects: Dict[str, Optional[str]] = {}
    entity_vocabs_out: Dict[str, dict] = {}
    for kind in ("fixed-effect", "random-effect"):
        kdir = os.path.join(root, kind)
        if not os.path.isdir(kdir):
            continue
        for name in sorted(os.listdir(kdir)):
            if name not in vocabs:
                # a coordinate the caller has no vocabulary for (dropped
                # from the config, or a collapsed-merge name) cannot be
                # decoded — skip it instead of KeyError-ing the whole load
                continue
            cdir = os.path.join(kdir, name)
            info = {}
            with open(os.path.join(cdir, "id-info")) as f:
                for line in f:
                    if "=" in line:
                        k, v = line.strip().split("=", 1)
                        info[k] = v
            shards[name] = info.get("featureShardId", name)
            random_effects[name] = info.get("randomEffectType")
            vocab = vocabs[name]
            _, records = read_avro_file(
                os.path.join(cdir, "coefficients", "part-00000.avro")
            )
            if kind == "fixed-effect":
                means, _ = _record_to_coefficients(records[0], vocab)
                params[name] = means
            else:
                if entity_vocabs is not None and name in entity_vocabs:
                    evocab = entity_vocabs[name]
                else:
                    evocab = {
                        rec["modelId"]: i for i, rec in enumerate(records)
                    }
                entity_vocabs_out[name] = dict(evocab)
                if info.get("coefficientLayout") == ENTITY_SPARSE:
                    params[name] = _records_to_compact(
                        records, vocab, evocab)
                    continue
                table = np.zeros((len(evocab), len(vocab)))
                for rec in records:
                    raw = rec["modelId"]
                    e = evocab.get(raw, evocab.get(_maybe_int(raw)))
                    if e is not None:
                        table[e], _ = _record_to_coefficients(rec, vocab)
                params[name] = table
    fdir = os.path.join(root, "factored-random-effect")
    if os.path.isdir(fdir):
        for name in sorted(os.listdir(fdir)):
            if name not in vocabs:
                continue
            cdir = os.path.join(fdir, name)
            evocab = (
                entity_vocabs.get(name) if entity_vocabs is not None else None
            )
            fparams, info, evocab = load_factored_coordinate(
                cdir, vocabs[name], evocab
            )
            params[name] = fparams
            shards[name] = info.get("featureShardId", name)
            random_effects[name] = info.get("randomEffectType")
            entity_vocabs_out[name] = evocab
    return params, shards, random_effects, entity_vocabs_out


def _maybe_int(s):
    try:
        return int(s)
    except (TypeError, ValueError):
        return s


def union_entity_vocab(vocabs) -> dict:
    """Union of raw entity ids over an iterable of {raw: row} vocabs,
    assigned rows in first-seen order."""
    out: dict = {}
    for vocab in vocabs:
        for raw in vocab:
            out.setdefault(raw, len(out))
    return out


def remap_entity_rows(
    table: np.ndarray, own: dict, shared: dict
) -> np.ndarray:
    """Re-index a per-entity row table from its own {raw: row} vocab into a
    shared one (missing entities keep zero rows — the cogroup
    missing-entity-scores-0 semantic). Identity vocab: returns the input
    unchanged (no copy)."""
    table = np.asarray(table)
    if shared == own:
        return table
    src = np.fromiter(own.values(), np.int64, count=len(own))
    dst = np.asarray([shared[raw] for raw in own], np.int64)
    out = np.zeros((len(shared), table.shape[1]), table.dtype)
    out[dst] = table[src]
    return out


def resolve_game_dirs(root: str) -> Tuple[str, str]:
    """(model_root, vocab_root): model_root holds fixed-effect/random-effect
    subdirs — the training-output root itself, its 'best' child, or the
    first 'all/<i>' child; vocab_root holds the feature-index-*.txt files
    (the training-output root, walking up from model_root)."""

    def has_model(d):
        return os.path.isdir(os.path.join(d, "fixed-effect")) or os.path.isdir(
            os.path.join(d, "random-effect")
        )

    candidates = [root, os.path.join(root, "best")]
    all_dir = os.path.join(root, "all")
    if os.path.isdir(all_dir):
        candidates += [
            os.path.join(all_dir, s) for s in sorted(os.listdir(all_dir))
        ]
    model_root = next((c for c in candidates if has_model(c)), None)
    if model_root is None:
        raise FileNotFoundError(
            f"no GAME model (fixed-effect/random-effect dirs) under {root}"
        )

    def has_vocabs(d):
        return any(
            f.startswith("feature-index-") and f.endswith(".txt")
            for f in os.listdir(d)
        )

    vocab_root = model_root
    while not has_vocabs(vocab_root):
        parent = os.path.dirname(vocab_root.rstrip(os.sep))
        if not parent or parent == vocab_root:
            raise FileNotFoundError(
                f"no feature-index-*.txt vocab files found at or above "
                f"{model_root}"
            )
        vocab_root = parent
    return model_root, vocab_root


def load_game_model_auto(root: str):
    """One-call GAME model load for scoring: resolve the model/vocab dirs
    under a training-output root, load every coordinate, and merge entity
    vocabularies per random-effect TYPE (the union over the coordinates
    sharing it — data is indexed once per type, and each coordinate's table
    rows must live in that shared space; a first-coordinate-wins merge
    would silently misattribute per-entity rows). Coordinates lacking an
    entity contribute zero rows — the reference's missing-entity-scores-0
    cogroup semantic.

    Returns ``(params, shards, random_effects, shard_vocabs, re_vocabs)``
    where ``shard_vocabs`` maps feature-shard id -> FeatureVocabulary and
    ``re_vocabs`` maps random-effect type -> shared {raw_id: row} vocab.
    Shared by the offline scoring driver (:mod:`photon_ml_tpu.cli.score`)
    and the online engine (:mod:`photon_ml_tpu.serving.engine`)."""
    import jax.numpy as jnp

    from photon_ml_tpu.game.factored import FactoredParams, is_factored_params
    from photon_ml_tpu.game.scoring import CompactReTable

    model_root, vocab_root = resolve_game_dirs(root)
    vocab_files = {
        f[len("feature-index-"):-len(".txt")]: os.path.join(vocab_root, f)
        for f in os.listdir(vocab_root)
        if f.startswith("feature-index-") and f.endswith(".txt")
    }
    shard_vocabs = {
        shard: FeatureVocabulary.load(path)
        for shard, path in vocab_files.items()
    }
    # coordinate -> shard comes from id-info; vocabs keyed per coordinate
    # for load_game_model
    coord_shards: Dict[str, str] = {}
    for kind in ("fixed-effect", "random-effect", "factored-random-effect"):
        kdir = os.path.join(model_root, kind)
        if not os.path.isdir(kdir):
            continue
        for name in os.listdir(kdir):
            with open(os.path.join(kdir, name, "id-info")) as f:
                for line in f:
                    if line.startswith("featureShardId="):
                        coord_shards[name] = line.strip().split("=", 1)[1]
    coord_vocabs = {
        name: shard_vocabs[shard] for name, shard in coord_shards.items()
    }
    params, shards, random_effects, entity_vocabs = load_game_model(
        model_root, coord_vocabs
    )
    re_vocabs: Dict[str, dict] = {}
    for re_key in sorted(
        {re for re in random_effects.values() if re is not None}
    ):
        re_vocabs[re_key] = union_entity_vocab(
            entity_vocabs[name]
            for name, rk in random_effects.items()
            if rk == re_key
        )
    for name, re_key in random_effects.items():
        if re_key is None:
            continue
        shared = re_vocabs[re_key]
        own = entity_vocabs[name]
        p = params[name]
        if is_factored_params(p):
            params[name] = FactoredParams(
                gamma=jnp.asarray(remap_entity_rows(p.gamma, own, shared)),
                projection=p.projection,
            )
        elif isinstance(p, CompactReTable):
            params[name] = remap_compact_rows(
                p, own, shared, len(coord_vocabs[name]))
        else:
            params[name] = remap_entity_rows(p, own, shared)
    return params, shards, random_effects, shard_vocabs, re_vocabs


def collapse_game_model(
    params: Dict[str, np.ndarray],
    shards: Dict[str, str],
    random_effects: Dict[str, Optional[str]],
    entity_vocabs: Dict[str, dict],
):
    """Merge coordinates sharing (effect type, feature shard) by
    coefficient ADDITION (``ModelProcessingUtils.collapseGameModel``
    :224-264): fixed-effect vectors sum directly; random-effect tables
    cogroup on the raw entity id (an entity absent from one coordinate
    contributes zeros). Returns (params, shards, random_effects,
    entity_vocabs) with merged coordinates named "<effect>-<shard>".
    Factored coordinates are rejected like the reference's
    UnsupportedOperationException for unknown model types."""
    from photon_ml_tpu.game.scoring import CompactReTable

    groups: Dict[Tuple[str, str], List[str]] = {}
    for name in params:
        if _is_factored(params[name]):
            raise ValueError(
                f"collapse of factored coordinate {name!r} is not supported "
                "(reference ModelProcessingUtils.scala:235-236)"
            )
        effect = random_effects.get(name) or "fixed-effect"
        groups.setdefault((effect, shards[name]), []).append(name)
    for names in groups.values():
        if len(names) > 1 and any(
                isinstance(params[n], CompactReTable) for n in names):
            raise ValueError(
                f"collapse of per-entity sparse coordinates {names} into "
                "one is not supported")

    out_params: Dict[str, np.ndarray] = {}
    out_shards: Dict[str, str] = {}
    out_res: Dict[str, Optional[str]] = {}
    out_evocabs: Dict[str, dict] = {}
    for (effect, shard), names in groups.items():
        merged_name = f"{effect}-{shard}"
        out_shards[merged_name] = shard
        re_type = random_effects.get(names[0])
        out_res[merged_name] = re_type
        if re_type is None:
            out_params[merged_name] = np.sum(
                [np.asarray(params[n]) for n in names], axis=0
            )
            continue
        if len(names) == 1 and isinstance(params[names[0]], CompactReTable):
            out_params[merged_name] = params[names[0]]
            out_evocabs[merged_name] = entity_vocabs[names[0]]
            continue
        # cogroup random-effect tables on raw entity ids
        merged_vocab = union_entity_vocab(
            entity_vocabs[n] for n in names
        )
        d = np.asarray(params[names[0]]).shape[1]
        table = np.zeros((len(merged_vocab), d))
        for n in names:
            table += remap_entity_rows(
                params[n], entity_vocabs[n], merged_vocab
            )
        out_params[merged_name] = table
        out_evocabs[merged_name] = merged_vocab
    return out_params, out_shards, out_res, out_evocabs


# ---------------------------------------------------------------------------
# Factored random effects (latent-factor wire format,
# ``ModelProcessingUtils.saveMatrixFactorizationModelToHDFS`` :274-332)
# ---------------------------------------------------------------------------


def _is_factored(table) -> bool:
    from photon_ml_tpu.game.factored import is_factored_params

    return is_factored_params(table)


def _write_latent_factor_table(
    path: str, table: np.ndarray, vocab: Optional[dict]
) -> None:
    """(rows, k) -> LatentFactorAvro records keyed by the vocab's raw ids
    (positional string ids when no vocab)."""
    from photon_ml_tpu.io.schemas import LATENT_FACTOR_SCHEMA

    index_to_id = {v: k for k, v in vocab.items()} if vocab else {}
    write_avro_file(
        path,
        LATENT_FACTOR_SCHEMA,
        [
            {
                "effectId": str(index_to_id.get(i, i)),
                "latentFactor": [float(v) for v in table[i]],
            }
            for i in range(table.shape[0])
        ],
    )


def _fill_table_from_latent_records(
    records, vocab: Optional[dict], what: str
):
    """LatentFactorAvro records -> ((rows, k) table, vocab). Builds the
    vocab from record order when absent; raises on records whose id the
    vocab cannot place (silent drops would corrupt scoring)."""
    if vocab is None:
        vocab = {rec["effectId"]: i for i, rec in enumerate(records)}
    k = len(records[0]["latentFactor"]) if records else 1
    table = np.zeros((len(vocab), k))
    for rec in records:
        raw = rec["effectId"]
        i = vocab.get(raw, vocab.get(_maybe_int(raw)))
        if i is None:
            raise ValueError(
                f"{what}: record id {raw!r} is not in the provided "
                "vocabulary — refusing a silently truncated table"
            )
        table[i] = rec["latentFactor"]
    return table, dict(vocab)


def _save_factored_coordinate(
    root: str,
    name: str,
    params,  # FactoredParams
    shard: str,
    re_type: Optional[str],
    entity_vocab: dict,
    vocab: FeatureVocabulary,
):
    """w_e = B gamma_e saved as two LatentFactorAvro tables: gamma rows
    keyed by raw entity id, projection rows keyed by the feature key —
    the factorization survives the round trip (materializing (E, d) would
    defeat the representation's point)."""
    from photon_ml_tpu.io.schemas import LATENT_FACTOR_SCHEMA

    gamma = np.asarray(params.gamma)
    projection = np.asarray(params.projection)
    cdir = os.path.join(root, "factored-random-effect", name)
    os.makedirs(cdir, exist_ok=True)
    with open(os.path.join(cdir, "id-info"), "w") as f:
        f.write(f"featureShardId={shard}\n")
        if re_type is not None:
            f.write(f"randomEffectType={re_type}\n")
        f.write(f"latentDim={gamma.shape[1]}\n")
    _write_latent_factor_table(
        os.path.join(cdir, "latent-factors.avro"), gamma, entity_vocab
    )
    write_avro_file(
        os.path.join(cdir, "projection.avro"),
        LATENT_FACTOR_SCHEMA,
        [
            {
                "effectId": "{}\x01{}".format(*vocab.name_term(j)),
                "latentFactor": [float(v) for v in projection[j]],
            }
            for j in range(projection.shape[0])
        ],
    )


def save_mf_model(
    root: str,
    model,  # game.factored.MatrixFactorizationModel
    row_effect_type: str,
    col_effect_type: str,
    row_vocab: Optional[dict] = None,
    col_vocab: Optional[dict] = None,
):
    """Matrix-factorization model -> <root>/<rowEffectType>/ and
    <root>/<colEffectType>/ LatentFactorAvro files
    (``ModelProcessingUtils.saveMatrixFactorizationModelToHDFS``
    :267-296). Vocab dicts map raw ids -> row index; positional string ids
    are used when absent."""
    from photon_ml_tpu.io.schemas import LATENT_FACTOR_SCHEMA

    if row_effect_type == col_effect_type:
        raise ValueError(
            "row and col effect types must differ (they name directories)"
        )
    for effect, factors, vocab in (
        (row_effect_type, np.asarray(model.row_factors), row_vocab),
        (col_effect_type, np.asarray(model.col_factors), col_vocab),
    ):
        edir = os.path.join(root, effect)
        os.makedirs(edir, exist_ok=True)
        _write_latent_factor_table(
            os.path.join(edir, "part-00000.avro"), factors, vocab
        )


def load_mf_model(
    root: str,
    row_effect_type: str,
    col_effect_type: str,
    row_vocab: Optional[dict] = None,
    col_vocab: Optional[dict] = None,
):
    """Inverse of :func:`save_mf_model`
    (``ModelProcessingUtils.loadMatrixFactorizationModelFromHDFS``
    :303-332). Returns (MatrixFactorizationModel, row_vocab, col_vocab)."""
    import jax.numpy as jnp

    from photon_ml_tpu.game.factored import MatrixFactorizationModel

    def load_side(effect, vocab):
        _, records = read_avro_file(
            os.path.join(root, effect, "part-00000.avro")
        )
        table, vocab = _fill_table_from_latent_records(
            records, vocab, f"MF {effect}"
        )
        return jnp.asarray(table), vocab

    rows, row_vocab = load_side(row_effect_type, row_vocab)
    cols, col_vocab = load_side(col_effect_type, col_vocab)
    return MatrixFactorizationModel(rows, cols), row_vocab, col_vocab


def load_factored_coordinate(
    cdir: str,
    vocab: FeatureVocabulary,
    entity_vocab: Optional[dict] = None,
):
    """Returns (FactoredParams, info dict, entity_vocab)."""
    import jax.numpy as jnp

    from photon_ml_tpu.game.factored import FactoredParams

    info = {}
    with open(os.path.join(cdir, "id-info")) as f:
        for line in f:
            if "=" in line:
                k, v = line.strip().split("=", 1)
                info[k] = v
    k = int(info["latentDim"])
    _, grecords = read_avro_file(os.path.join(cdir, "latent-factors.avro"))
    gamma, entity_vocab = _fill_table_from_latent_records(
        grecords, entity_vocab, f"factored coordinate {cdir}"
    )
    _, precords = read_avro_file(os.path.join(cdir, "projection.avro"))
    projection = np.zeros((len(vocab), k))
    for rec in precords:
        name, _, term = rec["effectId"].partition("\x01")
        idx = vocab.get(name, term)
        if idx is not None:
            projection[idx] = rec["latentFactor"]
    return (
        FactoredParams(
            gamma=jnp.asarray(gamma), projection=jnp.asarray(projection)
        ),
        info,
        entity_vocab,
    )
