"""I/O tests: Avro codec round-trips (incl. binary-compat checks against
hand-decoded bytes), vocabulary build/save/load, ingest semantics
(dedup-by-sum, intercept, missing features), model save/load round-trips
(GLM + GAME layout)."""

import io as pyio
import os

import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.core.tasks import TaskType
from photon_ml_tpu.core.types import Coefficients
from photon_ml_tpu.io import (
    BAYESIAN_LINEAR_MODEL_SCHEMA,
    TRAINING_EXAMPLE_SCHEMA,
    FeatureVocabulary,
    labeled_batch_from_avro,
    load_game_model,
    load_glm_model,
    read_avro_file,
    save_game_model,
    save_glm_model,
    training_examples_to_arrays,
    write_avro_file,
)
from photon_ml_tpu.io.avro import _decode_long, _encode_long, read_avro_dir
from photon_ml_tpu.io.ingest import make_training_example
from photon_ml_tpu.io.vocab import INTERCEPT_KEY, feature_key


class TestVarint:
    @pytest.mark.parametrize(
        "n", [0, 1, -1, 2, -2, 63, 64, -64, -65, 1 << 20, -(1 << 20), (1 << 62)]
    )
    def test_zigzag_round_trip(self, n):
        assert _decode_long(pyio.BytesIO(_encode_long(n))) == n

    def test_known_encodings(self):
        # Avro spec examples: 0->00, -1->01, 1->02, -2->03, 2->04
        assert _encode_long(0) == b"\x00"
        assert _encode_long(-1) == b"\x01"
        assert _encode_long(1) == b"\x02"
        assert _encode_long(-2) == b"\x03"
        assert _encode_long(2) == b"\x04"


class TestContainerRoundTrip:
    def records(self):
        return [
            make_training_example(
                1.0,
                {("age", ""): 0.5, ("country", "us"): 1.0},
                uid="u1",
                weight=2.0,
            ),
            make_training_example(
                0.0, {("age", ""): -1.5}, offset=0.25
            ),
        ]

    @pytest.mark.parametrize("codec", ["null", "deflate"])
    def test_round_trip(self, tmp_path, codec):
        path = str(tmp_path / "t.avro")
        write_avro_file(
            path, TRAINING_EXAMPLE_SCHEMA, self.records(), codec=codec
        )
        schema, recs = read_avro_file(path)
        assert schema["name"] == "TrainingExampleAvro"
        assert recs[0]["uid"] == "u1"
        assert recs[0]["weight"] == 2.0
        assert recs[0]["offset"] is None
        assert recs[1]["offset"] == 0.25
        assert recs[1]["features"][0]["value"] == -1.5

    def test_many_records_multi_block(self, tmp_path):
        path = str(tmp_path / "big.avro")
        recs = [
            make_training_example(float(i % 2), {("f", str(i % 7)): i * 0.1})
            for i in range(500)
        ]
        write_avro_file(path, TRAINING_EXAMPLE_SCHEMA, recs, block_size=512)
        _, out = read_avro_file(path)
        assert len(out) == 500
        assert out[499]["features"][0]["value"] == pytest.approx(49.9)

    def test_read_dir(self, tmp_path):
        for i in range(3):
            write_avro_file(
                str(tmp_path / f"part-0000{i}.avro"),
                TRAINING_EXAMPLE_SCHEMA,
                [make_training_example(float(i), {("x", ""): 1.0})],
            )
        _, recs = read_avro_dir(str(tmp_path))
        assert [r["label"] for r in recs] == [0.0, 1.0, 2.0]


class TestVocabulary:
    def test_build_save_load(self, tmp_path):
        recs = [
            make_training_example(1.0, {("b", "t1"): 1.0, ("a", ""): 2.0}),
            make_training_example(0.0, {("b", "t1"): 3.0, ("c", "x"): 1.0}),
        ]
        vocab = FeatureVocabulary.from_records(recs, add_intercept=True)
        assert len(vocab) == 4  # a, b:t1, c:x + intercept
        assert vocab.intercept_index == 3
        path = str(tmp_path / "vocab.txt")
        vocab.save(path)
        loaded = FeatureVocabulary.load(path)
        assert loaded.key_to_index == vocab.key_to_index
        assert loaded.intercept_index == 3

    def test_newline_in_feature_key_round_trips(self, tmp_path):
        keys = [feature_key("a\nb", ""), feature_key("c", "back\\slash")]
        vocab = FeatureVocabulary(keys)
        path = str(tmp_path / "v.txt")
        vocab.save(path)
        loaded = FeatureVocabulary.load(path)
        assert loaded.index_to_key == vocab.index_to_key

    def test_selected_features_filter(self):
        recs = [make_training_example(1.0, {("a", ""): 1.0, ("b", ""): 1.0})]
        vocab = FeatureVocabulary.from_records(
            recs, add_intercept=False, selected_keys={feature_key("a", "")}
        )
        assert len(vocab) == 1


class TestIngest:
    def test_dedup_by_sum_and_intercept(self):
        rec = make_training_example(1.0, {("a", ""): 1.0})
        rec["features"].append({"name": "a", "term": "", "value": 2.5})
        vocab = FeatureVocabulary([feature_key("a", "")], add_intercept=True)
        cols = training_examples_to_arrays([rec], vocab)
        assert cols["features"][0, vocab.get("a")] == 3.5  # summed
        assert cols["features"][0, vocab.intercept_index] == 1.0

    def test_null_label_scoring_vs_training(self):
        # nullable-label Avro (the realistic scoring input): scoring opts
        # in via allow_null_labels and gets 0.0; training fails loudly
        rec = make_training_example(0.0, {("a", ""): 1.0})
        rec["label"] = None
        vocab = FeatureVocabulary([feature_key("a", "")])
        cols = training_examples_to_arrays(
            [rec], vocab, allow_null_labels=True
        )
        assert cols["labels"][0] == 0.0
        with pytest.raises(ValueError, match="null/missing label"):
            training_examples_to_arrays([rec], vocab)

        from photon_ml_tpu.io.ingest import game_data_from_avro

        data, _, _ = game_data_from_avro(
            [rec], {"global": vocab}, [], allow_null_labels=True
        )
        assert np.asarray(data.labels)[0] == 0.0
        with pytest.raises(ValueError, match="null/missing label"):
            game_data_from_avro([rec], {"global": vocab}, [])

    def test_unknown_features_skipped(self):
        rec = make_training_example(1.0, {("known", ""): 1.0, ("junk", ""): 9.0})
        vocab = FeatureVocabulary([feature_key("known", "")])
        cols = training_examples_to_arrays([rec], vocab)
        assert cols["features"].shape == (1, 1)
        assert cols["features"][0, 0] == 1.0

    def test_batch_from_avro_trains(self, tmp_path, rng):
        # end-to-end: synthesize avro -> ingest -> train -> sane AUC
        n, d = 300, 6
        x = rng.normal(size=(n, d))
        w = rng.normal(size=d)
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-x @ w))).astype(float)
        recs = [
            make_training_example(
                y[i], {(f"f{j}", ""): x[i, j] for j in range(d)}
            )
            for i in range(n)
        ]
        path = str(tmp_path / "train.avro")
        write_avro_file(path, TRAINING_EXAMPLE_SCHEMA, recs)
        _, loaded = read_avro_file(path)
        vocab = FeatureVocabulary.from_records(loaded, add_intercept=False)
        batch = labeled_batch_from_avro(loaded, vocab, dtype=jnp.float64)

        from photon_ml_tpu.models import GLMTrainingConfig, train_glm
        from photon_ml_tpu.ops import RegularizationContext
        from photon_ml_tpu.ops.metrics import area_under_roc_curve

        (tm,) = train_glm(
            batch,
            GLMTrainingConfig(
                regularization=RegularizationContext("L2"), reg_weights=(0.1,)
            ),
        )
        auc = float(
            area_under_roc_curve(
                batch.labels,
                tm.model.compute_margin(batch.features),
                batch.weights,
            )
        )
        assert auc > 0.8


class TestModelPersistence:
    def test_glm_round_trip(self, tmp_path, rng):
        vocab = FeatureVocabulary(
            [feature_key(f"f{i}", "t") for i in range(5)], add_intercept=True
        )
        means = rng.normal(size=6)
        means[2] = 0.0  # sparsified away but must round-trip as 0
        variances = rng.uniform(0.5, 2.0, size=6)
        coef = Coefficients.of(means, variances)
        path = str(tmp_path / "model.avro")
        save_glm_model(
            path, coef, vocab, TaskType.LOGISTIC_REGRESSION, model_id="m0"
        )
        loaded, task = load_glm_model(path, vocab)
        assert task == TaskType.LOGISTIC_REGRESSION
        np.testing.assert_allclose(np.asarray(loaded.means), means, atol=1e-15)
        np.testing.assert_allclose(
            np.asarray(loaded.variances)[means != 0.0],
            variances[means != 0.0],
            atol=1e-15,
        )

    def test_empty_means_with_variances(self, tmp_path, rng):
        # by-name schema reference (variances: "NameTermValueAvro") must
        # resolve even when the declaring means array is empty
        vocab = FeatureVocabulary([feature_key("f", "")])
        coef = Coefficients.of(np.zeros(1), np.ones(1))
        path = str(tmp_path / "zero.avro")
        save_glm_model(path, coef, vocab, TaskType.LINEAR_REGRESSION)
        loaded, task = load_glm_model(path, vocab)
        assert task == TaskType.LINEAR_REGRESSION
        np.testing.assert_allclose(np.asarray(loaded.variances), [1.0])

    def test_game_layout_round_trip(self, tmp_path, rng):
        g_vocab = FeatureVocabulary([feature_key("g0", ""), feature_key("g1", "")])
        u_vocab = FeatureVocabulary([feature_key("u0", ""), feature_key("u1", "")])
        w_fixed = rng.normal(size=2)
        table = rng.normal(size=(3, 2))
        entity_vocab = {"alice": 0, "bob": 1, "carol": 2}
        root = str(tmp_path / "game")
        save_game_model(
            root,
            params={"global": w_fixed, "per-user": table},
            shards={"global": "shardG", "per-user": "shardU"},
            vocabs={"global": g_vocab, "per-user": u_vocab},
            entity_vocabs={"per-user": entity_vocab},
            random_effects={"global": None, "per-user": "userId"},
            task=TaskType.LOGISTIC_REGRESSION,
        )
        assert os.path.isdir(os.path.join(root, "fixed-effect", "global"))
        assert os.path.isdir(os.path.join(root, "random-effect", "per-user"))
        params, shards, res, evocabs = load_game_model(
            root,
            vocabs={"global": g_vocab, "per-user": u_vocab},
            entity_vocabs={"per-user": entity_vocab},
        )
        np.testing.assert_allclose(params["global"], w_fixed, atol=1e-15)
        np.testing.assert_allclose(params["per-user"], table, atol=1e-15)
        assert shards == {"global": "shardG", "per-user": "shardU"}
        assert res == {"global": None, "per-user": "userId"}
        assert evocabs == {"per-user": entity_vocab}

        # Without a caller-supplied entity vocab the row<->entity mapping is
        # returned (it must never be lost) and indexing the table
        # through it recovers the same per-entity coefficients.
        params2, _, _, evocabs2 = load_game_model(
            root, vocabs={"global": g_vocab, "per-user": u_vocab}
        )
        ev2 = evocabs2["per-user"]
        assert set(ev2) == {str(k) for k in entity_vocab}
        for raw, row in entity_vocab.items():
            np.testing.assert_allclose(
                params2["per-user"][ev2[str(raw)]], table[row], atol=1e-15
            )


class TestMatrixFactorizationIO:
    def test_round_trip_with_vocabs(self, tmp_path, rng):
        import jax.numpy as jnp

        from photon_ml_tpu.game.factored import MatrixFactorizationModel
        from photon_ml_tpu.io.models import load_mf_model, save_mf_model

        r, c, k = 6, 4, 3
        model = MatrixFactorizationModel(
            jnp.asarray(rng.normal(size=(r, k))),
            jnp.asarray(rng.normal(size=(c, k))),
        )
        rv = {f"member{i}": i for i in range(r)}
        cv = {f"item{i}": i for i in range(c)}
        root = str(tmp_path / "mf")
        save_mf_model(root, model, "memberId", "itemId", rv, cv)
        loaded, rv2, cv2 = load_mf_model(
            root, "memberId", "itemId", rv, cv
        )
        np.testing.assert_allclose(
            np.asarray(loaded.row_factors),
            np.asarray(model.row_factors),
            atol=1e-12,
        )
        np.testing.assert_allclose(
            np.asarray(loaded.col_factors),
            np.asarray(model.col_factors),
            atol=1e-12,
        )
        # scores survive the round trip, including missing-id zeros
        rows = np.asarray([0, 2, -1], np.int32)
        cols = np.asarray([1, -1, 3], np.int32)
        np.testing.assert_allclose(
            np.asarray(loaded.score(rows, cols)),
            np.asarray(model.score(rows, cols)),
            atol=1e-12,
        )

    def test_round_trip_without_vocabs(self, tmp_path, rng):
        import jax.numpy as jnp

        from photon_ml_tpu.game.factored import MatrixFactorizationModel
        from photon_ml_tpu.io.models import load_mf_model, save_mf_model

        model = MatrixFactorizationModel(
            jnp.asarray(rng.normal(size=(3, 2))),
            jnp.asarray(rng.normal(size=(5, 2))),
        )
        root = str(tmp_path / "mf2")
        save_mf_model(root, model, "rowId", "colId")
        loaded, _, _ = load_mf_model(root, "rowId", "colId")
        np.testing.assert_allclose(
            np.asarray(loaded.row_factors),
            np.asarray(model.row_factors),
            atol=1e-12,
        )

    def test_same_effect_types_rejected(self, tmp_path, rng):
        import jax.numpy as jnp

        from photon_ml_tpu.game.factored import MatrixFactorizationModel
        from photon_ml_tpu.io.models import save_mf_model

        model = MatrixFactorizationModel(
            jnp.ones((2, 2)), jnp.ones((2, 2))
        )
        with pytest.raises(ValueError, match="must differ"):
            save_mf_model(str(tmp_path / "x"), model, "id", "id")
