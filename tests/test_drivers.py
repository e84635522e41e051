"""End-to-end driver integration tests — the analog of the reference's
``DriverIntegTest.scala:47-670`` and ``DriverGameIntegTest.scala:343-400``:
synthesize Avro fixtures, run the real drivers (ingest -> train -> save ->
load -> score -> metric), and assert on stages, outputs, and quality. No
hand assembly of the pipeline."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.cli.score import run_scoring
from photon_ml_tpu.cli.stages import DriverStage
from photon_ml_tpu.cli.train import run_glm_training
from photon_ml_tpu.cli.game_train import run_game_training
from photon_ml_tpu.io.avro import read_avro_file, write_avro_file
from photon_ml_tpu.io.schemas import TRAINING_EXAMPLE_SCHEMA


def _sigmoid(z):
    return 1 / (1 + np.exp(-z))


def make_glm_records(rng, n, d, w_true, noise=0.0):
    x = rng.normal(size=(n, d))
    y = (rng.uniform(size=n) < _sigmoid(x @ w_true + noise)).astype(float)
    records = []
    for i in range(n):
        records.append(
            {
                "uid": f"row{i}",
                "label": float(y[i]),
                "features": [
                    {"name": f"f{j}", "term": "", "value": float(x[i, j])}
                    for j in range(d)
                ],
                "metadataMap": None,
                "weight": None,
                "offset": None,
            }
        )
    return records


def make_game_records(rng, n_users, rows_per_user, d_g, d_u, truth=None):
    """Mixed-effects fixture: global features gf*, per-user features uf*,
    userId in metadataMap (the Yahoo-music-style shape of
    ``DriverGameIntegTest``). Pass ``truth=(w_g, w_u)`` to draw additional
    data from the SAME model (e.g. a validation split)."""
    if truth is None:
        w_g = rng.normal(size=d_g)
        w_u = rng.normal(size=(n_users, d_u)) * 2.0
    else:
        w_g, w_u = truth
    records = []
    i = 0
    for u in range(n_users):
        for _ in range(rows_per_user):
            xg = rng.normal(size=d_g)
            xu = rng.normal(size=d_u)
            margin = xg @ w_g + xu @ w_u[u]
            y = float(rng.uniform() < _sigmoid(margin))
            feats = [
                {"name": f"gf{j}", "term": "", "value": float(xg[j])}
                for j in range(d_g)
            ] + [
                {"name": f"uf{j}", "term": "", "value": float(xu[j])}
                for j in range(d_u)
            ]
            records.append(
                {
                    "uid": f"row{i}",
                    "label": y,
                    "features": feats,
                    "metadataMap": {"userId": f"user{u}"},
                    "weight": None,
                    "offset": None,
                }
            )
            i += 1
    return records, (w_g, w_u)


def write_records(path, records):
    write_avro_file(path, TRAINING_EXAMPLE_SCHEMA, records)
    return path


def write_feature_file(path, names):
    from photon_ml_tpu.io.vocab import FeatureVocabulary, feature_key

    FeatureVocabulary(
        [feature_key(n, "") for n in names], add_intercept=True
    ).save(path)
    return path


@pytest.fixture
def glm_fixture(rng, tmp_path):
    w_true = rng.normal(size=6) * 1.5
    train = write_records(
        str(tmp_path / "train.avro"), make_glm_records(rng, 600, 6, w_true)
    )
    valid = write_records(
        str(tmp_path / "valid.avro"), make_glm_records(rng, 300, 6, w_true)
    )
    return train, valid, tmp_path


class TestGLMDriver:
    def test_full_pipeline_with_validation(self, rng, glm_fixture):
        train, valid, tmp = glm_fixture
        run = run_glm_training(
            {
                "train_input": [train],
                "validate_input": [valid],
                "output_dir": str(tmp / "out"),
                "task": "LOGISTIC_REGRESSION",
                "optimizer": "TRON",
                "reg_type": "L2",
                "reg_weights": [10.0, 1.0],
                "max_iters": 50,
                "tolerance": 1e-9,
            }
        )
        assert run.stages == [
            DriverStage.INIT,
            DriverStage.PREPROCESSED,
            DriverStage.TRAINED,
            DriverStage.VALIDATED,
        ]
        assert run.num_training_rows == 600
        assert run.num_features == 7  # 6 + intercept
        assert len(run.models) == 2
        assert run.best is not None
        auc = run.validation_metrics[run.best_index][
            "AREA_UNDER_RECEIVER_OPERATOR_CHARACTERISTICS"
        ]
        assert auc > 0.85
        out = tmp / "out"
        assert (out / "best-model.avro").exists()
        assert (out / "feature-index.txt").exists()
        assert (out / "feature-summary.tsv").exists()
        assert (out / "validation-metrics.json").exists()
        assert (out / "log-message.txt").exists()
        txts = [f for f in os.listdir(out / "models") if f.endswith(".txt")]
        assert len(txts) == 2  # model text per lambda

    def test_output_dir_guard(self, rng, glm_fixture):
        train, _, tmp = glm_fixture
        cfg = {
            "train_input": [train],
            "output_dir": str(tmp / "out2"),
            "reg_weights": [1.0],
            "max_iters": 5,
        }
        run_glm_training(cfg)
        with pytest.raises(FileExistsError):
            run_glm_training(cfg)
        run_glm_training({**cfg, "overwrite": True})  # explicit overwrite ok

    def test_constraints_respected(self, rng, glm_fixture):
        train, _, tmp = glm_fixture
        constraints = [
            {"name": "f0", "term": "", "lowerBound": -0.1, "upperBound": 0.1},
            {"name": "*", "term": "*", "lowerBound": -5, "upperBound": 5},
        ]
        cpath = tmp / "constraints.json"
        cpath.write_text(json.dumps(constraints))
        run = run_glm_training(
            {
                "train_input": [train],
                "output_dir": str(tmp / "outc"),
                "optimizer": "LBFGS",
                "reg_type": "NONE",
                "reg_weights": [0.0],
                "constraint_file": str(cpath),
                "max_iters": 60,
            }
        )
        w = np.asarray(run.models[0].model.coefficients.means)
        f0 = run.vocab.get("f0", "")
        assert -0.1 - 1e-9 <= w[f0] <= 0.1 + 1e-9
        assert np.all(w >= -5 - 1e-9) and np.all(w <= 5 + 1e-9)

    def test_glm_scoring_round_trip(self, rng, glm_fixture):
        train, valid, tmp = glm_fixture
        run_glm_training(
            {
                "train_input": [train],
                "validate_input": [valid],
                "output_dir": str(tmp / "outm"),
                "optimizer": "TRON",
                "reg_weights": [1.0],
                "max_iters": 50,
                "tolerance": 1e-9,
            }
        )
        srun = run_scoring(
            {
                "input": [valid],
                "model_dir": str(tmp / "outm"),
                "output_dir": str(tmp / "scores"),
                "model_kind": "glm",
                "evaluate": True,
            }
        )
        assert srun.scores.shape == (300,)
        auc = srun.metrics["AREA_UNDER_RECEIVER_OPERATOR_CHARACTERISTICS"]
        assert auc > 0.85
        _, recs = read_avro_file(srun.output_path)
        assert len(recs) == 300
        assert recs[0]["uid"].startswith("row")
        assert np.isfinite(recs[0]["predictionScore"])

    def test_sparse_driver_matches_dense(self, rng, glm_fixture):
        train, valid, tmp = glm_fixture
        common = {
            "train_input": [train],
            "validate_input": [valid],
            "optimizer": "TRON",
            "reg_weights": [1.0],
            "max_iters": 60,
            "tolerance": 1e-10,
        }
        dense = run_glm_training(
            {**common, "output_dir": str(tmp / "outd")}
        )
        sparse = run_glm_training(
            {**common, "output_dir": str(tmp / "outs"), "sparse": True}
        )
        np.testing.assert_allclose(
            np.asarray(sparse.models[0].model.coefficients.means),
            np.asarray(dense.models[0].model.coefficients.means),
            atol=1e-8,
        )


@pytest.fixture
def game_fixture(rng, tmp_path):
    trecords, truth = make_game_records(
        rng, n_users=12, rows_per_user=25, d_g=4, d_u=2
    )
    vrecords, _ = make_game_records(
        rng, n_users=12, rows_per_user=10, d_g=4, d_u=2, truth=truth
    )
    train = write_records(str(tmp_path / "gtrain.avro"), trecords)
    valid = write_records(str(tmp_path / "gvalid.avro"), vrecords)
    gshard = write_feature_file(
        str(tmp_path / "global.features"), [f"gf{j}" for j in range(4)]
    )
    ushard = write_feature_file(
        str(tmp_path / "user.features"), [f"uf{j}" for j in range(2)]
    )
    return train, valid, gshard, ushard, tmp_path


def game_params(train, valid, gshard, ushard, out, **over):
    base = {
        "train_input": [train],
        "validate_input": [valid] if valid else [],
        "output_dir": out,
        "task": "LOGISTIC_REGRESSION",
        "num_iterations": 2,
        "updating_sequence": ["global", "per-user"],
        "feature_shards": {"gshard": gshard, "ushard": ushard},
        "coordinates": {
            "global": {
                "shard": "gshard",
                "optimizer": "TRON",
                "reg_weights": [0.1],
                "max_iters": 20,
                "tolerance": 1e-8,
            },
            "per-user": {
                "shard": "ushard",
                "random_effect": "userId",
                "optimizer": "TRON",
                "reg_weights": [1.0],
                "max_iters": 20,
                "tolerance": 1e-8,
                "num_buckets": 2,
            },
        },
    }
    base.update(over)
    return base


class TestGameDriver:
    def test_fixed_plus_random(self, rng, game_fixture):
        train, valid, gs, us, tmp = game_fixture
        run = run_game_training(
            game_params(train, valid, gs, us, str(tmp / "gout"))
        )
        assert len(run.sweep) == 1
        hist = run.sweep[0]["history"]
        objs = [h.objective for h in hist]
        assert all(b <= a + 1e-6 for a, b in zip(objs, objs[1:]))
        # per-coordinate validation metric logged on every update
        assert all(h.validation_metric is not None for h in hist)
        assert run.sweep[0]["validation_metric"] > 0.80
        best_dir = run.output_dirs[0]
        assert os.path.isdir(os.path.join(best_dir, "fixed-effect", "global"))
        assert os.path.isdir(
            os.path.join(best_dir, "random-effect", "per-user")
        )

    def test_fixed_only(self, rng, game_fixture):
        train, valid, gs, us, tmp = game_fixture
        params = game_params(train, valid, gs, us, str(tmp / "gout2"))
        params["updating_sequence"] = ["global"]
        params["coordinates"] = {
            "global": params["coordinates"]["global"]
        }
        run = run_game_training(params)
        assert set(run.sweep[0]["model"].params) == {"global"}

    def test_random_only(self, rng, game_fixture):
        train, valid, gs, us, tmp = game_fixture
        params = game_params(train, valid, gs, us, str(tmp / "gout3"))
        params["updating_sequence"] = ["per-user"]
        params["coordinates"] = {
            "per-user": params["coordinates"]["per-user"]
        }
        run = run_game_training(params)
        model = run.sweep[0]["model"]
        assert model.params["per-user"].shape == (12, 3)  # 2 + intercept

    def test_grid_sweep_selects_best(self, rng, game_fixture):
        train, valid, gs, us, tmp = game_fixture
        params = game_params(
            train, valid, gs, us, str(tmp / "gout4"),
            model_output_mode="ALL",
        )
        params["coordinates"]["per-user"]["reg_weights"] = [1000.0, 1.0]
        run = run_game_training(params)
        assert len(run.sweep) == 2
        combos = [s["combo"]["per-user"] for s in run.sweep]
        assert combos == [1000.0, 1.0]
        # the sane reg weight must win on validation
        assert run.sweep[run.best_index]["combo"]["per-user"] == 1.0
        assert len(run.output_dirs) == 2  # ALL mode writes every combo

        # scoring an ALL-mode output dir must resolve a real model (not
        # silently score zeros) whether pointed at the root or a sub-model
        for model_dir, out in [
            (str(tmp / "gout4"), str(tmp / "gs4a")),
            (run.output_dirs[1], str(tmp / "gs4b")),
        ]:
            srun = run_scoring(
                {
                    "input": [valid],
                    "model_dir": model_dir,
                    "output_dir": out,
                    "model_kind": "game",
                }
            )
            assert np.abs(srun.scores).max() > 0.0

    def test_grid_sweep_vmapped_no_validation(self, rng, game_fixture):
        """Without validation/warm-start/checkpointing the driver trains
        the whole reg-weight grid as ONE vmapped sweep (SURVEY §2.5.6);
        every entry must equal its sequential single-combo run."""
        train, valid, gs, us, tmp = game_fixture
        params = game_params(
            train, None, gs, us, str(tmp / "goutv"),
            model_output_mode="ALL",
        )
        params["coordinates"]["per-user"]["reg_weights"] = [100.0, 1.0]
        run = run_game_training(params)
        assert len(run.sweep) == 2
        for i, lam in enumerate([100.0, 1.0]):
            p2 = game_params(train, None, gs, us, str(tmp / f"gouts{i}"))
            p2["coordinates"]["per-user"]["reg_weights"] = [lam]
            r2 = run_game_training(p2)
            for k in r2.sweep[0]["model"].params:
                np.testing.assert_allclose(
                    np.asarray(run.sweep[i]["model"].params[k]),
                    np.asarray(r2.sweep[0]["model"].params[k]),
                    atol=1e-8,
                    err_msg=f"combo {lam} coord {k}",
                )

    def test_game_scoring_round_trip(self, rng, game_fixture):
        train, valid, gs, us, tmp = game_fixture
        run = run_game_training(
            game_params(train, valid, gs, us, str(tmp / "gout5"))
        )
        srun = run_scoring(
            {
                "input": [valid],
                "model_dir": str(tmp / "gout5"),
                "output_dir": str(tmp / "gscores"),
                "model_kind": "game",
                "evaluate": True,
            }
        )
        auc = srun.metrics["AREA_UNDER_RECEIVER_OPERATOR_CHARACTERISTICS"]
        # scoring the model the driver saved must reproduce the driver's
        # own final validation metric
        np.testing.assert_allclose(
            auc, run.sweep[run.best_index]["validation_metric"], atol=1e-9
        )

    def test_driver_checkpoint_resume(self, rng, game_fixture):
        train, valid, gs, us, tmp = game_fixture
        out = str(tmp / "gout7")
        base = game_params(
            train, None, gs, us, out,
            checkpoint_every=1, num_iterations=1,
        )
        run_game_training(base)
        ck_root = os.path.join(out, "checkpoints")
        assert os.path.isdir(ck_root) and os.listdir(ck_root)
        # resume in-place to 2 iterations; must match a straight 2-iter run
        resumed = run_game_training(
            {**base, "num_iterations": 2, "resume": True}
        )
        straight = run_game_training(
            game_params(
                train, None, gs, us, str(tmp / "gout7b"), num_iterations=2
            )
        )
        for name, p in straight.sweep[0]["model"].params.items():
            np.testing.assert_array_equal(
                np.asarray(resumed.sweep[0]["model"].params[name]),
                np.asarray(p),
            )

    def test_unknown_entity_scores_zero_in_scoring(self, rng, game_fixture):
        train, valid, gs, us, tmp = game_fixture
        run_game_training(
            game_params(train, None, gs, us, str(tmp / "gout6"))
        )
        # scoring data with an unseen user: random-effect contributes 0
        recs, _ = make_game_records(rng, n_users=1, rows_per_user=5, d_g=4, d_u=2)
        for r in recs:
            r["metadataMap"] = {"userId": "brand-new-user"}
        spath = write_records(str(tmp / "unseen.avro"), recs)
        srun = run_scoring(
            {
                "input": [spath],
                "model_dir": str(tmp / "gout6"),
                "output_dir": str(tmp / "gscores6"),
                "model_kind": "game",
            }
        )
        assert np.all(np.isfinite(srun.scores))


class TestUtils:
    def test_date_range_expansion(self, tmp_path):
        from photon_ml_tpu.utils.dates import DateRange, expand_date_paths

        for day in ("2024/01/30", "2024/01/31", "2024/02/01"):
            (tmp_path / day).mkdir(parents=True)
        got = expand_date_paths(
            [str(tmp_path)], DateRange.from_dates("20240131-20240202")
        )
        assert got == [
            str(tmp_path / "2024/01/31"),
            str(tmp_path / "2024/02/01"),
        ]
        with pytest.raises(FileNotFoundError):
            expand_date_paths(
                [str(tmp_path)], DateRange.from_dates("20230101-20230102")
            )

    def test_logger_writes_file(self, tmp_path):
        from photon_ml_tpu.utils.logging import PhotonLogger

        path = tmp_path / "log.txt"
        with PhotonLogger(str(path), level="INFO") as log:
            log.debug("hidden")
            log.info("visible")
        text = path.read_text()
        assert "visible" in text and "hidden" not in text


def make_sparse_user_records(rng, n_users, rows_per_user, d_g, d_u, truth=None):
    """Per-entity-sparse fixture: each user's rows touch only ITS OWN pair
    of user features (uf{2u}, uf{2u+1}) out of a d_u-wide space — the
    regime INDEX_MAP projection compacts losslessly."""
    if truth is None:
        w_g = rng.normal(size=d_g)
        w_u = rng.normal(size=(n_users, d_u)) * 2.0
    else:
        w_g, w_u = truth
    records = []
    i = 0
    for u in range(n_users):
        j0, j1 = (2 * u) % d_u, (2 * u + 1) % d_u
        for _ in range(rows_per_user):
            xg = rng.normal(size=d_g)
            x0, x1 = rng.normal(), rng.normal()
            margin = xg @ w_g + x0 * w_u[u, j0] + x1 * w_u[u, j1]
            y = float(rng.uniform() < _sigmoid(margin))
            feats = [
                {"name": f"gf{j}", "term": "", "value": float(xg[j])}
                for j in range(d_g)
            ] + [
                {"name": f"uf{j0}", "term": "", "value": float(x0)},
                {"name": f"uf{j1}", "term": "", "value": float(x1)},
            ]
            records.append(
                {
                    "uid": f"row{i}",
                    "label": y,
                    "features": feats,
                    "metadataMap": {"userId": f"user{u}"},
                    "weight": None,
                    "offset": None,
                }
            )
            i += 1
    return records, (w_g, w_u)


class TestProjectedGameDriver:
    D_U = 10

    @pytest.fixture
    def sparse_game_fixture(self, rng, tmp_path):
        trecords, truth = make_sparse_user_records(
            rng, n_users=10, rows_per_user=30, d_g=3, d_u=self.D_U
        )
        vrecords, _ = make_sparse_user_records(
            rng, n_users=10, rows_per_user=10, d_g=3, d_u=self.D_U,
            truth=truth,
        )
        train = write_records(str(tmp_path / "ptrain.avro"), trecords)
        valid = write_records(str(tmp_path / "pvalid.avro"), vrecords)
        gshard = write_feature_file(
            str(tmp_path / "pg.features"), [f"gf{j}" for j in range(3)]
        )
        ushard = write_feature_file(
            str(tmp_path / "pu.features"),
            [f"uf{j}" for j in range(self.D_U)],
        )
        return train, valid, gshard, ushard, tmp_path

    def _params(self, fixture, out, projector=None, **over):
        train, valid, gs, us, tmp = fixture
        p = game_params(train, valid, gs, us, out, **over)
        if projector is not None:
            p["coordinates"]["per-user"]["projector"] = projector
        return p

    def test_index_map_reproduces_unprojected(self, sparse_game_fixture):
        tmp = sparse_game_fixture[4]
        plain = run_game_training(
            self._params(sparse_game_fixture, str(tmp / "plain"))
        )
        proj = run_game_training(
            self._params(
                sparse_game_fixture, str(tmp / "proj"),
                projector="INDEX_MAP",
            )
        )
        # per-entity-sparse + L2: unused columns solve to exactly 0, so the
        # compacted solve reproduces the full-space solution
        np.testing.assert_allclose(
            np.asarray(proj.sweep[0]["model"].params["per-user"]),
            np.asarray(plain.sweep[0]["model"].params["per-user"]),
            atol=1e-5,
        )
        np.testing.assert_allclose(
            proj.sweep[0]["validation_metric"],
            plain.sweep[0]["validation_metric"],
            atol=1e-6,
        )

    def test_random_projector_trains_saves_loads_scores(
        self, sparse_game_fixture
    ):
        train, valid, gs, us, tmp = sparse_game_fixture
        out = str(tmp / "rand")
        run = run_game_training(
            self._params(
                sparse_game_fixture, out, projector="RANDOM=4"
            )
        )
        # the in-memory + on-disk model is in ORIGINAL feature space
        table = np.asarray(run.sweep[0]["model"].params["per-user"])
        assert table.shape == (10, self.D_U + 1)  # + intercept
        srun = run_scoring(
            {
                "input": [valid],
                "model_dir": out,
                "output_dir": str(tmp / "rand-scores"),
                "model_kind": "game",
                "evaluate": True,
            }
        )
        auc = srun.metrics["AREA_UNDER_RECEIVER_OPERATOR_CHARACTERISTICS"]
        # scoring the saved model reproduces the driver's own validation
        np.testing.assert_allclose(
            auc, run.sweep[run.best_index]["validation_metric"], atol=1e-9
        )
        assert auc > 0.6

    def test_identity_projector_matches_no_projector(
        self, sparse_game_fixture
    ):
        tmp = sparse_game_fixture[4]
        plain = run_game_training(
            self._params(sparse_game_fixture, str(tmp / "id-plain"))
        )
        ident = run_game_training(
            self._params(
                sparse_game_fixture, str(tmp / "id-proj"),
                projector="IDENTITY",
            )
        )
        np.testing.assert_array_equal(
            np.asarray(ident.sweep[0]["model"].params["per-user"]),
            np.asarray(plain.sweep[0]["model"].params["per-user"]),
        )

    def test_unknown_projector_rejected(self, sparse_game_fixture):
        tmp = sparse_game_fixture[4]
        with pytest.raises(ValueError, match="unknown projector"):
            run_game_training(
                self._params(
                    sparse_game_fixture, str(tmp / "bad"),
                    projector="HASHING",
                )
            )


class TestFactoredGameDriver:
    def test_factored_trains_saves_loads_scores(self, rng, game_fixture):
        train, valid, gs, us, tmp = game_fixture
        out = str(tmp / "fact")
        params = game_params(train, valid, gs, us, out)
        params["coordinates"]["per-user"]["latent_dim"] = 2
        params["coordinates"]["per-user"]["num_inner_iterations"] = 2
        params["coordinates"]["per-user"]["latent_reg_weight"] = 0.1
        run = run_game_training(params)
        model = run.sweep[0]["model"]
        fp = model.params["per-user"]
        assert hasattr(fp, "gamma") and hasattr(fp, "projection")
        assert np.asarray(fp.gamma).shape == (12, 2)
        assert np.asarray(fp.projection).shape == (3, 2)  # 2 + intercept
        # training objective decreased and validation ran per update
        hist = run.sweep[0]["history"]
        objs = [h.objective for h in hist]
        assert all(b <= a + 1e-6 for a, b in zip(objs, objs[1:]))
        # on-disk: latent wire format under factored-random-effect/
        best = run.output_dirs[0]
        fdir = os.path.join(best, "factored-random-effect", "per-user")
        assert os.path.exists(os.path.join(fdir, "latent-factors.avro"))
        assert os.path.exists(os.path.join(fdir, "projection.avro"))

        srun = run_scoring(
            {
                "input": [valid],
                "model_dir": out,
                "output_dir": str(tmp / "fact-scores"),
                "model_kind": "game",
                "evaluate": True,
            }
        )
        auc = srun.metrics["AREA_UNDER_RECEIVER_OPERATOR_CHARACTERISTICS"]
        # scoring the saved latent tables reproduces the driver's own
        # final validation metric exactly
        np.testing.assert_allclose(
            auc, run.sweep[run.best_index]["validation_metric"], atol=1e-9
        )

    def test_factored_with_projector_rejected(self, rng, game_fixture):
        train, valid, gs, us, tmp = game_fixture
        params = game_params(train, valid, gs, us, str(tmp / "factbad"))
        params["coordinates"]["per-user"]["latent_dim"] = 2
        params["coordinates"]["per-user"]["projector"] = "INDEX_MAP"
        with pytest.raises(ValueError, match="mutually exclusive"):
            run_game_training(params)

    def test_latent_optimizer_is_the_projection_solves_own(
            self, rng, game_fixture):
        """"re-config;latent-config;mf-config": NEWTON on the lanes, the
        latent matrix by TRON under its own budget; the record says what
        that solve did; an optimizer the projection solve does not
        implement, or the key without latent_dim, is refused."""
        train, valid, gs, us, tmp = game_fixture
        params = game_params(train, valid, gs, us, str(tmp / "factopt"))
        params["coordinates"]["per-user"].update(
            optimizer="NEWTON", max_iters=2, tolerance=0.0, latent_dim=2,
            latent_optimizer="TRON", latent_max_iters=3,
            latent_tolerance=0.0, latent_reg_weight=1.0,
        )
        run = run_game_training(params)
        solves = [
            it["projection"] for h in run.sweep[0]["history"]
            if h.coordinate == "per-user" for it in h.inner_iterations
        ]
        assert solves and all(s["iterations"] == 3 for s in solves)
        assert all(s["cg_iterations"] > 0 for s in solves)

        params["output_dir"] = str(tmp / "factopt-bad")
        params["coordinates"]["per-user"]["latent_optimizer"] = "NEWTON"
        with pytest.raises(ValueError, match="projection solve implements"):
            run_game_training(params)
        del params["coordinates"]["per-user"]["latent_dim"]
        with pytest.raises(ValueError, match="latent_optimizer"):
            run_game_training(params)

    def test_factored_latent_round_trip_io(self, rng, tmp_path):
        """save -> load preserves gamma and projection exactly (through
        the raw-entity-id and feature-key mappings)."""
        import jax.numpy as jnp

        from photon_ml_tpu.game.factored import FactoredParams
        from photon_ml_tpu.io.models import (
            load_game_model,
            save_game_model,
        )
        from photon_ml_tpu.io.vocab import FeatureVocabulary, feature_key

        e, d, k = 5, 4, 2
        gamma = rng.normal(size=(e, k))
        projection = rng.normal(size=(d, k))
        vocab = FeatureVocabulary(
            [feature_key(f"f{j}", "t") for j in range(d)]
        )
        evocab = {f"user{i}": i for i in range(e)}
        root = str(tmp_path / "fmodel")
        save_game_model(
            root,
            params={
                "fact": FactoredParams(
                    gamma=jnp.asarray(gamma),
                    projection=jnp.asarray(projection),
                )
            },
            shards={"fact": "ushard"},
            vocabs={"fact": vocab},
            entity_vocabs={"fact": evocab},
            random_effects={"fact": "userId"},
        )
        params, shards, res, evs = load_game_model(
            root, {"fact": vocab}, {"fact": evocab}
        )
        np.testing.assert_allclose(
            np.asarray(params["fact"].gamma), gamma, atol=1e-12
        )
        np.testing.assert_allclose(
            np.asarray(params["fact"].projection), projection, atol=1e-12
        )
        assert shards["fact"] == "ushard"
        assert res["fact"] == "userId"


class TestWarmStartAndCollapse:
    def test_glm_warm_start_converges_immediately(self, rng, glm_fixture):
        train, valid, tmp = glm_fixture
        common = {
            "train_input": [train],
            "optimizer": "LBFGS",
            "reg_weights": [1.0],
            "max_iters": 200,
            "tolerance": 1e-12,
        }
        first = run_glm_training(
            {**common, "output_dir": str(tmp / "ws1"), "model_output_mode": "ALL"}
        )
        # models/ holds the single trained model; warm-start from it
        mdir = os.path.join(str(tmp / "ws1"), "models")
        model_file = [f for f in os.listdir(mdir) if f.endswith(".avro")][0]
        second = run_glm_training(
            {
                **common,
                "output_dir": str(tmp / "ws2"),
                "initial_model_dir": os.path.join(mdir, model_file),
            }
        )
        # warm start at the optimum: convergence within a couple iterations
        assert int(second.models[0].result.iterations) <= 3
        np.testing.assert_allclose(
            np.asarray(second.models[0].model.coefficients.means),
            np.asarray(first.models[0].model.coefficients.means),
            atol=1e-4,
        )

    def test_game_warm_start_starts_near_optimum(self, rng, game_fixture):
        train, valid, gs, us, tmp = game_fixture
        first = run_game_training(
            game_params(train, None, gs, us, str(tmp / "gws1"),
                        model_output_mode="ALL", num_iterations=3)
        )
        warm = run_game_training(
            game_params(
                train, None, gs, us, str(tmp / "gws2"),
                num_iterations=1,
                initial_model_dir=first.output_dirs[0],
            )
        )
        # the warm run's FIRST objective must already be at (or below)
        # the cold run's final objective
        cold_final = first.sweep[0]["history"][-1].objective
        warm_first = warm.sweep[0]["history"][0].objective
        assert warm_first <= cold_final + 1e-4

    def test_collapse_game_model_sums_coefficients(self, rng):
        from photon_ml_tpu.io.models import collapse_game_model

        params = {
            "a": np.asarray([[1.0, 2.0], [3.0, 4.0]]),  # RE table
            "b": np.asarray([[10.0, 20.0], [30.0, 40.0]]),
            "f1": np.asarray([1.0, 1.0, 1.0]),
            "f2": np.asarray([2.0, 2.0, 2.0]),
        }
        shards = {"a": "us", "b": "us", "f1": "gs", "f2": "gs"}
        res = {"a": "userId", "b": "userId", "f1": None, "f2": None}
        evocabs = {
            "a": {"u0": 0, "u1": 1},
            "b": {"u1": 0, "u2": 1},  # overlapping + disjoint entities
        }
        p, s, r, ev = collapse_game_model(params, shards, res, evocabs)
        assert set(p) == {"userId-us", "fixed-effect-gs"}
        np.testing.assert_allclose(
            p["fixed-effect-gs"], [3.0, 3.0, 3.0]
        )
        merged = p["userId-us"]
        mv = ev["userId-us"]
        np.testing.assert_allclose(merged[mv["u0"]], [1.0, 2.0])
        np.testing.assert_allclose(merged[mv["u1"]], [13.0, 24.0])  # summed
        np.testing.assert_allclose(merged[mv["u2"]], [30.0, 40.0])

    def test_collapse_output_driver_flag(self, rng, game_fixture):
        train, valid, gs, us, tmp = game_fixture
        # two coordinates on the SAME shard + RE type -> one merged model
        params = game_params(train, valid, gs, us, str(tmp / "gcol"))
        params["coordinates"]["per-user-2"] = dict(
            params["coordinates"]["per-user"]
        )
        params["updating_sequence"] = ["global", "per-user", "per-user-2"]
        params["collapse_output"] = True
        run = run_game_training(params)
        best = run.output_dirs[0]
        merged = os.path.join(best, "random-effect", "userId-ushard")
        assert os.path.isdir(merged), os.listdir(
            os.path.join(best, "random-effect")
        )
        # merged model scores = sum of both coordinates' contributions
        srun = run_scoring(
            {
                "input": [valid],
                "model_dir": str(tmp / "gcol"),
                "output_dir": str(tmp / "gcol-scores"),
                "model_kind": "game",
            }
        )
        assert np.abs(srun.scores).max() > 0


class TestResponsePredictionFieldNames:
    RESPONSE_SCHEMA = {
        "name": "SimplifiedResponsePrediction",
        "namespace": "com.linkedin.lab.regression.avro",
        "type": "record",
        "fields": [
            {"name": "response", "type": "double"},
            {
                "name": "features",
                "type": {
                    "type": "array",
                    "items": {
                        "name": "RPFeature",
                        "type": "record",
                        "fields": [
                            {"name": "name", "type": "string"},
                            {"name": "term", "type": "string"},
                            {"name": "value", "type": "double"},
                        ],
                    },
                },
            },
            {"name": "weight", "type": "double", "default": 1.0},
            {"name": "offset", "type": "double", "default": 0.0},
        ],
    }

    def test_trains_from_response_prediction_records(self, rng, tmp_path):
        n, d = 300, 4
        x = rng.normal(size=(n, d))
        w = rng.normal(size=d)
        y = (rng.uniform(size=n) < _sigmoid(x @ w)).astype(float)
        recs = [
            {
                "response": float(y[i]),
                "features": [
                    {"name": f"f{j}", "term": "", "value": float(x[i, j])}
                    for j in range(d)
                ],
                "weight": 1.0,
                "offset": 0.0,
            }
            for i in range(n)
        ]
        tdir = tmp_path / "rp"
        tdir.mkdir()
        write_avro_file(
            str(tdir / "part-0.avro"), self.RESPONSE_SCHEMA, recs
        )
        run = run_glm_training(
            {
                "train_input": [str(tdir)],
                "output_dir": str(tmp_path / "rp-out"),
                "field_names": "RESPONSE_PREDICTION",
                "optimizer": "TRON",
                "reg_weights": [1.0],
                "max_iters": 50,
            }
        )
        coef = np.asarray(run.models[0].model.coefficients.means)
        assert np.all(np.isfinite(coef)) and np.abs(coef).max() > 0.1
        # sign agreement with the generating weights (strong signal)
        idx = [run.vocab.get(f"f{j}", "") for j in range(d)]
        assert np.all(np.sign(coef[idx]) == np.sign(w))

    def test_unknown_field_names_rejected(self, rng, tmp_path):
        from photon_ml_tpu.io.ingest import normalize_field_names

        with pytest.raises(ValueError, match="unknown field-name set"):
            normalize_field_names([], "ADMM_WHATEVER")


class TestValidatePerIteration:
    def test_snapshots_and_metrics_per_iteration(self, rng, glm_fixture):
        train, valid, tmp = glm_fixture
        run = run_glm_training(
            {
                "train_input": [train],
                "validate_input": [valid],
                "output_dir": str(tmp / "vpi"),
                "optimizer": "LBFGS",
                "reg_weights": [1.0],
                "max_iters": 30,
                "validate_per_iteration": True,
            }
        )
        hist = run.models[0].result.w_history
        iters = int(run.models[0].result.iterations)
        assert hist is not None and hist.shape[0] == 31
        # final snapshot equals the returned model coefficients (both are
        # de-normalized raw-space)
        np.testing.assert_allclose(
            np.asarray(hist[iters]),
            np.asarray(run.models[0].model.coefficients.means),
            atol=1e-12,
        )
        path = os.path.join(str(tmp / "vpi"), "per-iteration-metrics.json")
        assert os.path.exists(path)
        data = json.load(open(path))
        rows = data["0_lambda_1"]
        assert len(rows) == iters + 1
        aucs = [
            r["AREA_UNDER_RECEIVER_OPERATOR_CHARACTERISTICS"] for r in rows
        ]
        # AUC improves from the zero-model start to the converged model
        assert aucs[-1] > aucs[0]
        assert aucs[-1] > 0.85

    def test_requires_validation_input(self, rng, glm_fixture):
        train, _, tmp = glm_fixture
        with pytest.raises(ValueError, match="validate_per_iteration"):
            run_glm_training(
                {
                    "train_input": [train],
                    "output_dir": str(tmp / "vpi2"),
                    "validate_per_iteration": True,
                }
            )


class TestNonLogisticDrivers:
    """Driver-level e2e for the non-logistic tasks + per-example
    offsets/weights — the remaining DriverIntegTest scenario shapes."""

    def test_poisson_glm_driver_e2e(self, rng, tmp_path):
        n, d = 800, 4
        x = rng.normal(size=(n, d)) * 0.5
        w = np.asarray([0.8, -0.5, 0.3, 0.0])
        rate = np.exp(x @ w)
        y = rng.poisson(rate).astype(float)
        recs = [
            {
                "uid": f"r{i}",
                "label": float(y[i]),
                "features": [
                    {"name": f"f{j}", "term": "", "value": float(x[i, j])}
                    for j in range(d)
                ],
                "metadataMap": None,
                "weight": None,
                "offset": None,
            }
            for i in range(n)
        ]
        tdir = tmp_path / "ptrain"
        tdir.mkdir()
        write_avro_file(
            str(tdir / "p.avro"), TRAINING_EXAMPLE_SCHEMA, recs
        )
        run = run_glm_training(
            {
                "train_input": [str(tdir)],
                "validate_input": [str(tdir)],
                "output_dir": str(tmp_path / "pout"),
                "task": "POISSON_REGRESSION",
                "optimizer": "TRON",
                "reg_weights": [0.1],
                "max_iters": 60,
                "tolerance": 1e-10,
                "add_intercept": False,
            }
        )
        coef = np.asarray(run.models[0].model.coefficients.means)
        idx = [run.vocab.get(f"f{j}", "") for j in range(d)]
        # recovers the generating coefficients of the log link (sampling
        # noise at n=800 plus the L2 pull bounds the agreement)
        np.testing.assert_allclose(coef[idx], w, atol=0.25)
        assert "ROOT_MEAN_SQUARED_ERROR" in run.validation_metrics[0]

    def test_game_offsets_and_weights_flow_through(self, rng, tmp_path):
        """Per-example offsets shift margins; zero-weight rows are
        ignored by training."""
        n_users, rows, d_u = 6, 60, 2
        w_u = rng.normal(size=(n_users, d_u)) * 2.0
        records = []
        for u in range(n_users):
            for i in range(rows):
                xu = rng.normal(size=d_u)
                offset = float(rng.normal() * 0.5)
                margin = xu @ w_u[u] + offset
                y = float(rng.uniform() < _sigmoid(margin))
                # half the rows of user 0 are poisoned but weighted 0
                poisoned = u == 0 and i % 2 == 0
                records.append(
                    {
                        "uid": f"u{u}r{i}",
                        "label": (1.0 - y) if poisoned else y,
                        "features": [
                            {
                                "name": f"uf{j}",
                                "term": "",
                                "value": float(xu[j]),
                            }
                            for j in range(d_u)
                        ],
                        "metadataMap": {"userId": f"user{u}"},
                        "weight": 0.0 if poisoned else 1.0,
                        "offset": offset,
                    }
                )
        train = write_records(str(tmp_path / "gw.avro"), records)
        ushard = write_feature_file(
            str(tmp_path / "uw.features"), [f"uf{j}" for j in range(d_u)]
        )
        run = run_game_training(
            {
                "train_input": [train],
                "output_dir": str(tmp_path / "gwout"),
                "task": "LOGISTIC_REGRESSION",
                "num_iterations": 2,
                "updating_sequence": ["per-user"],
                "feature_shards": {"ushard": ushard},
                "coordinates": {
                    "per-user": {
                        "shard": "ushard",
                        "random_effect": "userId",
                        "optimizer": "TRON",
                        "reg_weights": [1.0],
                        "max_iters": 30,
                        "tolerance": 1e-9,
                        "num_buckets": 2,
                    }
                },
            }
        )
        table = np.asarray(run.sweep[0]["model"].params["per-user"])
        evocab = run.entity_vocabs["userId"]
        # every user's coefficient signs recover the truth — including
        # user 0, whose poisoned rows carried weight 0
        for u in range(n_users):
            e = evocab[f"user{u}"]
            idx = [
                run.shard_vocabs["ushard"].get(f"uf{j}", "")
                for j in range(d_u)
            ]
            agree = np.sign(table[e][idx]) == np.sign(w_u[u])
            assert agree.all(), (u, table[e][idx], w_u[u])


class TestSharedRandomEffectTypeScoring:
    def test_coordinates_sharing_re_type_score_correctly(
        self, rng, tmp_path
    ):
        """Two coordinates share randomEffectType userId with DIFFERENT
        entity sets/orders on disk: scoring must cogroup by raw id, not
        first-coordinate-wins row indexing (regression: scores were
        silently misattributed)."""
        from photon_ml_tpu.io.models import save_game_model
        from photon_ml_tpu.io.vocab import FeatureVocabulary, feature_key

        root = str(tmp_path / "model")
        vocab = FeatureVocabulary(
            [feature_key("uf0", ""), feature_key("uf1", "")]
        )
        save_game_model(
            root,
            params={
                "a": np.asarray([[1.0, 0.0], [2.0, 0.0]]),  # u0, u1
                "b": np.asarray([[30.0, 0.0], [40.0, 0.0]]),  # u1, u2
            },
            shards={"a": "us", "b": "us"},
            vocabs={"a": vocab, "b": vocab},
            entity_vocabs={
                "a": {"u0": 0, "u1": 1},
                "b": {"u1": 0, "u2": 1},
            },
            random_effects={"a": "userId", "b": "userId"},
        )
        vocab.save(os.path.join(root, "feature-index-us.txt"))

        sdir = tmp_path / "score"
        sdir.mkdir()
        recs = [
            {
                "uid": f"r{i}",
                "label": 0.0,
                "features": [
                    {"name": "uf0", "term": "", "value": 1.0}
                ],
                "metadataMap": {"userId": u},
                "weight": None,
                "offset": None,
            }
            for i, u in enumerate(["u0", "u1", "u2"])
        ]
        write_avro_file(
            str(sdir / "p.avro"), TRAINING_EXAMPLE_SCHEMA, recs
        )
        srun = run_scoring(
            {
                "input": [str(sdir)],
                "model_dir": root,
                "output_dir": str(tmp_path / "out"),
                "model_kind": "game",
            }
        )
        # u0 -> a only (1); u1 -> a + b (2 + 30); u2 -> b only (40)
        np.testing.assert_allclose(srun.scores, [1.0, 32.0, 40.0])


class TestMeshShardedDriver:
    def test_data_and_feature_mesh_match_local(self, rng, glm_fixture):
        """mesh_shape through the CLI: 'data' and 'data'+'feature' sharded
        solves reproduce the single-device solution."""
        train, valid, tmp = glm_fixture
        common = {
            "train_input": [train],
            "optimizer": "TRON",
            "reg_weights": [1.0],
            "max_iters": 60,
            "tolerance": 1e-12,
        }
        local = run_glm_training(
            {**common, "output_dir": str(tmp / "mlocal")}
        )
        data_sharded = run_glm_training(
            {
                **common,
                "output_dir": str(tmp / "mdata"),
                "mesh_shape": {"data": 4},
            }
        )
        feat_sharded = run_glm_training(
            {
                **common,
                "output_dir": str(tmp / "mfeat"),
                "mesh_shape": {"data": 2, "feature": 4},
            }
        )
        w = np.asarray(local.models[0].model.coefficients.means)
        np.testing.assert_allclose(
            np.asarray(data_sharded.models[0].model.coefficients.means),
            w,
            atol=1e-8,
        )
        np.testing.assert_allclose(
            np.asarray(feat_sharded.models[0].model.coefficients.means),
            w,
            atol=1e-8,
        )

    def test_sparse_feature_mesh_with_normalization(self, rng, glm_fixture):
        """Driver-reachable r4 composition: SPARSE ingest + ('data',
        'feature') mesh + scale normalization reproduces the local dense
        run (the huge-d Criteo-regime configuration end to end)."""
        train, valid, tmp = glm_fixture
        common = {
            "train_input": [train],
            "optimizer": "TRON",
            "reg_weights": [1.0],
            "max_iters": 60,
            "tolerance": 1e-12,
            "normalization": "SCALE_WITH_STANDARD_DEVIATION",
        }
        local = run_glm_training(
            {**common, "output_dir": str(tmp / "nlocal")}
        )
        sparse_feat = run_glm_training(
            {
                **common,
                "output_dir": str(tmp / "nsparsefeat"),
                "sparse": True,
                "mesh_shape": {"data": 2, "feature": 4},
            }
        )
        np.testing.assert_allclose(
            np.asarray(sparse_feat.models[0].model.coefficients.means),
            np.asarray(local.models[0].model.coefficients.means),
            atol=1e-8,
        )

    def test_mesh_shape_validation(self, rng, glm_fixture):
        train, _, tmp = glm_fixture
        with pytest.raises(ValueError, match="axes must be"):
            run_glm_training(
                {
                    "train_input": [train],
                    "output_dir": str(tmp / "mbad"),
                    "mesh_shape": {"entity": 2},
                }
            )


class TestMultiprocessValidation:
    """Unit drills for the multi-process GAME parameter gate — these
    never spawn processes, they exercise the validation surface."""

    def _params(self, tmp_path, **over):
        from photon_ml_tpu.cli.config import GameDriverParams, load_params

        base = game_params(
            "train", None, "gs", "us", str(tmp_path / "out"), **over
        )
        # the gate's supported surface: no validation rows, num_buckets=1
        base["validate_input"] = []
        for spec in base["coordinates"].values():
            spec["num_buckets"] = 1
        return load_params(base, GameDriverParams)

    def test_supported_surface_passes(self, tmp_path):
        from photon_ml_tpu.cli.game_train import (
            _validate_multiprocess_params,
        )

        _validate_multiprocess_params(self._params(tmp_path))

    def test_warm_start_rejected(self, tmp_path):
        """Warm start remaps RE tables by POSITION into each process's
        local entity vocabulary — coefficients would silently attach to
        the wrong entities. The gate must fail loudly."""
        from photon_ml_tpu.cli.game_train import (
            _validate_multiprocess_params,
        )

        params = self._params(
            tmp_path, initial_model_dir=str(tmp_path / "prev")
        )
        with pytest.raises(ValueError, match="initial_model_dir"):
            _validate_multiprocess_params(params)

    def test_non_string_entity_ids_rejected_at_globalization(self):
        """The entity-vocabulary globalization must refuse non-str ids
        instead of silently str()-coercing them (which would re-key the
        global vocab with different types than single-process runs)."""
        from photon_ml_tpu.cli.game_train import _ordered_entity_ids

        assert _ordered_entity_ids("userId", {"u1": 1, "u0": 0}) == [
            "u0",
            "u1",
        ]
        with pytest.raises(ValueError, match="not str"):
            _ordered_entity_ids("userId", {7: 0, "u1": 1})


class TestQualityFingerprintExport:
    """Train-time baseline fingerprints ride the standard driver outputs
    (docs/OBSERVABILITY.md "Quality & drift")."""

    def test_glm_driver_exports_fingerprint(self, rng, tmp_path):
        from photon_ml_tpu.io.ingest import make_training_example

        records = [
            make_training_example(
                float(rng.uniform() < 0.5),
                {("a", ""): float(rng.normal()),
                 ("b", ""): float(rng.normal())},
            )
            for _ in range(60)
        ]
        train = write_records(str(tmp_path / "fp.avro"), records)
        run = run_glm_training(
            {
                "train_input": [train],
                "output_dir": str(tmp_path / "fpout"),
                "task": "LOGISTIC_REGRESSION",
                "reg_weights": [1.0],
                "max_iters": 8,
            }
        )
        from photon_ml_tpu.obs.quality import BaselineFingerprint

        fp = BaselineFingerprint.load(str(tmp_path / "fpout"))
        assert fp.rows == 60
        assert "features" in fp.shards
        # margin sketch present: the exported model's training scores
        assert fp.margin.histogram.weight == 60
        assert run.num_training_rows == 60

    def test_glm_driver_opt_out(self, rng, tmp_path):
        from photon_ml_tpu.io.ingest import make_training_example

        records = [
            make_training_example(
                float(i % 2), {("a", ""): float(i)}
            )
            for i in range(20)
        ]
        train = write_records(str(tmp_path / "nofp.avro"), records)
        run_glm_training(
            {
                "train_input": [train],
                "output_dir": str(tmp_path / "nofpout"),
                "task": "LOGISTIC_REGRESSION",
                "reg_weights": [1.0],
                "max_iters": 4,
                "quality_fingerprint": False,
            }
        )
        assert not os.path.exists(
            str(tmp_path / "nofpout" / "quality-fingerprint.json")
        )

    def test_game_export_carries_baseline_into_serving(
        self, rng, game_fixture
    ):
        """game_train writes the fingerprint into the export subdir and
        the scoring engine loads it as its drift baseline — the
        hot-reload path swaps baselines atomically with the model."""
        train, valid, gs, us, tmp = game_fixture
        run = run_game_training(
            game_params(
                train, valid, gs, us, str(tmp / "qout"),
                model_output_mode="BEST",
            )
        )
        export = run.output_dirs[0]
        assert os.path.exists(
            os.path.join(export, "quality-fingerprint.json")
        )
        from photon_ml_tpu.obs.quality import BaselineFingerprint
        from photon_ml_tpu.serving.engine import ScoringEngine

        fp = BaselineFingerprint.load(export)
        assert fp.rows == 12 * 25
        assert set(fp.shards) == {"gshard", "ushard"}
        assert fp.margin.histogram.weight == 12 * 25
        assert "userId" in fp.categoricals
        engine = ScoringEngine.from_model_dir(export)
        assert engine.drift is not None
        assert engine.drift.baseline.rows == 12 * 25


@pytest.mark.partition
class TestGameDriverEntitySharded:
    """`photon-game-train --entity-shards N` (docs/PARALLEL.md): the
    driver-level wiring of entity-sharded descent — permuted row
    layout, shard_map'd random-effect coordinate, exported tables back
    in GLOBAL entity order, equal to the unsharded driver run."""

    def test_entity_sharded_matches_unsharded(self, rng, game_fixture):
        train, valid, gs, us, tmp = game_fixture
        base = game_params(train, valid, gs, us, str(tmp / "ges0"))
        run_plain = run_game_training(base)

        params = game_params(train, valid, gs, us, str(tmp / "ges1"))
        params["entity_shards"] = 4
        run_sharded = run_game_training(params)

        m_plain = run_plain.sweep[0]["model"]
        m_sharded = run_sharded.sweep[0]["model"]
        # exported tables are back in GLOBAL order: same shapes, same
        # values to solver tolerance
        np.testing.assert_allclose(
            np.asarray(m_sharded.params["global"]),
            np.asarray(m_plain.params["global"]),
            atol=1e-6,
        )
        np.testing.assert_allclose(
            np.asarray(m_sharded.params["per-user"]),
            np.asarray(m_plain.params["per-user"]),
            atol=1e-6,
        )
        assert run_sharded.sweep[0]["validation_metric"] == pytest.approx(
            run_plain.sweep[0]["validation_metric"], abs=1e-6
        )

    def test_entity_sharded_with_sharded_ckpt(self, rng, game_fixture):
        """--entity-shards + --sharded-ckpt compose: the stored-order
        entity keys land in the checkpoint shards and the run resumes."""
        train, valid, gs, us, tmp = game_fixture
        params = game_params(train, valid, gs, us, str(tmp / "ges2"))
        params["entity_shards"] = 2
        params["sharded_ckpt"] = True
        params["checkpoint_every"] = 1
        params["validate_per_coordinate"] = False
        run1 = run_game_training(params)
        assert run1.sweep[0]["validation_metric"] is not None
        # checkpoints were written sharded; a resumed run reuses them
        ckpt_root = os.path.join(str(tmp / "ges2"), "checkpoints")
        assert os.path.isdir(ckpt_root)
        params["overwrite"] = True
        params["resume"] = True
        run2 = run_game_training(params)
        np.testing.assert_allclose(
            np.asarray(run2.sweep[0]["model"].params["per-user"]),
            np.asarray(run1.sweep[0]["model"].params["per-user"]),
            atol=1e-10,
        )
