"""Sparse feature shards in GAME: the wide fixed-effect bag regime.

The reference's featureShardContainer holds (sparse) Breeze vectors per
shard; our analog stores a shard as padded-ELL ``SparseFeatures``. A
sparse shard must train/score the fixed-effect coordinate identically to
its dense twin, while per-entity (random/factored/projected) coordinates
reject it loudly — they gather dense rows."""

import numpy as np
import pytest

from photon_ml_tpu.cli.game_train import run_game_training
from photon_ml_tpu.io.avro import write_avro_file
from photon_ml_tpu.io.ingest import IngestSource, make_training_example
from photon_ml_tpu.io.schemas import TRAINING_EXAMPLE_SCHEMA
from photon_ml_tpu.io.vocab import FeatureVocabulary
from photon_ml_tpu.ops.sparse import is_sparse, to_dense


@pytest.fixture()
def game_files(rng, tmp_path):
    n, d_global, d_user = 500, 24, 4
    recs = []
    for i in range(n):
        feats = {}
        for j in rng.choice(d_global, 6, replace=False):
            feats[(f"g{j}", "")] = float(rng.normal())
        for j in range(d_user):
            feats[(f"u{j}", "")] = float(rng.normal())
        rec = make_training_example(label=float(i % 2), features=feats)
        rec["metadataMap"] = {"userId": f"user{i % 20}"}
        recs.append(rec)
    write_avro_file(
        str(tmp_path / "train" / "p.avro"), TRAINING_EXAMPLE_SCHEMA, recs
    )
    gvocab = tmp_path / "global.txt"
    gvocab.write_text(
        "".join(f"g{j}\x01\n" for j in range(d_global)) + "(INTERCEPT)\x01\n"
    )
    uvocab = tmp_path / "user.txt"
    uvocab.write_text("".join(f"u{j}\x01\n" for j in range(d_user)))
    return tmp_path, str(gvocab), str(uvocab)


def _params(tmp_path, gvocab, uvocab, out, sparse_shards, hot_columns=0):
    return {
        "train_input": [str(tmp_path / "train")],
        "validate_input": [str(tmp_path / "train")],
        "output_dir": str(tmp_path / out),
        "task": "LOGISTIC_REGRESSION",
        "num_iterations": 2,
        "updating_sequence": ["global", "per-user"],
        "feature_shards": {"globalShard": gvocab, "userShard": uvocab},
        "coordinates": {
            "global": {
                "shard": "globalShard",
                "optimizer": "TRON",
                "reg_weights": [1.0],
                "max_iters": 40,
                "tolerance": 1e-9,
                "hot_columns": hot_columns,
            },
            "per-user": {
                "shard": "userShard",
                "optimizer": "TRON",
                "reg_weights": [1.0],
                "random_effect": "userId",
                "max_iters": 40,
                "tolerance": 1e-9,
            },
        },
        "sparse_shards": sparse_shards,
    }


class TestSparseShardIngest:
    def test_game_data_matches_dense(self, game_files):
        tmp_path, gvocab, uvocab = game_files
        vocabs = {
            "globalShard": FeatureVocabulary.load(gvocab),
            "userShard": FeatureVocabulary.load(uvocab),
        }
        src = IngestSource([str(tmp_path / "train")])
        dense, _, _, _ = src.game_data(vocabs, ["userId"])
        sp, _, _, _ = IngestSource([str(tmp_path / "train")]).game_data(
            vocabs, ["userId"], sparse_shards={"globalShard"}
        )
        assert is_sparse(sp.features["globalShard"])
        assert not is_sparse(sp.features["userShard"])
        np.testing.assert_allclose(
            to_dense(sp.features["globalShard"]),
            np.asarray(dense.features["globalShard"]),
            rtol=1e-12,
        )
        # fallback (Python codec) agrees too
        fb = IngestSource([str(tmp_path / "train")])
        fb._native = lambda: None
        sp2, _, _, _ = fb.game_data(
            vocabs, ["userId"], sparse_shards={"globalShard"}
        )
        np.testing.assert_allclose(
            to_dense(sp2.features["globalShard"]),
            np.asarray(dense.features["globalShard"]),
            rtol=1e-12,
        )


class TestSparseShardTraining:
    def test_fixed_effect_sparse_matches_dense(self, game_files):
        tmp_path, gvocab, uvocab = game_files
        r_dense = run_game_training(
            _params(tmp_path, gvocab, uvocab, "out_dense", [])
        )
        r_sparse = run_game_training(
            _params(tmp_path, gvocab, uvocab, "out_sparse", ["globalShard"])
        )
        md = r_dense.sweep[r_dense.best_index]
        ms = r_sparse.sweep[r_sparse.best_index]
        np.testing.assert_allclose(
            np.asarray(ms["model"].params["global"]),
            np.asarray(md["model"].params["global"]),
            rtol=1e-6, atol=1e-8,
        )
        np.testing.assert_allclose(
            np.asarray(ms["model"].params["per-user"]),
            np.asarray(md["model"].params["per-user"]),
            rtol=1e-6, atol=1e-8,
        )
        np.testing.assert_allclose(
            ms["validation_metric"], md["validation_metric"], rtol=1e-8
        )

    def test_hybrid_fixed_coordinate_matches_dense(self, game_files):
        """hot_columns on the sparse fixed shard: the coordinate-local
        hybrid (and its private row permutation) must not change the
        solution, the per-user tables, or the validation metric."""
        tmp_path, gvocab, uvocab = game_files
        r_dense = run_game_training(
            _params(tmp_path, gvocab, uvocab, "out_dense2", [])
        )
        r_hyb = run_game_training(
            _params(
                tmp_path, gvocab, uvocab, "out_hyb",
                ["globalShard"], hot_columns=-1,
            )
        )
        md = r_dense.sweep[r_dense.best_index]
        mh = r_hyb.sweep[r_hyb.best_index]
        np.testing.assert_allclose(
            np.asarray(mh["model"].params["global"]),
            np.asarray(md["model"].params["global"]),
            rtol=1e-6, atol=1e-8,
        )
        np.testing.assert_allclose(
            np.asarray(mh["model"].params["per-user"]),
            np.asarray(md["model"].params["per-user"]),
            rtol=1e-6, atol=1e-8,
        )
        np.testing.assert_allclose(
            mh["validation_metric"], md["validation_metric"], rtol=1e-8
        )

    def test_scoring_driver_with_sparse_shard(self, game_files):
        from photon_ml_tpu.cli.score import run_scoring

        tmp_path, gvocab, uvocab = game_files
        run_game_training(
            _params(tmp_path, gvocab, uvocab, "m", ["globalShard"])
        )
        s_sparse = run_scoring(
            {
                "input": [str(tmp_path / "train")],
                "model_dir": str(tmp_path / "m"),
                "output_dir": str(tmp_path / "sc_sparse"),
                "model_kind": "game",
                "evaluate": True,
                "sparse_shards": ["globalShard"],
            }
        )
        s_dense = run_scoring(
            {
                "input": [str(tmp_path / "train")],
                "model_dir": str(tmp_path / "m"),
                "output_dir": str(tmp_path / "sc_dense"),
                "model_kind": "game",
                "evaluate": True,
            }
        )
        np.testing.assert_allclose(
            s_sparse.scores, s_dense.scores, rtol=1e-9
        )
        for k, v in s_dense.metrics.items():
            np.testing.assert_allclose(s_sparse.metrics[k], v, rtol=1e-9)


class TestSparseShardCheckpoint:
    def test_resume_equals_uninterrupted(self, game_files):
        """Checkpoint/resume across a sparse-shard GAME run: the resumed
        run reproduces the uninterrupted one exactly (params + history),
        with the ELL shard rebuilt from data at startup."""
        tmp_path, gvocab, uvocab = game_files
        full_params = _params(
            tmp_path, gvocab, uvocab, "ck_full", ["globalShard"]
        )
        full_params["num_iterations"] = 3
        r_full = run_game_training(full_params)

        part = _params(tmp_path, gvocab, uvocab, "ck_part", ["globalShard"])
        part["num_iterations"] = 2
        part["checkpoint_every"] = 1
        run_game_training(part)
        resumed = dict(part)
        resumed["num_iterations"] = 3
        resumed["resume"] = True
        r_res = run_game_training(resumed)

        mf = r_full.sweep[r_full.best_index]["model"]
        mr = r_res.sweep[r_res.best_index]["model"]
        np.testing.assert_allclose(
            np.asarray(mr.params["global"]),
            np.asarray(mf.params["global"]),
            rtol=1e-10,
        )
        np.testing.assert_allclose(
            np.asarray(mr.params["per-user"]),
            np.asarray(mf.params["per-user"]),
            rtol=1e-10,
        )


class TestBuildIndexJob:
    def test_index_job_feeds_both_drivers(self, game_files):
        """The standalone vocabulary job (FeatureIndexingJob analog)
        produces files the GAME driver consumes as feature_shards; the
        name-prefix filter partitions the namespace into bags."""
        from photon_ml_tpu.cli.build_index import build_index

        tmp_path, gvocab, uvocab = game_files
        out = str(tmp_path / "index")
        gpath = build_index(
            [str(tmp_path / "train")], out, shard="globalShard",
            name_prefix="g", add_intercept=True,
        )
        upath = build_index(
            [str(tmp_path / "train")], out, shard="userShard",
            name_prefix="u",
        )
        built_g = FeatureVocabulary.load(gpath)
        built_u = FeatureVocabulary.load(upath)
        assert all(
            k.startswith("g") or k.startswith("(INTERCEPT)")
            for k in built_g.index_to_key
        )
        assert built_g.intercept_index is not None
        assert set(built_u.index_to_key) == set(
            FeatureVocabulary.load(uvocab).index_to_key
        )
        # the GAME driver accepts the built files directly
        params = _params(tmp_path, gpath, upath, "out_idx", [])
        run = run_game_training(params)
        assert run.sweep[run.best_index]["validation_metric"] is not None

    def test_cli_main(self, game_files, capsys):
        from photon_ml_tpu.cli.build_index import main

        tmp_path, _, _ = game_files
        main(
            [
                "--input", str(tmp_path / "train"),
                "--output-dir", str(tmp_path / "idx2"),
            ]
        )
        path = capsys.readouterr().out.strip()
        assert path.endswith("feature-index.txt")
        v = FeatureVocabulary.load(path)
        assert len(v) > 0


class TestWideSparseRandomEffect:
    """A SPARSE shard trains a random effect through INDEX_MAP in ragged
    compact widths (per-entity active-column unions,
    ``RandomEffectCoordinateInProjectedSpace.scala:26-120``,
    ``IndexMapProjectorRDD.scala:113-120``): each bucket's lanes at their
    widest union, one flat table, TRON a lane; back-projection gives
    per-entity (column, value) lists, never an (E, d) table."""

    def _wide_data(self, rng, n, n_users, d_wide, pool=24, nnz=5):
        from photon_ml_tpu.game.data import GameData
        from photon_ml_tpu.ops.sparse import from_coo

        user = rng.integers(0, n_users, size=n).astype(np.int32)
        # each user touches only a private pool of columns: the regime
        # INDEX_MAP exists for (huge d, small per-entity unions)
        pools = rng.choice(d_wide, size=(n_users, pool), replace=True)
        rows = np.repeat(np.arange(n), nnz)
        slot = rng.integers(0, pool, size=n * nnz)
        cols = pools[user.repeat(nnz), slot]
        vals = rng.normal(size=n * nnz)
        sf = from_coo(rows, cols, vals, n, d_wide)
        y = (rng.uniform(size=n) < 0.5).astype(np.float64)
        data = GameData.create(
            features={"wide": sf},
            labels=y,
            entity_ids={"userId": user},
        )
        return data, sf, user, y

    @staticmethod
    def _config(max_iters=40, tolerance=1e-12, **kw):
        from photon_ml_tpu.core.tasks import TaskType
        from photon_ml_tpu.game import CoordinateConfig
        from photon_ml_tpu.models.training import OptimizerType

        return CoordinateConfig(
            shard="wide", task=TaskType.LOGISTIC_REGRESSION,
            optimizer=OptimizerType.TRON, reg_weight=1.0,
            max_iters=max_iters, tolerance=tolerance,
            random_effect="userId", **kw,
        )

    @staticmethod
    def _run_cd(coord, y, iterations=2, fuse_passes=True):
        import jax.numpy as jnp

        from photon_ml_tpu.core.tasks import TaskType
        from photon_ml_tpu.game import CoordinateDescent

        n = y.shape[0]
        cd = CoordinateDescent(
            coordinates={"re": coord},
            labels=jnp.asarray(y),
            base_offsets=jnp.zeros((n,)),
            weights=jnp.ones((n,)),
            task=TaskType.LOGISTIC_REGRESSION,
            fuse_passes=fuse_passes,
        )
        return cd.run(num_iterations=iterations)

    @staticmethod
    def _dense(compact, d):
        """The per-entity lists as an (E, d) table — the test's own oracle
        form, at a toy width."""
        cols, vals = np.asarray(compact.columns), np.asarray(compact.values)
        table = np.zeros((cols.shape[0], d))
        for e in range(cols.shape[0]):
            keep = cols[e] < d
            table[e, cols[e][keep]] = vals[e][keep]
        return table

    def test_matches_dense_oracle(self, rng):
        """The ragged INDEX_MAP coordinate through the FUSED descent ==
        plain dense RE CD on the densified shard (no caps: per-entity
        subproblems are identical; columns outside an entity's union solve
        to exactly 0 under L2, and are not held at all)."""
        import jax.numpy as jnp

        from photon_ml_tpu.game import (
            IndexMapRandomEffectCoordinate,
            RandomEffectCoordinate,
            build_bucketed_random_effect_design,
        )
        from photon_ml_tpu.game.data import GameData
        from photon_ml_tpu.game.scoring import CompactReTable

        d_wide = 3000
        n, n_users = 400, 12
        data, sf, user, y = self._wide_data(rng, n, n_users, d_wide)
        cfg = self._config()
        coord = IndexMapRandomEffectCoordinate.from_sparse_shard(
            data, "userId", "wide", n_users, cfg, num_buckets=2,
            dtype=jnp.float64,
        )
        m_proj, h_proj = self._run_cd(coord, y)
        compact = coord.back_project(m_proj.params["re"])
        assert isinstance(compact, CompactReTable)
        assert np.asarray(compact.columns).shape[1] < d_wide
        table_wide = self._dense(compact, d_wide)

        dense = to_dense(sf)
        dense_data = GameData.create(
            features={"wide": dense}, labels=y,
            entity_ids={"userId": user},
        )
        design = build_bucketed_random_effect_design(
            dense_data, "userId", "wide", n_users, num_buckets=2,
            dtype=jnp.float64,
        )
        dense_coord = RandomEffectCoordinate(
            design=design,
            row_features=jnp.asarray(dense),
            row_entities=jnp.asarray(user),
            full_offsets_base=jnp.zeros((n,)),
            config=cfg,
        )
        m_dense, _ = self._run_cd(dense_coord, y)
        table_dense = np.asarray(m_dense.params["re"])

        np.testing.assert_allclose(table_wide, table_dense, atol=1e-7)
        assert h_proj[-1].objective <= h_proj[0].objective + 1e-9

    def test_60k_columns_per_entity_sklearn_oracle(self, rng):
        """The acceptance shape: an RE coordinate trains on a 60k-column
        SPARSE shard (a dense design would be ~GBs); one entity's solution
        is checked against sklearn on that entity's own rows, and the
        entity's lists hold exactly its active columns."""
        import jax.numpy as jnp

        from photon_ml_tpu.game import IndexMapRandomEffectCoordinate

        d_wide = 60_000
        n, n_users = 600, 10
        data, sf, user, y = self._wide_data(
            rng, n, n_users, d_wide, pool=20, nnz=6
        )
        coord = IndexMapRandomEffectCoordinate.from_sparse_shard(
            data, "userId", "wide", n_users, self._config(max_iters=50),
            num_buckets=2, dtype=jnp.float64,
        )
        model, _ = self._run_cd(coord, y, iterations=1)
        compact = coord.back_project(model.params["re"])
        cols = np.asarray(compact.columns)
        vals = np.asarray(compact.values)
        assert cols.shape[0] == n_users and cols.shape[1] <= 20
        assert np.all(np.isfinite(vals))

        # dense oracle for ONE entity: its rows restricted to its active
        # columns — mathematically the exact same L2-logistic problem
        from sklearn.linear_model import LogisticRegression

        e = 3
        rows_e = np.flatnonzero(user == e)
        dense_rows = np.zeros((rows_e.size, d_wide))
        ind = np.asarray(sf.indices)[rows_e]
        val = np.asarray(sf.values)[rows_e]
        keep = ind < d_wide
        r_ids = np.broadcast_to(
            np.arange(rows_e.size)[:, None], ind.shape
        )[keep]
        np.add.at(dense_rows, (r_ids, ind[keep]), val[keep])
        active = np.flatnonzero(np.abs(dense_rows).sum(axis=0))
        skl = LogisticRegression(
            C=1.0, fit_intercept=False, tol=1e-10, max_iter=2000
        ).fit(dense_rows[:, active], y[rows_e])
        held = cols[e] < d_wide
        # the entity's lists are exactly its active columns, ascending
        np.testing.assert_array_equal(cols[e][held], active)
        np.testing.assert_allclose(vals[e][held], skl.coef_.ravel(),
                                   atol=2e-5)
        assert np.all(vals[e][~held] == 0.0)

    def test_sparse_re_scoring_matches_dense(self, rng):
        """Compact scoring (the coordinate's gather of the flat table at
        every row's stored entries, and ``score_game_data`` over the
        back-projected lists) == dense scoring of the densified table on
        the densified shard."""
        import jax.numpy as jnp

        from photon_ml_tpu.game import IndexMapRandomEffectCoordinate
        from photon_ml_tpu.game.scoring import score_game_data

        d_wide = 2000
        data, sf, user, y = self._wide_data(rng, 200, 8, d_wide)
        coord = IndexMapRandomEffectCoordinate.from_sparse_shard(
            data, "userId", "wide", 8, self._config(), num_buckets=2,
            dtype=jnp.float64,
        )
        flat = jnp.asarray(
            rng.normal(size=coord.initial_params().shape)
            * (coord.design.index_map.columns >= 0))
        compact = coord.back_project(flat)
        table = self._dense(compact, d_wide)
        dense_data = __import__("dataclasses").replace(
            data, features={"wide": to_dense(sf)}
        )
        s_dense = np.asarray(
            score_game_data(
                {"re": table}, {"re": "wide"}, {"re": "userId"}, dense_data
            )
        )
        s_compact = np.asarray(
            score_game_data(
                {"re": compact}, {"re": "wide"}, {"re": "userId"}, data
            )
        )
        np.testing.assert_allclose(np.asarray(coord.score(flat)), s_dense,
                                   rtol=1e-9, atol=1e-12)
        np.testing.assert_allclose(s_compact, s_dense, rtol=1e-9,
                                   atol=1e-12)

    def test_precompacted_table_and_cache(self, rng):
        """The lists an INDEX_MAP coordinate back-projects ARE a
        CompactReTable: scored as they are against sparse and dense
        shards, with no host densify; the implicit compaction cache of
        (E, d) tables serves only IMMUTABLE tables (jax arrays /
        non-writeable numpy) and evicts with its referent."""
        import jax.numpy as jnp

        from photon_ml_tpu.game import IndexMapRandomEffectCoordinate
        from photon_ml_tpu.game.scoring import (
            CompactReTable,
            _COMPACT_CACHE,
            _compact_table,
            _compact_table_cached,
            score_game_data,
        )

        d_wide = 500
        data, sf, user, y = self._wide_data(rng, 100, 6, d_wide)
        coord = IndexMapRandomEffectCoordinate.from_sparse_shard(
            data, "userId", "wide", 6, self._config(), num_buckets=1,
            dtype=jnp.float64,
        )
        model, _ = self._run_cd(coord, y, iterations=1)
        lists = coord.back_project(model.params["re"])
        table = self._dense(lists, d_wide)
        base = np.asarray(
            score_game_data(
                {"re": table}, {"re": "wide"}, {"re": "userId"}, data
            )
        )
        for compact in (lists, CompactReTable(*_compact_table(table))):
            got = np.asarray(
                score_game_data(
                    {"re": compact}, {"re": "wide"}, {"re": "userId"}, data
                )
            )
            np.testing.assert_allclose(got, base, rtol=1e-9, atol=1e-12)
            # against a dense shard: the compact-dense gather kernel (the
            # serving engine's path) reproduces the scores
            dense_data = __import__("dataclasses").replace(
                data, features={"wide": to_dense(sf)}
            )
            got_dense = np.asarray(
                score_game_data(
                    {"re": compact}, {"re": "wide"}, {"re": "userId"},
                    dense_data,
                )
            )
            np.testing.assert_allclose(got_dense, base, rtol=1e-9,
                                       atol=1e-12)

        # writeable numpy: never cached (in-place mutation must be seen)
        t_np = np.array(table)
        c1 = _compact_table_cached(t_np)
        t_np[0, :] = 0.0
        c2 = _compact_table_cached(t_np)
        assert not np.array_equal(
            np.asarray(c1.values[0]), np.asarray(c2.values[0])
        )

        # jax array (immutable): cached by identity, evicted on death
        t_dev = jnp.asarray(table)
        c1 = _compact_table_cached(t_dev)
        c2 = _compact_table_cached(t_dev)
        assert c1 is c2
        key = id(t_dev)
        assert key in _COMPACT_CACHE
        del t_dev, c1, c2
        import gc

        gc.collect()
        assert key not in _COMPACT_CACHE


class TestSparseShardGuards:
    def test_random_effect_on_sparse_shard_rejected_without_projector(
        self, game_files
    ):
        tmp_path, gvocab, uvocab = game_files
        params = _params(
            tmp_path, gvocab, uvocab, "out_bad", ["userShard"]
        )
        with pytest.raises(ValueError, match="dense per-row features"):
            run_game_training(params)

    def test_random_effect_on_sparse_shard_with_index_map_trains(
        self, game_files
    ):
        """The driver path end-to-end: sparse userShard + INDEX_MAP
        projector trains, saves per-entity (column, value) lists
        (``coefficientLayout=entity-sparse``), loads them back as lists,
        and the scoring driver reproduces the saved model's AUC."""
        from photon_ml_tpu.cli.score import run_scoring
        from photon_ml_tpu.game.scoring import CompactReTable
        from photon_ml_tpu.io.models import load_game_model_auto

        tmp_path, gvocab, uvocab = game_files
        params = _params(
            tmp_path, gvocab, uvocab, "out_wide_re", ["userShard"]
        )
        params["coordinates"]["per-user"]["projector"] = "INDEX_MAP"
        run = run_game_training(params)
        best = run.sweep[run.best_index]
        assert isinstance(best["model"].params["per-user"], CompactReTable)
        info = (tmp_path / "out_wide_re" / "best" / "random-effect"
                / "per-user" / "id-info").read_text()
        assert "coefficientLayout=entity-sparse" in info
        loaded, _, _, _, _ = load_game_model_auto(
            str(tmp_path / "out_wide_re"))
        assert isinstance(loaded["per-user"], CompactReTable)
        scored = run_scoring(
            {
                "input": [str(tmp_path / "train")],
                "model_dir": str(tmp_path / "out_wide_re"),
                "output_dir": str(tmp_path / "sc_wide_re"),
                "model_kind": "game",
                "evaluate": True,
                "sparse_shards": ["userShard"],
            }
        )
        np.testing.assert_allclose(
            scored.metrics["AREA_UNDER_RECEIVER_OPERATOR_CHARACTERISTICS"],
            best["validation_metric"], rtol=1e-5)

    def test_index_map_model_serves_its_lists(self, game_files):
        """The serving engine loads an INDEX_MAP coordinate's saved
        per-entity lists as they are and scores dense rows through them
        exactly as the offline scorer does from the same load."""
        from photon_ml_tpu.game.scoring import CompactReTable, score_game_data
        from photon_ml_tpu.io.models import load_game_model_auto
        from photon_ml_tpu.serving.engine import ScoringEngine

        tmp_path, gvocab, uvocab = game_files
        params = _params(
            tmp_path, gvocab, uvocab, "out_served", ["userShard"]
        )
        params["coordinates"]["per-user"]["projector"] = "INDEX_MAP"
        run_game_training(params)
        root = str(tmp_path / "out_served")
        loaded, shards, res, shard_vocabs, re_vocabs = load_game_model_auto(
            root)
        assert isinstance(loaded["per-user"], CompactReTable)
        data, _, _, _ = IngestSource([str(tmp_path / "train")]).game_data(
            shard_vocabs, ["userId"], entity_vocabs=re_vocabs)
        offline = np.asarray(score_game_data(loaded, shards, res, data))
        engine = ScoringEngine.from_model_dir(root)
        np.testing.assert_allclose(engine.score_data(data), offline,
                                   rtol=1e-9, atol=1e-12)

    def test_hot_columns_requires_sparse_fixed(self, game_files):
        tmp_path, gvocab, uvocab = game_files
        # dense shard + hot_columns -> config error
        params = _params(
            tmp_path, gvocab, uvocab, "out_bad2", [], hot_columns=-1
        )
        with pytest.raises(ValueError, match="hot_columns applies"):
            run_game_training(params)

    def test_design_builder_guard(self, game_files):
        from photon_ml_tpu.game.data import (
            GameData,
            build_bucketed_random_effect_design,
            build_random_effect_design,
        )
        from photon_ml_tpu.ops.sparse import from_dense

        rng = np.random.default_rng(0)
        x = rng.normal(size=(20, 5))
        data = GameData.create(
            features={"s": from_dense(x)},
            labels=np.zeros(20),
            entity_ids={"u": np.zeros(20, np.int32)},
        )
        for builder in (
            build_random_effect_design,
            build_bucketed_random_effect_design,
        ):
            with pytest.raises(ValueError, match="sparse"):
                builder(data, "u", "s", 1)
