"""GLM training API: sklearn/closed-form parity, lambda paths, normalization
equivalence (the reference's NormalizationContextIntegTest contract), task
validation matrix, model selection."""

import jax.numpy as jnp
import numpy as np
import os
import pytest
from sklearn.linear_model import LogisticRegression

from photon_ml_tpu.core.normalization import NormalizationType
from photon_ml_tpu.core.types import Coefficients, LabeledBatch
from photon_ml_tpu.core.validators import (
    DataValidationType,
    sanity_check_data,
)
from photon_ml_tpu.models import (
    GLMTrainingConfig,
    OptimizerType,
    TaskType,
    train_glm,
)
from photon_ml_tpu.models.selection import select_best_model
from photon_ml_tpu.ops.objective import RegularizationContext


def make_logistic_data(rng, n=800, d=12, intercept=True):
    x = rng.normal(size=(n, d))
    if intercept:
        x = np.concatenate([x, np.ones((n, 1))], axis=1)
    w_true = rng.normal(size=x.shape[1])
    p = 1.0 / (1.0 + np.exp(-x @ w_true))
    y = (rng.uniform(size=n) < p).astype(float)
    return x, y


class TestLogistic:
    def test_matches_sklearn_l2(self, rng):
        x, y = make_logistic_data(rng, intercept=False)
        batch = LabeledBatch.create(x, y, dtype=jnp.float64)
        lam = 2.0
        cfg = GLMTrainingConfig(
            task=TaskType.LOGISTIC_REGRESSION,
            regularization=RegularizationContext("L2"),
            reg_weights=(lam,),
            tolerance=1e-12,
            max_iters=200,
        )
        (tm,) = train_glm(batch, cfg)
        skl = LogisticRegression(
            C=1.0 / lam, fit_intercept=False, tol=1e-12, max_iter=5000
        ).fit(x, y)
        np.testing.assert_allclose(
            np.asarray(tm.model.coefficients.means),
            skl.coef_.ravel(),
            atol=1e-6,
        )

    def test_tron_equals_lbfgs(self, rng):
        x, y = make_logistic_data(rng, intercept=False)
        batch = LabeledBatch.create(x, y, dtype=jnp.float64)
        common = dict(
            task=TaskType.LOGISTIC_REGRESSION,
            regularization=RegularizationContext("L2"),
            reg_weights=(1.0,),
            tolerance=1e-12,
            max_iters=100,
        )
        (lb,) = train_glm(batch, GLMTrainingConfig(**common))
        (tr,) = train_glm(
            batch, GLMTrainingConfig(optimizer=OptimizerType.TRON, **common)
        )
        np.testing.assert_allclose(
            np.asarray(lb.model.coefficients.means),
            np.asarray(tr.model.coefficients.means),
            atol=1e-6,
        )

    def test_lambda_path_order_and_shrinkage(self, rng):
        x, y = make_logistic_data(rng, intercept=False)
        batch = LabeledBatch.create(x, y, dtype=jnp.float64)
        lambdas = (0.1, 10.0, 1.0)  # deliberately unsorted
        cfg = GLMTrainingConfig(
            task=TaskType.LOGISTIC_REGRESSION,
            regularization=RegularizationContext("L2"),
            reg_weights=lambdas,
        )
        trained = train_glm(batch, cfg)
        assert [tm.reg_weight for tm in trained] == list(lambdas)
        norms = {
            tm.reg_weight: float(jnp.linalg.norm(tm.model.coefficients.means))
            for tm in trained
        }
        assert norms[10.0] < norms[1.0] < norms[0.1]

    def test_elastic_net_sparsity(self, rng):
        x, y = make_logistic_data(rng, n=400, d=30, intercept=False)
        batch = LabeledBatch.create(x, y, dtype=jnp.float64)
        cfg = GLMTrainingConfig(
            task=TaskType.LOGISTIC_REGRESSION,
            regularization=RegularizationContext("ELASTIC_NET", alpha=0.9),
            reg_weights=(5.0,),
            max_iters=200,
        )
        (tm,) = train_glm(batch, cfg)
        w = np.asarray(tm.model.coefficients.means)
        assert np.sum(w == 0.0) > 0  # OWL-QN produces exact zeros

    def test_variances_positive(self, rng):
        x, y = make_logistic_data(rng, intercept=False)
        batch = LabeledBatch.create(x, y, dtype=jnp.float64)
        cfg = GLMTrainingConfig(
            task=TaskType.LOGISTIC_REGRESSION,
            regularization=RegularizationContext("L2"),
            reg_weights=(1.0,),
            compute_variances=True,
        )
        (tm,) = train_glm(batch, cfg)
        v = np.asarray(tm.model.coefficients.variances)
        assert v.shape == tm.model.coefficients.means.shape
        assert np.all(v > 0)


class TestNormalizationEquivalence:
    """Training with any normalization type must give the same raw-space
    model when unregularized (``NormalizationContextIntegTest`` contract)."""

    @pytest.mark.parametrize(
        "norm",
        [
            NormalizationType.NONE,
            NormalizationType.SCALE_WITH_STANDARD_DEVIATION,
            NormalizationType.SCALE_WITH_MAX_MAGNITUDE,
            NormalizationType.STANDARDIZATION,
        ],
    )
    def test_raw_space_solution_invariant(self, rng, norm):
        rng = np.random.default_rng(5)
        x, y = make_logistic_data(rng, n=500, d=6, intercept=True)
        x[:, :3] *= 50.0  # badly scaled features
        batch = LabeledBatch.create(x, y, dtype=jnp.float64)
        base_cfg = dict(
            task=TaskType.LOGISTIC_REGRESSION,
            reg_weights=(0.0,),
            tolerance=1e-13,
            max_iters=500,
            intercept_index=x.shape[1] - 1,
        )
        (ref,) = train_glm(batch, GLMTrainingConfig(**base_cfg))
        (tm,) = train_glm(batch, GLMTrainingConfig(normalization=norm, **base_cfg))
        np.testing.assert_allclose(
            np.asarray(tm.model.coefficients.means),
            np.asarray(ref.model.coefficients.means),
            atol=5e-4,
        )


class TestNormalizationInverse:
    def test_transform_round_trip(self, rng):
        from photon_ml_tpu.core.normalization import (
            build_normalization_context,
        )
        from photon_ml_tpu.ops.stats import summarize_features

        x = np.concatenate(
            [rng.normal(size=(80, 5)) * 7 + 2, np.ones((80, 1))], axis=1
        )
        batch = LabeledBatch.create(x, np.zeros(80), dtype=jnp.float64)
        ctx = build_normalization_context(
            NormalizationType.STANDARDIZATION, summarize_features(batch), 5
        )
        coef = Coefficients.of(rng.normal(size=6), rng.uniform(1, 2, size=6))
        raw = ctx.transform_model_coefficients(coef, 5)
        back = ctx.inverse_transform_model_coefficients(raw, 5)
        np.testing.assert_allclose(
            np.asarray(back.means), np.asarray(coef.means), atol=1e-12
        )
        np.testing.assert_allclose(
            np.asarray(back.variances), np.asarray(coef.variances), atol=1e-12
        )

    def test_warm_start_raw_space(self, rng):
        x, y = make_logistic_data(rng, n=400, d=5, intercept=True)
        x[:, :2] *= 20.0
        batch = LabeledBatch.create(x, y, dtype=jnp.float64)
        cfg = GLMTrainingConfig(
            task=TaskType.LOGISTIC_REGRESSION,
            normalization=NormalizationType.STANDARDIZATION,
            intercept_index=5,
            reg_weights=(0.01,),
            tolerance=1e-12,
            max_iters=300,
        )
        (first,) = train_glm(batch, cfg)
        # warm start from the raw-space model: must converge ~immediately
        (second,) = train_glm(
            batch, cfg, initial_coefficients=first.model.coefficients
        )
        assert int(second.result.iterations) <= 2
        np.testing.assert_allclose(
            np.asarray(second.model.coefficients.means),
            np.asarray(first.model.coefficients.means),
            atol=1e-6,
        )


class TestLinearAndPoisson:
    def test_ridge_closed_form(self, rng):
        n, d = 300, 8
        x = rng.normal(size=(n, d))
        y = x @ rng.normal(size=d) + 0.1 * rng.normal(size=n)
        lam = 3.0
        batch = LabeledBatch.create(x, y, dtype=jnp.float64)
        cfg = GLMTrainingConfig(
            task=TaskType.LINEAR_REGRESSION,
            regularization=RegularizationContext("L2"),
            reg_weights=(lam,),
            tolerance=1e-13,
            max_iters=200,
        )
        (tm,) = train_glm(batch, cfg)
        w_closed = np.linalg.solve(x.T @ x + lam * np.eye(d), x.T @ y)
        np.testing.assert_allclose(
            np.asarray(tm.model.coefficients.means), w_closed, atol=1e-7
        )

    def test_poisson_stationarity(self, rng):
        n, d = 400, 6
        x = rng.normal(size=(n, d)) * 0.3
        y = rng.poisson(np.exp(x @ rng.normal(size=d) * 0.5)).astype(float)
        batch = LabeledBatch.create(x, y, dtype=jnp.float64)
        cfg = GLMTrainingConfig(
            task=TaskType.POISSON_REGRESSION,
            regularization=RegularizationContext("L2"),
            reg_weights=(0.5,),
            tolerance=1e-12,
            max_iters=200,
        )
        (tm,) = train_glm(batch, cfg)
        w = np.asarray(tm.model.coefficients.means)
        grad = x.T @ (np.exp(x @ w) - y) + 0.5 * w
        assert np.linalg.norm(grad) < 1e-5 * n

    def test_smoothed_hinge_classifies(self, rng):
        x = rng.normal(size=(400, 5))
        y = (x @ rng.normal(size=5) > 0).astype(float)  # separable
        batch = LabeledBatch.create(x, y, dtype=jnp.float64)
        cfg = GLMTrainingConfig(
            task=TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM,
            regularization=RegularizationContext("L2"),
            reg_weights=(0.1,),
        )
        (tm,) = train_glm(batch, cfg)
        pred = np.asarray(tm.model.predict_class(jnp.asarray(x)))
        assert np.mean(pred == y) > 0.7


class TestValidationMatrix:
    def test_tron_l1_forbidden(self):
        with pytest.raises(ValueError, match="TRON"):
            GLMTrainingConfig(
                optimizer=OptimizerType.TRON,
                regularization=RegularizationContext("L1"),
            ).validate()

    def test_constraints_with_normalization_forbidden(self):
        with pytest.raises(ValueError, match="constraint"):
            GLMTrainingConfig(
                normalization=NormalizationType.SCALE_WITH_STANDARD_DEVIATION,
                lower_bounds=jnp.zeros(3),
                intercept_index=0,
            ).validate()

    def test_standardization_needs_intercept(self):
        with pytest.raises(ValueError, match="intercept"):
            GLMTrainingConfig(
                normalization=NormalizationType.STANDARDIZATION
            ).validate()

    def test_tron_smoothed_hinge_forbidden(self):
        with pytest.raises(ValueError, match="first-order"):
            GLMTrainingConfig(
                task=TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM,
                optimizer=OptimizerType.TRON,
            ).validate()


class TestHashableBounds:
    """Box bounds are stored as content-hashed HashableBounds so configs
    key the lru_cache'd solver builder in O(1) instead of hashing a
    d_block-length float tuple per solve."""

    def test_wrap_equality_and_hash(self):
        from photon_ml_tpu.models.training import HashableBounds

        a = HashableBounds([0.0, 1.0, 2.0])
        b = HashableBounds(np.array([0.0, 1.0, 2.0]))
        c = HashableBounds([0.0, 1.0, 2.5])
        assert a == b and hash(a) == hash(b)
        assert a != c
        assert a == (0.0, 1.0, 2.0)  # sequence equality for tests/callers
        assert a != None  # noqa: E711 — exercises __eq__(None)
        assert len(a) == 3 and list(a) == [0.0, 1.0, 2.0]
        np.testing.assert_array_equal(np.asarray(a), [0.0, 1.0, 2.0])

    def test_digest_key_is_o1_per_lookup(self):
        """Equality between HashableBounds is digest-vs-digest — the
        bytes key is computed ONCE at construction, so every solver-
        cache lookup on a bounds-carrying config costs O(1) in d (no
        per-lookup elementwise compare of d boxed floats)."""
        from unittest import mock

        from photon_ml_tpu.models.training import HashableBounds

        a = HashableBounds(np.arange(10_000, dtype=float))
        b = HashableBounds(np.arange(10_000, dtype=float))
        assert isinstance(a.digest, bytes)
        assert a.digest == b.digest and a == b
        # HB-vs-HB equality must never touch the value arrays
        with mock.patch.object(
            np, "array_equal",
            side_effect=AssertionError("O(d) compare on HB==HB"),
        ):
            assert a == b
            assert a != HashableBounds(np.arange(3, dtype=float))
        # d=10k configs differing only in bounds hash/compare apart
        cfg_a = GLMTrainingConfig(lower_bounds=a)
        cfg_b = GLMTrainingConfig(
            lower_bounds=np.arange(10_000, dtype=float) + 1.0
        )
        assert cfg_a != cfg_b

    def test_config_wraps_and_rewraps_idempotently(self):
        import dataclasses

        from photon_ml_tpu.models.training import HashableBounds

        cfg = GLMTrainingConfig(
            lower_bounds=np.zeros(4), upper_bounds=(1.0, 1.0, 1.0, 1.0)
        )
        assert isinstance(cfg.lower_bounds, HashableBounds)
        assert isinstance(cfg.upper_bounds, HashableBounds)
        lb = cfg.lower_bounds
        cfg2 = dataclasses.replace(cfg, reg_weights=(2.0,))
        assert cfg2.lower_bounds is lb  # no rewrap churn
        assert cfg == dataclasses.replace(cfg)  # hashable + stable
        assert hash(cfg) == hash(dataclasses.replace(cfg))
        scfg = cfg.solver_config()
        np.testing.assert_array_equal(
            np.asarray(scfg.lower_bounds), np.zeros(4)
        )


class TestValidators:
    def test_clean_data_passes(self, rng):
        x, y = make_logistic_data(rng, n=100, d=4, intercept=False)
        batch = LabeledBatch.create(x, y, dtype=jnp.float64)
        counts = sanity_check_data(batch, TaskType.LOGISTIC_REGRESSION)
        assert all(v == 0 for v in counts.values())

    def test_nan_features_rejected(self, rng):
        x, y = make_logistic_data(rng, n=50, d=4, intercept=False)
        x[3, 2] = np.nan
        batch = LabeledBatch.create(x, y, dtype=jnp.float64)
        with pytest.raises(ValueError, match="finite_features"):
            sanity_check_data(batch, TaskType.LOGISTIC_REGRESSION)

    def test_nonbinary_label_rejected_for_classifier(self, rng):
        x, _ = make_logistic_data(rng, n=50, d=4, intercept=False)
        y = np.full(50, 2.0)
        batch = LabeledBatch.create(x, y, dtype=jnp.float64)
        with pytest.raises(ValueError, match="binary_label"):
            sanity_check_data(batch, TaskType.LOGISTIC_REGRESSION)

    def test_negative_label_rejected_for_poisson(self, rng):
        x, _ = make_logistic_data(rng, n=50, d=4, intercept=False)
        y = np.full(50, -1.0)
        batch = LabeledBatch.create(x, y, dtype=jnp.float64)
        with pytest.raises(ValueError, match="non_negative_label"):
            sanity_check_data(batch, TaskType.POISSON_REGRESSION)

    def test_disabled_mode_skips(self, rng):
        x, _ = make_logistic_data(rng, n=50, d=4, intercept=False)
        y = np.full(50, np.nan)
        batch = LabeledBatch.create(x, y, dtype=jnp.float64)
        assert (
            sanity_check_data(
                batch,
                TaskType.LOGISTIC_REGRESSION,
                DataValidationType.VALIDATE_DISABLED,
            )
            == {}
        )

    def test_padding_rows_exempt(self, rng):
        x, y = make_logistic_data(rng, n=50, d=4, intercept=False)
        batch = LabeledBatch.pad_to(
            LabeledBatch.create(x, y, dtype=jnp.float64), 64
        )
        # poison the padding rows only
        feats = np.array(batch.features)  # writable copy
        feats[55] = np.nan
        poisoned = LabeledBatch.create(
            feats, batch.labels, batch.offsets, batch.weights, batch.mask,
            dtype=jnp.float64,
        )
        sanity_check_data(poisoned, TaskType.LOGISTIC_REGRESSION)


class TestModelSelection:
    def test_best_lambda_by_auc(self, rng):
        x, y = make_logistic_data(rng, n=600, d=10, intercept=False)
        xt, yt = x[:400], y[:400]
        xv, yv = x[400:], y[400:]
        cfg = GLMTrainingConfig(
            task=TaskType.LOGISTIC_REGRESSION,
            regularization=RegularizationContext("L2"),
            reg_weights=(1000.0, 1.0),
        )
        trained = train_glm(LabeledBatch.create(xt, yt, dtype=jnp.float64), cfg)
        best, scores = select_best_model(
            trained, LabeledBatch.create(xv, yv, dtype=jnp.float64)
        )
        # AUC is scale-invariant so shrinkage barely moves it; just require
        # selection consistency: the winner carries the max score
        assert scores[best.reg_weight] == max(scores.values())

    def test_best_lambda_by_rmse(self, rng):
        n, d = 600, 8
        x = rng.normal(size=(n, d))
        y = x @ rng.normal(size=d) + 0.05 * rng.normal(size=n)
        cfg = GLMTrainingConfig(
            task=TaskType.LINEAR_REGRESSION,
            regularization=RegularizationContext("L2"),
            reg_weights=(10000.0, 0.1),
        )
        trained = train_glm(
            LabeledBatch.create(x[:400], y[:400], dtype=jnp.float64), cfg
        )
        best, scores = select_best_model(
            trained, LabeledBatch.create(x[400:], y[400:], dtype=jnp.float64)
        )
        # the absurd lambda shrinks predictions to ~0: RMSE must pick 0.1
        assert best.reg_weight == 0.1
        assert scores[0.1] < scores[10000.0]


class TestDebugHarness:
    def test_debug_nans_raises_at_producer(self):
        import jax
        import jax.numpy as jnp

        from photon_ml_tpu.utils.debug import debug_nans

        with debug_nans(True):
            with pytest.raises(FloatingPointError):
                jax.jit(lambda x: jnp.log(x) * 0 + jnp.sqrt(x))(
                    jnp.asarray(-1.0)
                )
        # restored afterwards: the same op silently yields nan again
        assert bool(jnp.isnan(jnp.sqrt(jnp.asarray(-1.0))))

    def test_assert_all_finite_names_path(self):
        import jax.numpy as jnp

        from photon_ml_tpu.utils.debug import assert_all_finite

        good = {"a": jnp.ones(3), "b": [jnp.zeros(2)]}
        assert_all_finite(good, "model")
        bad = {"a": jnp.ones(3), "b": [jnp.asarray([1.0, float("nan")])]}
        with pytest.raises(FloatingPointError, match=r"model\['b'\]\[0\]"):
            assert_all_finite(bad, "model")

    def test_assert_sharding(self, devices):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from photon_ml_tpu.parallel import make_mesh
        from photon_ml_tpu.utils.debug import assert_sharding

        mesh = make_mesh(8)
        x = jax.device_put(
            jnp.zeros((16, 4)), NamedSharding(mesh, P("data"))
        )
        assert_sharding(x, mesh, P("data"))
        with pytest.raises(AssertionError, match="sharding mismatch"):
            assert_sharding(x, mesh, P(None, "data"))

    def test_profile_trace_writes_artifact(self, tmp_path):
        import jax.numpy as jnp

        from photon_ml_tpu.utils.debug import profile_trace

        out = str(tmp_path / "trace")
        with profile_trace(out):
            float(jnp.sum(jnp.ones((64, 64)) @ jnp.ones((64, 64))))
        # a plugins/profile/<ts>/ tree with at least one trace file
        found = [
            os.path.join(r, f)
            for r, _, files in os.walk(out)
            for f in files
        ]
        assert found, f"no trace artifacts under {out}"

    def test_driver_profile_flag(self, rng, tmp_path):
        import numpy as np

        from photon_ml_tpu.cli.train import run_glm_training
        from photon_ml_tpu.io.avro import write_avro_file
        from photon_ml_tpu.io.ingest import make_training_example
        from photon_ml_tpu.io.schemas import TRAINING_EXAMPLE_SCHEMA

        x = rng.normal(size=(200, 3))
        y = (rng.uniform(size=200) < 0.5).astype(float)
        recs = [
            make_training_example(
                y[i], {(f"f{j}", ""): x[i, j] for j in range(3)}
            )
            for i in range(200)
        ]
        tdir = tmp_path / "t"
        tdir.mkdir()
        write_avro_file(
            str(tdir / "p.avro"), TRAINING_EXAMPLE_SCHEMA, recs
        )
        out = str(tmp_path / "out")
        run_glm_training(
            {
                "train_input": [str(tdir)],
                "output_dir": out,
                "reg_weights": [1.0],
                "max_iters": 5,
                "profile": True,
            }
        )
        assert os.path.isdir(os.path.join(out, "profile"))


class TestCachedSolveZeroRecompile:
    """The training-side analog of serving's zero-recompile guarantee
    (docs/OBSERVABILITY.md): ``_build_solver`` caches ONE jitted solve
    per config shape with reg weights as traced arguments, so a second
    train_glm at a new lambda — the lambda path, GAME CD rounds,
    bootstrap replicas — must reach steady state without a single new
    XLA backend compile."""

    def test_repeat_solves_do_not_recompile(self, rng):
        from photon_ml_tpu.obs import (
            install_compile_listener,
            xla_compile_events,
        )

        install_compile_listener()
        x, y = make_logistic_data(rng, n=400, d=8, intercept=False)
        batch = LabeledBatch.create(x, y, dtype=jnp.float64)

        def cfg(lam):
            return GLMTrainingConfig(
                task=TaskType.LOGISTIC_REGRESSION,
                optimizer=OptimizerType.TRON,
                regularization=RegularizationContext("L2"),
                reg_weights=(lam,),
                tolerance=1e-8,
                max_iters=30,
            )

        (warm,) = train_glm(batch, cfg(2.0))  # compile + warm
        np.asarray(warm.model.coefficients.means)
        before = xla_compile_events()
        for lam in (1.0, 0.5, 0.25):
            (tm,) = train_glm(batch, cfg(lam))
            np.asarray(tm.model.coefficients.means)
        assert xla_compile_events() == before, (
            "cached-solve path recompiled in steady state: reg weights "
            "must ride as traced arguments, never trace-time constants"
        )
