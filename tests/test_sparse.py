"""Sparse (wide) feature support: the padded-ELL kernels must agree exactly
with their dense counterparts, training on sparse batches must match the
dense oracle on the support, and the d >= 100k regime must work without ever
materializing an (n, d) matrix (the reference's PalDB >200k-feature regime,
``util/PalDBIndexMap.scala:43``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.core.types import LabeledBatch
from photon_ml_tpu.ops.losses import LOGISTIC_LOSS
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.ops.sparse import (
    SparseFeatures,
    from_coo,
    from_dense,
    matvec,
    rmatvec,
    colsum,
    to_dense,
)


def random_sparse(rng, n, d, nnz):
    rows = np.repeat(np.arange(n), nnz)
    cols = rng.integers(0, d, size=n * nnz)
    vals = rng.normal(size=n * nnz)
    return rows, cols, vals


class TestKernels:
    def test_round_trip_and_dedup(self, rng):
        # duplicate (row, col) pairs must sum (DataProcessingUtils dedup)
        rows = np.array([0, 0, 1, 0])
        cols = np.array([2, 2, 0, 1])
        vals = np.array([1.0, 2.0, 5.0, -1.0])
        sf = from_coo(rows, cols, vals, 3, 4, dtype=jnp.float64)
        dense = to_dense(sf)
        expect = np.zeros((3, 4))
        expect[0, 2] = 3.0
        expect[0, 1] = -1.0
        expect[1, 0] = 5.0
        np.testing.assert_array_equal(dense, expect)

    def test_matvec_rmatvec_colsum_match_dense(self, rng):
        n, d, nnz = 64, 50, 7
        sf = from_coo(*random_sparse(rng, n, d, nnz), n, d, dtype=jnp.float64)
        x = to_dense(sf)
        w = rng.normal(size=d)
        a = rng.normal(size=n)
        np.testing.assert_allclose(
            np.asarray(matvec(sf, jnp.asarray(w))), x @ w, rtol=1e-12
        )
        np.testing.assert_allclose(
            np.asarray(rmatvec(sf, jnp.asarray(a))), x.T @ a, rtol=1e-12
        )
        np.testing.assert_allclose(
            np.asarray(colsum(sf, jnp.asarray(a))),
            np.einsum("n,nd->d", a, x),
            rtol=1e-12,
        )
        np.testing.assert_allclose(
            np.asarray(colsum(sf, jnp.asarray(a), square=True)),
            np.einsum("n,nd->d", a, x * x),
            rtol=1e-12,
        )

    def test_padding_is_invisible(self, rng):
        # widen rows with explicit padding slots; results must not change
        sf = from_dense(rng.normal(size=(10, 6)), dtype=jnp.float64)
        wide = from_dense(to_dense(sf), nnz_per_row=6, dtype=jnp.float64)
        w = jnp.asarray(rng.normal(size=6))
        np.testing.assert_allclose(
            np.asarray(matvec(sf, w)), np.asarray(matvec(wide, w)), rtol=1e-12
        )

    def test_nnz_cap_rejects_denser_rows(self, rng):
        x = np.zeros((2, 5))
        x[0, :4] = 1.0
        with pytest.raises(ValueError, match="nnz_per_row"):
            from_dense(x, nnz_per_row=3)


class TestSparseObjective:
    def _batches(self, rng, n=128, d=40, nnz=6):
        sf = from_coo(*random_sparse(rng, n, d, nnz), n, d, dtype=jnp.float64)
        x = to_dense(sf)
        w_true = rng.normal(size=d)
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-x @ w_true))).astype(float)
        dense = LabeledBatch.create(x, y, dtype=jnp.float64)
        sparse = LabeledBatch.create(sf, y, dtype=jnp.float64)
        return dense, sparse, w_true

    def test_objective_value_grad_hvp_match_dense(self, rng):
        dense, sparse, _ = self._batches(rng)
        obj = GLMObjective(loss=LOGISTIC_LOSS, l2_weight=0.3)
        w = jnp.asarray(rng.normal(size=dense.num_features))
        v = jnp.asarray(rng.normal(size=dense.num_features))
        vd, gd = obj.value_and_grad(w, dense)
        vs, gs = jax.jit(obj.value_and_grad)(w, sparse)
        np.testing.assert_allclose(float(vs), float(vd), rtol=1e-12)
        np.testing.assert_allclose(np.asarray(gs), np.asarray(gd), rtol=1e-10)
        np.testing.assert_allclose(
            np.asarray(obj.hessian_vector(w, v, sparse)),
            np.asarray(obj.hessian_vector(w, v, dense)),
            rtol=1e-10,
        )
        np.testing.assert_allclose(
            np.asarray(obj.hessian_diagonal(w, sparse)),
            np.asarray(obj.hessian_diagonal(w, dense)),
            rtol=1e-10,
        )

    def test_training_matches_dense_oracle(self, rng):
        from photon_ml_tpu.models import (
            GLMTrainingConfig,
            OptimizerType,
            TaskType,
            train_glm,
        )
        from photon_ml_tpu.ops import RegularizationContext

        dense, sparse, _ = self._batches(rng, n=300, d=30, nnz=5)
        cfg = GLMTrainingConfig(
            task=TaskType.LOGISTIC_REGRESSION,
            optimizer=OptimizerType.TRON,
            regularization=RegularizationContext("L2"),
            reg_weights=(0.5,),
            tolerance=1e-12,
            max_iters=100,
        )
        (md,) = train_glm(dense, cfg)
        (ms,) = train_glm(sparse, cfg)
        np.testing.assert_allclose(
            np.asarray(ms.model.coefficients.means),
            np.asarray(md.model.coefficients.means),
            atol=1e-8,
        )

    def test_wide_features_100k(self, rng):
        """d = 120k: train sparse, compare against the dense oracle solved on
        the support columns only (the full dense matrix would be 120k wide)."""
        from photon_ml_tpu.models import (
            GLMTrainingConfig,
            OptimizerType,
            TaskType,
            train_glm,
        )
        from photon_ml_tpu.ops import RegularizationContext

        n, d, nnz = 512, 120_000, 4
        support = rng.choice(d, size=24, replace=False)  # active columns
        rows = np.repeat(np.arange(n), nnz)
        cols = support[rng.integers(0, support.size, size=n * nnz)]
        vals = rng.normal(size=n * nnz)
        sf = from_coo(rows, cols, vals, n, d, dtype=jnp.float64)
        w_true = np.zeros(d)
        w_true[support] = rng.normal(size=support.size)
        margins = np.zeros(n)
        np.add.at(margins, rows, vals * w_true[cols])
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margins))).astype(float)

        cfg = GLMTrainingConfig(
            task=TaskType.LOGISTIC_REGRESSION,
            optimizer=OptimizerType.TRON,
            regularization=RegularizationContext("L2"),
            reg_weights=(1.0,),
            tolerance=1e-10,
            max_iters=60,
        )
        (ms,) = train_glm(LabeledBatch.create(sf, y, dtype=jnp.float64), cfg)
        w_sparse = np.asarray(ms.model.coefficients.means)
        assert w_sparse.shape == (d,)

        # dense oracle on the support: same rows, support columns compacted
        col_map = {c: i for i, c in enumerate(sorted(support))}
        x_small = np.zeros((n, support.size))
        np.add.at(x_small, (rows, [col_map[c] for c in cols]), vals)
        (mo,) = train_glm(LabeledBatch.create(x_small, y, dtype=jnp.float64), cfg)
        w_oracle = np.asarray(mo.model.coefficients.means)
        np.testing.assert_allclose(
            w_sparse[sorted(support)], w_oracle, atol=1e-7
        )
        # off-support coefficients must be exactly zero (no data, L2 pull)
        off = np.setdiff1d(np.arange(d), support)
        assert np.abs(w_sparse[off]).max() < 1e-10

    def test_sparse_batch_shards_over_mesh(self, rng, devices):
        from photon_ml_tpu.parallel import make_mesh, shard_batch

        dense, sparse, _ = self._batches(rng, n=253, d=20, nnz=4)
        obj = GLMObjective(loss=LOGISTIC_LOSS, l2_weight=0.2)
        w = jnp.asarray(rng.normal(size=20))
        v_local, g_local = obj.value_and_grad(w, sparse)
        mesh = make_mesh()
        sharded = shard_batch(sparse, mesh)
        assert sharded.batch_size == 256  # padded to 8 devices
        with jax.set_mesh(mesh):
            v_dist, g_dist = jax.jit(obj.value_and_grad)(w, sharded)
        np.testing.assert_allclose(float(v_dist), float(v_local), rtol=1e-12)
        np.testing.assert_allclose(
            np.asarray(g_dist), np.asarray(g_local), rtol=1e-10
        )


class TestSparseStatsAndValidation:
    def test_summarize_features_matches_dense(self, rng):
        from photon_ml_tpu.ops.stats import summarize_features

        n, d, nnz = 60, 25, 4
        sf = from_coo(*random_sparse(rng, n, d, nnz), n, d, dtype=jnp.float64)
        x = to_dense(sf)
        mask = (rng.uniform(size=n) < 0.8).astype(float)
        sb = LabeledBatch.create(sf, np.zeros(n), mask=mask, dtype=jnp.float64)
        db = LabeledBatch.create(x, np.zeros(n), mask=mask, dtype=jnp.float64)
        ss = summarize_features(sb)
        ds = summarize_features(db)
        for f in ("mean", "variance", "count", "min", "max", "norm_l1",
                  "norm_l2", "mean_abs", "num_nonzeros"):
            np.testing.assert_allclose(
                np.asarray(getattr(ss, f)),
                np.asarray(getattr(ds, f)),
                rtol=1e-10, atol=1e-12, err_msg=f,
            )

    def test_standardized_training_on_sparse(self, rng):
        """Normalization != NONE must work end-to-end on sparse batches
        (summary -> whitening folded into the kernels, never densified)."""
        from photon_ml_tpu.core.normalization import NormalizationType
        from photon_ml_tpu.models import (
            GLMTrainingConfig,
            TaskType,
            train_glm,
        )
        from photon_ml_tpu.ops import RegularizationContext

        n, d, nnz = 256, 40, 6
        rows, cols, vals = random_sparse(rng, n, d, nnz)
        # intercept column d (standardization requires one)
        rows = np.concatenate([rows, np.arange(n)])
        cols = np.concatenate([cols, np.full(n, d)])
        vals = np.concatenate([vals, np.ones(n)])
        sf = from_coo(rows, cols, vals, n, d + 1, dtype=jnp.float64)
        x = to_dense(sf)
        w_true = rng.normal(size=d + 1)
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-x @ w_true))).astype(float)
        cfg = GLMTrainingConfig(
            task=TaskType.LOGISTIC_REGRESSION,
            regularization=RegularizationContext("L2"),
            reg_weights=(0.1,),
            normalization=NormalizationType.STANDARDIZATION,
            intercept_index=d,
            tolerance=1e-11,
            max_iters=200,
        )
        (ms,) = train_glm(LabeledBatch.create(sf, y, dtype=jnp.float64), cfg)
        (md,) = train_glm(LabeledBatch.create(x, y, dtype=jnp.float64), cfg)
        np.testing.assert_allclose(
            np.asarray(ms.model.coefficients.means),
            np.asarray(md.model.coefficients.means),
            atol=1e-7,
        )

    def test_validators_catch_sparse_nonfinite(self, rng):
        from photon_ml_tpu.core.tasks import TaskType
        from photon_ml_tpu.core.validators import sanity_check_data

        sf = from_dense(rng.normal(size=(20, 5)), dtype=jnp.float64)
        y = (rng.uniform(size=20) < 0.5).astype(float)
        ok = LabeledBatch.create(sf, y, dtype=jnp.float64)
        sanity_check_data(ok, TaskType.LOGISTIC_REGRESSION)

        import dataclasses

        bad_vals = np.asarray(sf.values).copy()
        bad_vals[3, 0] = np.nan
        bad = LabeledBatch.create(
            dataclasses.replace(sf, values=jnp.asarray(bad_vals)),
            y,
            dtype=jnp.float64,
        )
        with pytest.raises(ValueError, match="finite_features"):
            sanity_check_data(bad, TaskType.LOGISTIC_REGRESSION)

    def test_pad_to_keeps_padding_invariant(self, rng):
        from photon_ml_tpu.ops.sparse import row_density

        sf = from_dense(rng.normal(size=(10, 6)), dtype=jnp.float64)
        b = LabeledBatch.create(sf, np.zeros(10), dtype=jnp.float64)
        padded = LabeledBatch.pad_to(b, 16)
        dens = np.asarray(row_density(padded.features))
        assert np.all(dens[10:] == 0)  # padding rows store nothing
        np.testing.assert_array_equal(
            to_dense(padded.features)[:10], to_dense(sf)
        )


class TestSparseIngest:
    def test_sparse_ingest_matches_dense(self, rng):
        from photon_ml_tpu.io.ingest import (
            labeled_batch_from_avro,
            training_examples_to_arrays,
        )
        from photon_ml_tpu.io.vocab import FeatureVocabulary

        records = []
        names = [f"f{i}" for i in range(12)]
        for i in range(30):
            feats = [
                {"name": names[j], "term": "", "value": float(rng.normal())}
                for j in rng.choice(12, size=5, replace=False)
            ]
            # a duplicate entry to exercise dedup-by-sum
            feats.append(dict(feats[0]))
            records.append(
                {"label": float(i % 2), "features": feats, "offset": 0.1 * i,
                 "weight": 1.0 + 0.01 * i, "uid": str(i)}
            )
        vocab = FeatureVocabulary.from_records(records, add_intercept=True)
        dense = labeled_batch_from_avro(records, vocab, dtype=jnp.float64)
        sparse = labeled_batch_from_avro(
            records, vocab, dtype=jnp.float64, sparse=True
        )
        np.testing.assert_allclose(
            to_dense(sparse.features), np.asarray(dense.features), rtol=1e-12
        )
        np.testing.assert_allclose(
            np.asarray(sparse.offsets), np.asarray(dense.offsets), rtol=1e-12
        )
        np.testing.assert_allclose(
            np.asarray(sparse.weights), np.asarray(dense.weights), rtol=1e-12
        )
