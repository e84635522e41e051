"""Sparse (wide) feature support: the padded-ELL kernels must agree exactly
with their dense counterparts, training on sparse batches must match the
dense oracle on the support, and the d >= 100k regime must work without ever
materializing an (n, d) matrix (the reference's PalDB >200k-feature regime,
``util/PalDBIndexMap.scala:43``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.core.normalization import NormalizationContext
from photon_ml_tpu.core.types import LabeledBatch
from photon_ml_tpu.ops.losses import LOGISTIC_LOSS
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.ops.sparse import (
    SparseFeatures,
    from_coo,
    from_dense,
    matvec,
    matvec_and_feature_dots,
    rmatvec,
    colsum,
    shard_columns,
    to_dense,
)


def random_sparse(rng, n, d, nnz):
    rows = np.repeat(np.arange(n), nnz)
    cols = rng.integers(0, d, size=n * nnz)
    vals = rng.normal(size=n * nnz)
    return rows, cols, vals


def random_ell(rng, n, k, d, dtype=np.float32, pad_rows=0, dup_row=False):
    """Random ELL with the padding invariant (padding slots: id=d,
    value=0). ``pad_rows`` leading rows are ALL padding; ``dup_row``
    plants duplicate column ids inside row 0's slots."""
    idx = rng.integers(0, max(d, 1), size=(n, k)).astype(np.int32)
    val = rng.standard_normal((n, k)).astype(dtype)
    if dup_row and n > 0 and k >= 2:
        idx[0, :] = idx[0, 0]  # every slot of row 0 hits one column
    if pad_rows:
        idx[:pad_rows, :] = d
        val[:pad_rows, :] = 0
    return SparseFeatures(
        indices=jnp.asarray(idx), values=jnp.asarray(val), d=d
    )


def _dense_of_slots(sf, square=False):
    """(n, d) float64 matrix of the stored slots, padding dropped. A pair
    stored twice sums; with ``square`` each SLOT is squared first, which
    is what ``colsum(square=True)`` sums."""
    v = sf.values.astype(jnp.float64)
    return to_dense(SparseFeatures(sf.indices, v * v if square else v, sf.d))


def _ell_case(id, n, k, d, pad=0, dup=False, values="float64",
              vectors="float64", build="ell"):
    """``pad`` leading rows of padding only; ``dup``: row 0 holds one
    column in every slot; dtypes of the stored values and of the vectors."""
    return pytest.param(build, n, k, d, pad, dup, values, vectors, id=id)


ELL_CASES = [
    _ell_case("coo", 64, 7, 50, build="coo"),
    # d = 300 / 157 are no multiple of the 128-lane tile
    _ell_case("ragged-d", 37, 5, 300),
    _ell_case("leading-padding-rows", 37, 5, 300, pad=7),
    _ell_case("every-row-padding", 16, 4, 300, pad=16),
    _ell_case("one-slot-a-row", 23, 1, 157),
    _ell_case("duplicate-columns-in-a-row", 12, 6, 157, dup=True),
    _ell_case("d-1", 9, 3, 1),
    _ell_case("d-one-lane-tile", 40, 8, 128),
    _ell_case("bf16-values-f32-vectors", 33, 4, 270, values="bfloat16",
              vectors="float32"),
    _ell_case("bf16-values-bf16-vectors", 33, 4, 270, values="bfloat16",
              vectors="bfloat16"),
    _ell_case("empty-batch", 0, 4, 90, values="float32", vectors="float32"),
]


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

    @pytest.mark.parametrize(
        "build,n,k,d,pad,dup,values_dtype,vector_dtype", ELL_CASES
    )
    def test_matvec_rmatvec_colsum_match_dense(
        self, rng, build, n, k, d, pad, dup, values_dtype, vector_dtype
    ):
        """The three contractions (and the squared column sums) of a
        padded ELL against float64 numpy products of its stored slots."""
        if build == "coo":
            sf = from_coo(
                *random_sparse(rng, n, d, k), n, d, dtype=jnp.float64
            )
        else:
            sf = random_ell(
                rng, n, k, d, dtype=jnp.dtype(values_dtype), pad_rows=pad,
                dup_row=dup,
            )
        x, x2 = _dense_of_slots(sf), _dense_of_slots(sf, square=True)
        vdt = jnp.dtype(vector_dtype)
        w = jnp.asarray(rng.normal(size=d), dtype=vdt)
        a = jnp.asarray(rng.normal(size=n), dtype=vdt)
        c = jnp.asarray(rng.uniform(0.1, 1.0, size=n), dtype=vdt)
        w64, a64, c64 = (np.asarray(v.astype(jnp.float64)) for v in (w, a, c))
        # float64 agrees to rounding; a bfloat16 design is held to what
        # its 8 bits of mantissa can give
        tol = 1e-12 if values_dtype == "float64" else (
            1e-2 if values_dtype == "bfloat16" else 1e-6
        )
        for got, want in (
            (matvec(sf, w), x @ w64),
            (rmatvec(sf, a), x.T @ a64),
            (colsum(sf, c), x.T @ c64),
            (colsum(sf, c, square=True), x2.T @ c64),
        ):
            assert got.shape == want.shape
            assert got.dtype == jnp.result_type(sf.values.dtype, vdt)
            scale = max(1.0, float(np.max(np.abs(want), initial=0.0)))
            np.testing.assert_allclose(
                np.asarray(got.astype(jnp.float64)), want,
                rtol=tol,
                atol=tol * scale,
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


_ALL_PASSES = ("value_grad_curvature", "hessian_vector", "hessian_diagonal")


class TestSparseObjective:
    def _batches(self, rng, n=128, d=40, nnz=6):
        sf = from_coo(*random_sparse(rng, n, d, nnz), n, d, dtype=jnp.float64)
        x = to_dense(sf)
        w_true = rng.normal(size=d)
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-x @ w_true))).astype(float)
        dense = LabeledBatch.create(x, y, dtype=jnp.float64)
        sparse = LabeledBatch.create(sf, y, dtype=jnp.float64)
        return dense, sparse, w_true

    @pytest.mark.parametrize(
        "passes,with_norm,weighted",
        [pytest.param(_ALL_PASSES, False, False, id="all-plain")]
        + [
            pytest.param((name,), norm, True, id=f"{name}-{tag}")
            for name in _ALL_PASSES
            for norm, tag in ((False, "weighted"), (True, "shift-and-scale"))
        ],
    )
    def test_objective_value_grad_hvp_match_dense(
        self, rng, passes, with_norm, weighted
    ):
        """Every pass of the objective on a padded ELL against the same
        pass on a dense matrix. Under a shift-and-scale normalization the
        dense side holds the WHITENED matrix (x - shifts) * factors and no
        context, so the normalization algebra of ``_backproject`` and of
        the Hessian diagonal is held to the matrix it stands for."""
        n, d, nnz = 128, 40, 6
        rows, cols, vals = random_sparse(rng, n, d, nnz)
        if weighted:  # three leading rows of padding only
            rows, cols, vals = rows[3 * nnz:], cols[3 * nnz:], vals[3 * nnz:]
        sf = from_coo(rows, cols, vals, n, d, dtype=jnp.float64)
        x = to_dense(sf)
        y = (rng.uniform(size=n) < 0.5).astype(float)
        extras = {}
        if weighted:
            extras = dict(
                offsets=rng.normal(size=n) * 0.1,
                weights=rng.uniform(0.5, 2.0, size=n),
            )
        norm = None
        if with_norm:
            factors = rng.uniform(0.5, 2.0, size=d)
            shifts = rng.normal(size=d) * 0.05
            norm = NormalizationContext(
                factors=jnp.asarray(factors), shifts=jnp.asarray(shifts)
            )
            x = (x - shifts) * factors
        sparse = LabeledBatch.create(sf, y, dtype=jnp.float64, **extras)
        dense = LabeledBatch.create(x, y, dtype=jnp.float64, **extras)
        obj_d = GLMObjective(loss=LOGISTIC_LOSS, l2_weight=0.3)
        obj_s = (
            dataclasses.replace(obj_d, normalization=norm) if norm else obj_d
        )
        w = jnp.asarray(rng.normal(size=d))
        v = jnp.asarray(rng.normal(size=d))
        if "value_grad_curvature" in passes:
            vd, gd, cd = obj_d.value_grad_curvature(w, dense)
            vs, gs, cs = jax.jit(obj_s.value_grad_curvature)(w, sparse)
            np.testing.assert_allclose(float(vs), float(vd), rtol=1e-12)
            np.testing.assert_allclose(
                np.asarray(gs), np.asarray(gd), rtol=1e-10, atol=1e-12
            )
            np.testing.assert_allclose(
                np.asarray(cs), np.asarray(cd), rtol=1e-10, atol=1e-14
            )
        if "hessian_vector" in passes:
            np.testing.assert_allclose(
                np.asarray(obj_s.hessian_vector(w, v, sparse)),
                np.asarray(obj_d.hessian_vector(w, v, dense)),
                rtol=1e-10, atol=1e-12,
            )
        if "hessian_diagonal" in passes:
            np.testing.assert_allclose(
                np.asarray(obj_s.hessian_diagonal(w, sparse)),
                np.asarray(obj_d.hessian_diagonal(w, dense)),
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


class TestFeatureShardedBucketedReduction:
    def test_unsharded_is_bit_identical(self, rng):
        sf = random_ell(rng, 21, 4, 97)
        w = jnp.asarray(rng.standard_normal(97).astype(np.float32))
        u = jnp.asarray(rng.standard_normal(97).astype(np.float32))
        z, (du, dw) = matvec_and_feature_dots(sf, w, ((u, w), (w, w)))
        np.testing.assert_array_equal(
            np.asarray(z), np.asarray(matvec(sf, w))
        )
        np.testing.assert_array_equal(
            np.asarray(du), np.asarray(jnp.vdot(u, w))
        )
        np.testing.assert_array_equal(
            np.asarray(dw), np.asarray(jnp.vdot(w, w))
        )

    def test_blocked_container_matches_unfused(self, rng):
        n, k, d = 30, 4, 96
        sf = random_ell(rng, n, k, d)
        blocked = shard_columns(sf, 2)
        d_block = 2 * blocked.d_shard
        w = jnp.asarray(rng.standard_normal(d_block).astype(np.float32))
        u = jnp.asarray(rng.standard_normal(d_block).astype(np.float32))
        z, (du,) = matvec_and_feature_dots(blocked, w, ((u, w),))
        np.testing.assert_allclose(
            np.asarray(z), np.asarray(matvec(blocked, w)),
            rtol=1e-6, atol=1e-6,
        )
        np.testing.assert_allclose(
            float(du), float(jnp.vdot(u, w)), rtol=1e-6
        )

    def test_coalesced_pass_reduces_one_payload(self, rng, devices):
        # What the CODE controls: with fuse_feature_reductions the pass
        # builds ONE (n + P,) feature-space reduction (margins + every
        # scalar dot) where the unfused pass builds 1 + P. How many
        # all-reduce INSTRUCTIONS that becomes is the compiler's call:
        # on jax 0.9.0's XLA the all-reduce combiner merges the unfused
        # pass's reductions too and both compile to the same count, so
        # asserting fused < unfused there tested the compiler, not this
        # code. Asserted here: the traced payload geometry, that fusing
        # never ADDS a collective, and numerical equality.
        from jax.sharding import NamedSharding, PartitionSpec as P

        from photon_ml_tpu.obs.xla_cost import count_collectives
        from photon_ml_tpu.ops.sparse import FeatureShardedSparse
        from photon_ml_tpu.parallel import make_feature_mesh
        from photon_ml_tpu.parallel.mesh import DATA_AXIS, FEATURE_AXIS

        n, k, d = 64, 4, 256
        sf = random_ell(rng, n, k, d)
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        batch = LabeledBatch.create(sf, y)
        mesh = make_feature_mesh(1, 2)
        blocked = shard_columns(batch.features, 2)
        spec = NamedSharding(mesh, P(DATA_AXIS, FEATURE_AXIS, None))
        placed = FeatureShardedSparse(
            indices=jax.device_put(blocked.indices, spec),
            values=jax.device_put(blocked.values, spec),
            d_shard=blocked.d_shard,
            d_orig=blocked.d_orig,
        )
        pb = dataclasses.replace(batch, features=placed)
        d_block = 2 * blocked.d_shard
        w0 = jax.device_put(
            jnp.zeros((d_block,), jnp.float32),
            NamedSharding(mesh, P(FEATURE_AXIS)),
        )

        from photon_ml_tpu import obs

        def traced_payloads():
            snap = obs.registry().snapshot()["counters"]
            key = "collective.traced.matvec_and_feature_dots.w2"
            return (
                snap.get(f"{key}.count", 0), snap.get(f"{key}.bytes", 0)
            )

        def compile_pass(fuse):
            obj = GLMObjective(
                loss=LOGISTIC_LOSS,
                l2_weight=1.0,
                fuse_feature_reductions=fuse,
            )
            before = traced_payloads()
            with jax.set_mesh(mesh):
                comp = (
                    jax.jit(lambda w, b: obj.value_and_grad(w, b))
                    .lower(w0, pb)
                    .compile()
                )
            after = traced_payloads()
            return comp, (after[0] - before[0], after[1] - before[1])

        fused_c, fused_note = compile_pass(True)
        unfused_c, unfused_note = compile_pass(False)
        # one coalesced reduction of n margins + the one L2 dot (f32)
        assert fused_note == (1, (n + 1) * 4)
        assert unfused_note == (0, 0)
        n_fused = sum(count_collectives(fused_c.as_text()).values())
        n_unfused = sum(count_collectives(unfused_c.as_text()).values())
        assert 1 <= n_fused <= n_unfused, (n_fused, n_unfused)
        # numerically identical up to reduction order
        vf, gf = fused_c(w0, pb)
        vu, gu = unfused_c(w0, pb)
        np.testing.assert_allclose(float(vf), float(vu), rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gu), rtol=1e-6, atol=1e-6
        )
