"""Multi-device tests on the 8-device virtual CPU mesh (the reference's
local-mode-Spark analog, SURVEY §4): distributed solve == local solve, and
the explicit shard_map path == the GSPMD path == the numpy oracle (the
RDD-vs-Iterable duality contract, ``ObjectiveFunctionIntegTest``)."""

import jax
import pytest
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.core.types import LabeledBatch
from photon_ml_tpu.models import GLMTrainingConfig, TaskType, train_glm
from photon_ml_tpu.ops import RegularizationContext
from photon_ml_tpu.ops.losses import LOGISTIC_LOSS
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.parallel import (
    distributed_train_glm,
    make_mesh,
    shard_batch,
    shard_map_value_and_grad,
)


def make_data(rng, n=400, d=10):
    x = rng.normal(size=(n, d))
    w = rng.normal(size=d)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-x @ w))).astype(float)
    return x, y


class TestShardedObjective:
    def test_shard_map_equals_local(self, rng, devices):
        x, y = make_data(rng)
        batch = LabeledBatch.create(x, y, dtype=jnp.float64)
        obj = GLMObjective(loss=LOGISTIC_LOSS, l2_weight=0.5)
        w = jnp.asarray(rng.normal(size=10))

        v_local, g_local = obj.value_and_grad(w, batch)

        mesh = make_mesh()
        sharded = shard_batch(batch, mesh)
        vg = shard_map_value_and_grad(obj, mesh)
        v_dist, g_dist = jax.jit(vg)(w, sharded)

        np.testing.assert_allclose(float(v_dist), float(v_local), rtol=1e-12)
        np.testing.assert_allclose(
            np.asarray(g_dist), np.asarray(g_local), rtol=1e-10
        )

    def test_gspmd_jit_equals_local(self, rng, devices):
        x, y = make_data(rng, n=397)  # deliberately not divisible by 8
        batch = LabeledBatch.create(x, y, dtype=jnp.float64)
        obj = GLMObjective(loss=LOGISTIC_LOSS, l2_weight=0.5)
        w = jnp.asarray(rng.normal(size=10))
        v_local, g_local = obj.value_and_grad(w, batch)

        mesh = make_mesh()
        sharded = shard_batch(batch, mesh)
        assert sharded.batch_size == 400  # padded to multiple of 8
        v_dist, g_dist = jax.jit(
            lambda w, b: obj.value_and_grad(w, b)
        )(w, sharded)
        np.testing.assert_allclose(float(v_dist), float(v_local), rtol=1e-12)
        np.testing.assert_allclose(
            np.asarray(g_dist), np.asarray(g_local), rtol=1e-10
        )


class TestDistributedTraining:
    def test_distributed_equals_local_solve(self, rng, devices):
        x, y = make_data(rng, n=500, d=8)
        batch = LabeledBatch.create(x, y, dtype=jnp.float64)
        cfg = GLMTrainingConfig(
            task=TaskType.LOGISTIC_REGRESSION,
            regularization=RegularizationContext("L2"),
            reg_weights=(1.0,),
            tolerance=1e-12,
            max_iters=100,
        )
        (local,) = train_glm(batch, cfg)
        mesh = make_mesh()
        (dist,) = distributed_train_glm(batch, cfg, mesh)
        np.testing.assert_allclose(
            np.asarray(dist.model.coefficients.means),
            np.asarray(local.model.coefficients.means),
            atol=1e-8,
        )

    def test_distributed_tron(self, rng, devices):
        from photon_ml_tpu.models import OptimizerType

        x, y = make_data(rng, n=512, d=6)
        batch = LabeledBatch.create(x, y, dtype=jnp.float64)
        cfg = GLMTrainingConfig(
            task=TaskType.LOGISTIC_REGRESSION,
            optimizer=OptimizerType.TRON,
            regularization=RegularizationContext("L2"),
            reg_weights=(0.5,),
            tolerance=1e-10,
            max_iters=50,
        )
        (local,) = train_glm(batch, cfg)
        (dist,) = distributed_train_glm(batch, cfg, make_mesh())
        np.testing.assert_allclose(
            np.asarray(dist.model.coefficients.means),
            np.asarray(local.model.coefficients.means),
            atol=1e-8,
        )


class TestEntityShardedGame:
    """Distributed GAME (fixed + bucketed random effect, entity-sharded over
    the mesh) must match the local run to tolerance — the driver-level
    contract the round-1 dryrun never exercised."""

    def _build_cd(self, data, n_users, design, mesh=None):
        from photon_ml_tpu.core.tasks import TaskType as TT
        from photon_ml_tpu.game import (
            CoordinateConfig,
            CoordinateDescent,
            FixedEffectCoordinate,
            RandomEffectCoordinate,
        )
        from photon_ml_tpu.parallel import shard_batch as _shard

        fe_cfg = CoordinateConfig(
            shard="global", reg_weight=0.1, max_iters=25, tolerance=1e-10
        )
        re_cfg = CoordinateConfig(
            shard="per_user",
            random_effect="userId",
            reg_weight=0.5,
            max_iters=25,
            tolerance=1e-10,
        )
        fe_batch = data.fixed_effect_batch("global", jnp.float64)
        row_feats = jnp.asarray(data.features["per_user"], jnp.float64)
        row_ents = jnp.asarray(data.entity_ids["userId"])
        offsets = jnp.asarray(data.offsets, jnp.float64)
        if mesh is not None:
            fe_batch = _shard(fe_batch, mesh)
        fixed = FixedEffectCoordinate(fe_batch, fe_cfg)
        random = RandomEffectCoordinate(
            design=design,
            row_features=row_feats,
            row_entities=row_ents,
            full_offsets_base=offsets,
            config=re_cfg,
        )
        return CoordinateDescent(
            coordinates={"fixed": fixed, "per-user": random},
            labels=jnp.asarray(data.labels, jnp.float64),
            base_offsets=offsets,
            weights=jnp.asarray(data.weights, jnp.float64),
            task=TT.LOGISTIC_REGRESSION,
        )

    def test_sharded_bucketed_game_equals_local(self, rng, devices):
        from test_game import make_mixed_effects_data

        from photon_ml_tpu.game import build_bucketed_random_effect_design
        from photon_ml_tpu.parallel import (
            make_game_mesh,
            shard_bucketed_design,
        )

        data, user, n_users = make_mixed_effects_data(
            rng, n_users=16, rows_per_user=12
        )
        local_design = build_bucketed_random_effect_design(
            data, "userId", "per_user", n_users, num_buckets=2,
            dtype=jnp.float64,
        )
        cd_local = self._build_cd(data, n_users, local_design)
        m_local, h_local = cd_local.run(num_iterations=2)

        mesh = make_game_mesh(4, 2)
        sharded_design = build_bucketed_random_effect_design(
            data, "userId", "per_user", n_users, num_buckets=2,
            entity_multiple=mesh.shape["entity"], dtype=jnp.float64,
        )
        sharded_design = shard_bucketed_design(sharded_design, mesh)
        cd_dist = self._build_cd(data, n_users, sharded_design, mesh=mesh)
        m_dist, h_dist = cd_dist.run(num_iterations=2)

        np.testing.assert_allclose(
            np.asarray(m_dist.params["fixed"]),
            np.asarray(m_local.params["fixed"]),
            atol=1e-8,
        )
        np.testing.assert_allclose(
            np.asarray(m_dist.params["per-user"]),
            np.asarray(m_local.params["per-user"]),
            atol=1e-8,
        )
        assert h_dist[-1].objective <= h_dist[0].objective


class TestFeatureSharding:
    """SURVEY §5.7: the coefficient axis itself shards over the mesh — the
    huge-d regime where replicating w per device is the memory ceiling."""

    def _data(self, rng, n=512, d=60):
        x = rng.normal(size=(n, d))
        w = rng.normal(size=d) * (rng.uniform(size=d) < 0.4)
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-x @ w))).astype(float)
        return LabeledBatch.create(x, y, dtype=jnp.float64)

    @pytest.mark.parametrize("optimizer", ["TRON", "LBFGS"])
    def test_matches_local_solve(self, rng, devices, optimizer):
        from photon_ml_tpu.models.training import OptimizerType
        from photon_ml_tpu.parallel import (
            feature_sharded_train_glm,
            make_feature_mesh,
        )

        batch = self._data(rng)
        cfg = GLMTrainingConfig(
            task=TaskType.LOGISTIC_REGRESSION,
            optimizer=OptimizerType[optimizer],
            regularization=RegularizationContext("L2"),
            reg_weights=(1.0,),
            max_iters=60,
            tolerance=1e-12,
            track_states=False,
        )
        mesh = make_feature_mesh(2, 4)
        (dist,) = feature_sharded_train_glm(batch, cfg, mesh)
        (local,) = train_glm(batch, cfg)
        np.testing.assert_allclose(
            np.asarray(dist.model.coefficients.means),
            np.asarray(local.model.coefficients.means),
            atol=1e-8,
        )
        # coefficients really were computed feature-sharded: d=60 pads to 64
        assert dist.model.coefficients.means.shape == (60,)

    def test_uneven_d_pads_and_strips(self, rng, devices):
        from photon_ml_tpu.models.training import OptimizerType
        from photon_ml_tpu.parallel import (
            feature_sharded_train_glm,
            make_feature_mesh,
        )

        batch = self._data(rng, n=300, d=13)  # 13 % 4 != 0
        cfg = GLMTrainingConfig(
            task=TaskType.LOGISTIC_REGRESSION,
            optimizer=OptimizerType.TRON,
            regularization=RegularizationContext("L2"),
            reg_weights=(1.0,),
            max_iters=40,
            tolerance=1e-10,
            track_states=False,
        )
        mesh = make_feature_mesh(2, 4)
        (dist,) = feature_sharded_train_glm(batch, cfg, mesh)
        (local,) = train_glm(batch, cfg)
        assert dist.model.coefficients.means.shape == (13,)
        np.testing.assert_allclose(
            np.asarray(dist.model.coefficients.means),
            np.asarray(local.model.coefficients.means),
            atol=1e-8,
        )

    def test_constraints_match_local(self, rng, devices):
        """Box constraints ride feature sharding: bound vectors are re-laid
        out into the blocked coefficient space (pad columns unconstrained),
        matching ``OptimizationUtils.projectCoefficientsToHypercube``."""
        from photon_ml_tpu.models.training import OptimizerType
        from photon_ml_tpu.parallel import (
            feature_sharded_train_glm,
            make_feature_mesh,
        )

        d = 13
        batch = self._data(rng, n=300, d=d)
        cfg = GLMTrainingConfig(
            optimizer=OptimizerType.LBFGS,
            regularization=RegularizationContext("L2"),
            reg_weights=(0.5,),
            lower_bounds=tuple([-0.2] * d),
            upper_bounds=tuple([0.2] * d),
            max_iters=60,
            tolerance=1e-12,
            track_states=False,
        )
        mesh = make_feature_mesh(2, 4)
        (dist,) = feature_sharded_train_glm(batch, cfg, mesh)
        (local,) = train_glm(batch, cfg)
        wd = np.asarray(dist.model.coefficients.means)
        assert np.all(wd >= -0.2 - 1e-12) and np.all(wd <= 0.2 + 1e-12)
        np.testing.assert_allclose(
            wd, np.asarray(local.model.coefficients.means), atol=1e-8
        )

    def test_standardization_matches_local(self, rng, devices):
        """Feature-sharded standardization == unsharded
        (``normalization/NormalizationContext.scala:41-151``): factors and
        shifts are computed in and applied to the blocked layout."""
        from photon_ml_tpu.core.normalization import NormalizationType
        from photon_ml_tpu.models.training import OptimizerType
        from photon_ml_tpu.parallel import (
            feature_sharded_train_glm,
            make_feature_mesh,
        )

        d = 21
        x = rng.normal(size=(400, d)) * rng.uniform(1, 9, size=d)
        x[:, -1] = 1.0  # intercept
        w = rng.normal(size=d)
        y = (rng.uniform(size=400) < 1 / (1 + np.exp(-x @ w * 0.1))).astype(
            float
        )
        batch = LabeledBatch.create(x, y, dtype=jnp.float64)
        cfg = GLMTrainingConfig(
            optimizer=OptimizerType.TRON,
            regularization=RegularizationContext("L2"),
            reg_weights=(1.0,),
            normalization=NormalizationType.STANDARDIZATION,
            intercept_index=d - 1,
            max_iters=60,
            tolerance=1e-12,
            track_states=False,
            compute_variances=True,
        )
        mesh = make_feature_mesh(2, 4)
        (dist,) = feature_sharded_train_glm(batch, cfg, mesh)
        (local,) = train_glm(batch, cfg)
        np.testing.assert_allclose(
            np.asarray(dist.model.coefficients.means),
            np.asarray(local.model.coefficients.means),
            atol=1e-8,
        )
        np.testing.assert_allclose(
            np.asarray(dist.model.coefficients.variances),
            np.asarray(local.model.coefficients.variances),
            rtol=1e-8,
        )


class TestFeatureShardedSparse:
    """The coefficient axis shards for SPARSE designs — the
    only honest path to the reference's huge-d claim (``README.md:58``,
    ``util/PalDBIndexMap.scala:43``). Entries are column-blocked
    (``ops.sparse.shard_columns``) so gradient/CG scatters hit each
    device's local coefficient block."""

    def _sparse_batch(self, rng, n, d, nnz, intercept=False, densify=True):
        from photon_ml_tpu.ops import sparse as sparse_ops

        rows = np.repeat(np.arange(n), nnz)
        cols = rng.integers(0, d - (2 if intercept else 1), size=n * nnz)
        vals = rng.normal(size=n * nnz)
        if intercept:
            rows = np.concatenate([rows, np.arange(n)])
            cols = np.concatenate([cols, np.full(n, d - 1)])
            vals = np.concatenate([vals, np.ones(n)])
        sf = sparse_ops.from_coo(rows, cols, vals, n, d, dtype=jnp.float64)
        w = rng.normal(size=d) * (rng.uniform(size=d) < 0.5)
        z = np.asarray(sparse_ops.matvec(sf, jnp.asarray(w))) * 0.5
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-z))).astype(float)
        # densify only for small oracle problems (wide tests pass densify
        # =False: a 2048 x 120k f64 throwaway would cost ~2 GB host RAM)
        dense = sparse_ops.to_dense(sf) if densify else None
        return sf, dense, y

    def test_kernels_match_ell(self, rng, devices):
        from photon_ml_tpu.ops import sparse as sparse_ops

        sf, _, _ = self._sparse_batch(rng, n=64, d=37, nnz=5)
        fs = sparse_ops.shard_columns(sf, 4)
        cmap = sparse_ops.blocked_column_map(37, 4)
        w = rng.normal(size=37)
        wb = np.zeros(fs.num_blocks * fs.d_shard)
        wb[cmap] = w
        np.testing.assert_allclose(
            np.asarray(sparse_ops.matvec(fs, jnp.asarray(wb))),
            np.asarray(sparse_ops.matvec(sf, jnp.asarray(w))),
            rtol=1e-12,
        )
        a = rng.normal(size=64)
        np.testing.assert_allclose(
            np.asarray(sparse_ops.rmatvec(fs, jnp.asarray(a)))[cmap],
            np.asarray(sparse_ops.rmatvec(sf, jnp.asarray(a))),
            rtol=1e-12,
        )
        np.testing.assert_allclose(
            np.asarray(sparse_ops.colsum(fs, jnp.asarray(a), square=True))[
                cmap
            ],
            np.asarray(sparse_ops.colsum(sf, jnp.asarray(a), square=True)),
            rtol=1e-12,
        )

    @pytest.mark.parametrize("optimizer", ["TRON", "LBFGS"])
    def test_sparse_matches_local_dense(self, rng, devices, optimizer):
        from photon_ml_tpu.models.training import OptimizerType
        from photon_ml_tpu.parallel import (
            feature_sharded_train_glm,
            make_feature_mesh,
        )

        sf, dense, y = self._sparse_batch(rng, n=500, d=83, nnz=6)
        cfg = GLMTrainingConfig(
            task=TaskType.LOGISTIC_REGRESSION,
            optimizer=OptimizerType[optimizer],
            regularization=RegularizationContext("L2"),
            reg_weights=(1.0,),
            max_iters=60,
            tolerance=1e-12,
            track_states=False,
        )
        mesh = make_feature_mesh(2, 4)
        (dist,) = feature_sharded_train_glm(
            LabeledBatch.create(sf, y, dtype=jnp.float64), cfg, mesh
        )
        (local,) = train_glm(
            LabeledBatch.create(dense, y, dtype=jnp.float64), cfg
        )
        np.testing.assert_allclose(
            np.asarray(dist.model.coefficients.means),
            np.asarray(local.model.coefficients.means),
            atol=1e-8,
        )

    def test_odd_row_count_pads(self, rng, devices):
        """n not divisible by the data axis: rows pad through the blocked
        container's pad_rows branch (all-padding slots, masked rows)."""
        from photon_ml_tpu.models.training import OptimizerType
        from photon_ml_tpu.parallel import (
            feature_sharded_train_glm,
            make_feature_mesh,
        )

        sf, dense, y = self._sparse_batch(rng, n=401, d=53, nnz=6)
        cfg = GLMTrainingConfig(
            optimizer=OptimizerType.TRON,
            regularization=RegularizationContext("L2"),
            reg_weights=(1.0,),
            max_iters=60,
            tolerance=1e-12,
            track_states=False,
        )
        mesh = make_feature_mesh(2, 4)
        (dist,) = feature_sharded_train_glm(
            LabeledBatch.create(sf, y, dtype=jnp.float64), cfg, mesh
        )
        (local,) = train_glm(
            LabeledBatch.create(dense, y, dtype=jnp.float64), cfg
        )
        np.testing.assert_allclose(
            np.asarray(dist.model.coefficients.means),
            np.asarray(local.model.coefficients.means),
            atol=1e-8,
        )

    def test_owlqn_l1_sparse(self, rng, devices):
        """OWL-QN under feature sharding: blocked pad columns have zero
        gradient and a positive l1 weight, so they stay exactly 0."""
        from photon_ml_tpu.models.training import OptimizerType
        from photon_ml_tpu.parallel import (
            feature_sharded_train_glm,
            make_feature_mesh,
        )

        sf, dense, y = self._sparse_batch(rng, n=400, d=45, nnz=5)
        cfg = GLMTrainingConfig(
            optimizer=OptimizerType.LBFGS,
            regularization=RegularizationContext("ELASTIC_NET", alpha=0.5),
            reg_weights=(0.3,),
            max_iters=80,
            tolerance=1e-12,
            track_states=False,
        )
        mesh = make_feature_mesh(2, 4)
        (dist,) = feature_sharded_train_glm(
            LabeledBatch.create(sf, y, dtype=jnp.float64), cfg, mesh
        )
        (local,) = train_glm(
            LabeledBatch.create(dense, y, dtype=jnp.float64), cfg
        )
        np.testing.assert_allclose(
            np.asarray(dist.model.coefficients.means),
            np.asarray(local.model.coefficients.means),
            atol=1e-7,
        )

    def test_sparse_standardization_matches_local(self, rng, devices):
        """Sparse + STANDARDIZATION under feature sharding: exercises the
        blocked statistics path (``feature_sharded_as_ell`` ->
        ``_summarize_sparse``) and the blocked shift/factor algebra."""
        from photon_ml_tpu.core.normalization import NormalizationType
        from photon_ml_tpu.models.training import OptimizerType
        from photon_ml_tpu.parallel import (
            feature_sharded_train_glm,
            make_feature_mesh,
        )

        d = 31
        sf, dense, y = self._sparse_batch(rng, n=400, d=d, intercept=True, nnz=5)
        cfg = GLMTrainingConfig(
            optimizer=OptimizerType.TRON,
            regularization=RegularizationContext("L2"),
            reg_weights=(1.0,),
            normalization=NormalizationType.STANDARDIZATION,
            intercept_index=d - 1,
            max_iters=60,
            tolerance=1e-12,
            track_states=False,
            compute_variances=True,
        )
        mesh = make_feature_mesh(2, 4)
        (dist,) = feature_sharded_train_glm(
            LabeledBatch.create(sf, y, dtype=jnp.float64), cfg, mesh
        )
        (local,) = train_glm(
            LabeledBatch.create(dense, y, dtype=jnp.float64), cfg
        )
        np.testing.assert_allclose(
            np.asarray(dist.model.coefficients.means),
            np.asarray(local.model.coefficients.means),
            atol=1e-8,
        )
        np.testing.assert_allclose(
            np.asarray(dist.model.coefficients.variances),
            np.asarray(local.model.coefficients.variances),
            rtol=1e-8,
        )

    def test_preblocked_rejected(self, rng, devices):
        from photon_ml_tpu.ops import sparse as sparse_ops
        from photon_ml_tpu.parallel import (
            feature_sharded_train_glm,
            make_feature_mesh,
        )

        sf, _, y = self._sparse_batch(rng, n=64, d=20, nnz=4)
        fs = sparse_ops.shard_columns(sf, 4)
        batch = LabeledBatch.create(fs, y)
        cfg = GLMTrainingConfig(reg_weights=(1.0,), track_states=False)
        with pytest.raises(ValueError, match="already column-blocked"):
            feature_sharded_train_glm(batch, cfg, make_feature_mesh(2, 4))

    def test_wide_120k_matches_local_ell(self, rng, devices):
        """The wide acceptance shape: d=120k sparse solve on the
        ('data', 'feature') mesh equals the single-shard ELL solve."""
        from photon_ml_tpu.models.training import OptimizerType
        from photon_ml_tpu.parallel import (
            feature_sharded_train_glm,
            make_feature_mesh,
        )

        sf, _, y = self._sparse_batch(
            rng, n=2048, d=120_000, nnz=8, densify=False
        )
        cfg = GLMTrainingConfig(
            optimizer=OptimizerType.TRON,
            regularization=RegularizationContext("L2"),
            reg_weights=(1.0,),
            max_iters=15,
            tolerance=1e-8,
            track_states=False,
        )
        mesh = make_feature_mesh(2, 4)
        batch = LabeledBatch.create(sf, y, dtype=jnp.float64)
        (dist,) = feature_sharded_train_glm(batch, cfg, mesh)
        (local,) = train_glm(batch, cfg)
        assert dist.model.coefficients.means.shape == (120_000,)
        np.testing.assert_allclose(
            np.asarray(dist.model.coefficients.means),
            np.asarray(local.model.coefficients.means),
            atol=1e-8,
        )

    def test_hybrid_rejected(self, rng, devices):
        from photon_ml_tpu.ops import sparse as sparse_ops
        from photon_ml_tpu.parallel import (
            feature_sharded_train_glm,
            make_feature_mesh,
        )

        sf, _, y = self._sparse_batch(rng, n=64, d=20, nnz=4)
        hf = sparse_ops.to_hybrid(sf, hot_columns=2)
        batch = LabeledBatch.create(hf, y[np.asarray(hf.row_perm)])
        cfg = GLMTrainingConfig(reg_weights=(1.0,), track_states=False)
        with pytest.raises(ValueError, match="hybrid"):
            feature_sharded_train_glm(batch, cfg, make_feature_mesh(2, 4))


_TWO_PROC_CHILD = r'''
import sys

proc_id = int(sys.argv[1])
port = sys.argv[2]
out_path = sys.argv[3]
f0, f1, vocab_path = sys.argv[4], sys.argv[5], sys.argv[6]

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 4)
jax.config.update("jax_enable_x64", True)

from photon_ml_tpu.parallel import (
    initialize_multihost,
    make_global_batch,
    make_mesh,
    process_local_paths,
)

joined = initialize_multihost(
    coordinator_address=f"localhost:{port}",
    num_processes=2,
    process_id=proc_id,
)
assert joined, "initialize_multihost must join"
assert jax.process_count() == 2
assert jax.device_count() == 8 and jax.local_device_count() == 4

import numpy as np

from photon_ml_tpu.io.ingest import IngestSource
from photon_ml_tpu.io.vocab import FeatureVocabulary
from photon_ml_tpu.models import GLMTrainingConfig, OptimizerType, TaskType
from photon_ml_tpu.models.training import train_glm
from photon_ml_tpu.ops.objective import RegularizationContext

mine = process_local_paths([f0, f1])
assert len(mine) == 1, mine
vocab = FeatureVocabulary.load(vocab_path)
local_batch, _, _ = IngestSource(mine).labeled_batch(
    vocab, dtype="float64"
)

mesh = make_mesh()  # all 8 devices, both hosts
global_batch = make_global_batch(local_batch, mesh)
assert global_batch.labels.shape[0] == 2 * local_batch.labels.shape[0]

cfg = GLMTrainingConfig(
    task=TaskType.LOGISTIC_REGRESSION,
    optimizer=OptimizerType.TRON,
    regularization=RegularizationContext("L2"),
    reg_weights=(1.0,),
    max_iters=40,
    tolerance=1e-12,
    track_states=False,
)
with jax.set_mesh(mesh):
    (tm,) = train_glm(global_batch, cfg)
w = np.asarray(tm.model.coefficients.means)
np.save(out_path, w)

# SPARSE leg: the same split ingested as padded-ELL; make_global_batch
# maps over pytree leaves, so the (n, k) indices/values row-shard the
# same way the dense design did. nnz_per_row PINS the ELL width: each
# process's local decode must produce the same static shapes.
local_sp, _, _ = IngestSource(mine).labeled_batch(
    vocab, dtype="float64", sparse=True, nnz_per_row=12
)
global_sp = make_global_batch(local_sp, mesh)
with jax.set_mesh(mesh):
    (tm_sp,) = train_glm(global_sp, cfg)
np.save(out_path.replace(".npy", "_sparse.npy"),
        np.asarray(tm_sp.model.coefficients.means))
print("child", proc_id, "ok", w.shape)
'''


class TestTwoProcessDistributed:
    """An ACTUAL two-process jax.distributed run (the
    analog of the reference's local-mode-Spark fake cluster,
    ``SparkTestUtils.scala:31-75``): 2 CPU processes x 4 virtual devices
    join one 8-device mesh, each ingests ITS file split, the global
    batch assembles via make_array_from_process_local_data, and the
    distributed solve equals the single-process read of both files."""

    def test_two_process_solve_matches_single(self, rng, tmp_path):
        import socket
        import subprocess
        import sys as _sys

        from photon_ml_tpu.io.avro import write_avro_file
        from photon_ml_tpu.io.ingest import (
            IngestSource,
            make_training_example,
        )
        from photon_ml_tpu.io.schemas import TRAINING_EXAMPLE_SCHEMA
        from photon_ml_tpu.io.vocab import FeatureVocabulary
        from photon_ml_tpu.models.training import OptimizerType

        d = 12
        n_per = 400  # rows per part file (equal: even process split)
        paths = []
        w_true = rng.normal(size=d)
        for part in range(2):
            recs = []
            for i in range(n_per):
                x = rng.normal(size=d)
                z = x @ w_true
                y = float(rng.uniform() < 1 / (1 + np.exp(-z)))
                recs.append(
                    make_training_example(
                        label=y,
                        features={
                            (f"f{j}", ""): float(x[j]) for j in range(d)
                        },
                    )
                )
            p = str(tmp_path / f"part-{part}.avro")
            write_avro_file(p, TRAINING_EXAMPLE_SCHEMA, recs)
            paths.append(p)
        vocab = FeatureVocabulary(
            [f"f{j}\x01" for j in range(d)], add_intercept=False
        )
        vocab_path = str(tmp_path / "vocab.txt")
        vocab.save(vocab_path)

        with socket.socket() as s:
            s.bind(("localhost", 0))
            port = s.getsockname()[1]

        child_py = str(tmp_path / "child.py")
        with open(child_py, "w") as f:
            f.write(_TWO_PROC_CHILD)
        procs = []
        import os as _os

        env = dict(_os.environ)
        env["PYTHONPATH"] = _os.getcwd()
        for pid in range(2):
            procs.append(
                subprocess.Popen(
                    [
                        _sys.executable, child_py, str(pid), str(port),
                        str(tmp_path / f"w{pid}.npy"),
                        paths[0], paths[1], vocab_path,
                    ],
                    env=env,
                    stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE,
                    text=True,
                )
            )
        for pid, proc in enumerate(procs):
            out, err = proc.communicate(timeout=600)
            assert proc.returncode == 0, (
                f"child {pid} rc={proc.returncode}\n{out}\n{err}"
            )

        w0 = np.load(tmp_path / "w0.npy")
        w1 = np.load(tmp_path / "w1.npy")
        np.testing.assert_allclose(w0, w1, atol=1e-12)

        # single-process oracle over BOTH files in path order
        from photon_ml_tpu.models import GLMTrainingConfig, TaskType
        from photon_ml_tpu.models.training import train_glm

        batch, _, _ = IngestSource(paths).labeled_batch(
            vocab, dtype=jnp.float64
        )
        cfg = GLMTrainingConfig(
            task=TaskType.LOGISTIC_REGRESSION,
            optimizer=OptimizerType.TRON,
            regularization=RegularizationContext("L2"),
            reg_weights=(1.0,),
            max_iters=40,
            tolerance=1e-12,
            track_states=False,
        )
        (local,) = train_glm(batch, cfg)
        np.testing.assert_allclose(
            w0, np.asarray(local.model.coefficients.means), atol=1e-8
        )

        # sparse leg: both children solved the padded-ELL ingest of the
        # same split; must agree with each other and the dense solution
        w0_sp = np.load(tmp_path / "w0_sparse.npy")
        w1_sp = np.load(tmp_path / "w1_sparse.npy")
        np.testing.assert_allclose(w0_sp, w1_sp, atol=1e-12)
        np.testing.assert_allclose(w0_sp, w0, atol=1e-8)


_TWO_PROC_GAME_CHILD = r'''
import sys

proc_id = int(sys.argv[1])
port = sys.argv[2]
out_path = sys.argv[3]
data_path = sys.argv[4]

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 4)
jax.config.update("jax_enable_x64", True)

from photon_ml_tpu.parallel import (
    fetch_replicated,
    global_entity_space,
    initialize_multihost,
    make_global_array,
    make_global_batch,
    make_global_re_design,
    make_mesh,
)

joined = initialize_multihost(
    coordinator_address=f"localhost:{port}",
    num_processes=2,
    process_id=proc_id,
)
assert joined and jax.process_count() == 2 and jax.device_count() == 8

import numpy as np
import jax.numpy as jnp

from photon_ml_tpu.core.tasks import TaskType
from photon_ml_tpu.game import (
    CoordinateConfig,
    CoordinateDescent,
    FixedEffectCoordinate,
    GameData,
    RandomEffectCoordinate,
    build_bucketed_random_effect_design,
)
from photon_ml_tpu.models.training import OptimizerType

z = np.load(data_path)
xg, xu, y, users = z["xg"], z["xu"], z["y"], z["users"]  # users LOCAL
e_local = int(z["e_local"])
n_local = y.shape[0]
mesh = make_mesh()  # all 8 devices across both processes
row_base = n_local * jax.process_index()
e_global, e_base = global_entity_space(e_local)

# local design over THIS process's entities (rows entity-partitioned:
# every entity's rows live entirely in this split), then globalized:
# bucket lanes concatenate over processes and shard over the mesh
gd = GameData.create(
    features={"g": xg, "u": xu}, labels=y, entity_ids={"userId": users}
)
local_design = build_bucketed_random_effect_design(
    gd, "userId", "u", e_local, num_buckets=1, dtype=jnp.float64
)
g_design = make_global_re_design(
    local_design, mesh, e_global, e_base, row_base
)
fb = make_global_batch(gd.fixed_effect_batch("g", dtype=jnp.float64), mesh)
row_feats = make_global_array(np.asarray(xu, np.float64), mesh)
row_ents = make_global_array(
    np.where(users >= 0, users + e_base, -1).astype(np.int32), mesh
)
labels_g = make_global_array(np.asarray(y, np.float64), mesh)
zeros_g = make_global_array(np.zeros(n_local), mesh)
ones_g = make_global_array(np.ones(n_local), mesh)

fe_cfg = CoordinateConfig(
    shard="g", task=TaskType.LOGISTIC_REGRESSION,
    optimizer=OptimizerType.NEWTON, reg_weight=1.0, max_iters=8,
    tolerance=1e-9,
)
re_cfg = CoordinateConfig(
    shard="u", task=TaskType.LOGISTIC_REGRESSION,
    optimizer=OptimizerType.NEWTON, reg_weight=5.0, max_iters=8,
    tolerance=1e-9, random_effect="userId",
)
fixed = FixedEffectCoordinate(fb, fe_cfg)
re = RandomEffectCoordinate(
    design=g_design,
    row_features=row_feats,
    row_entities=row_ents,
    full_offsets_base=zeros_g,
    config=re_cfg,
)
cd = CoordinateDescent(
    coordinates={"fixed": fixed, "re": re},
    labels=labels_g,
    base_offsets=zeros_g,
    weights=ones_g,
    task=TaskType.LOGISTIC_REGRESSION,
)
model, hist = cd.run(num_iterations=1)
np.save(out_path, np.asarray(fetch_replicated(model.params["fixed"])))
np.save(
    out_path.replace(".npy", "_table.npy"),
    np.asarray(fetch_replicated(model.params["re"])),
)
np.save(
    out_path.replace(".npy", "_obj.npy"),
    np.asarray([h.objective for h in hist]),
)
print("game child", proc_id, "ok")
'''


class TestTwoProcessGame:
    """A FULL GAME coordinate-descent
    pass (fixed + bucketed random effect, scores assembled globally)
    executed across 2 processes x 4 devices, equal to the single-process
    run — the analog of the reference's fake-cluster GAME integ tests
    (``DriverGameIntegTest.scala:343-400``)."""

    def _make_data(self, rng, e_per_proc=16, rows_per_user=12,
                   d_fixed=6, d_user=3):
        e_total = 2 * e_per_proc
        n_total = e_total * rows_per_user
        # process-major entity ids; every entity's rows contiguous so the
        # halves are entity-partitioned (the multi-process contract)
        users = np.repeat(np.arange(e_total, dtype=np.int32), rows_per_user)
        xg = rng.normal(size=(n_total, d_fixed))
        xu = rng.normal(size=(n_total, d_user))
        w_g = rng.normal(size=d_fixed)
        w_u = rng.normal(size=(e_total, d_user))
        logits = xg @ w_g + np.einsum("nd,nd->n", xu, w_u[users])
        y = (rng.uniform(size=n_total) < 1 / (1 + np.exp(-logits))).astype(
            float
        )
        return users, xg, xu, y

    def test_two_process_game_pass_matches_single(self, rng, tmp_path):
        import socket
        import subprocess
        import sys as _sys

        users, xg, xu, y = self._make_data(rng)
        n_local = y.shape[0] // 2
        e_local = 16
        for pid in range(2):
            sl = slice(pid * n_local, (pid + 1) * n_local)
            np.savez(
                tmp_path / f"game{pid}.npz",
                xg=xg[sl],
                xu=xu[sl],
                y=y[sl],
                users=users[sl] - pid * e_local,  # LOCAL entity ids
                e_local=e_local,
            )

        with socket.socket() as s:
            s.bind(("localhost", 0))
            port = s.getsockname()[1]
        child_py = str(tmp_path / "game_child.py")
        with open(child_py, "w") as f:
            f.write(_TWO_PROC_GAME_CHILD)
        import os as _os

        env = dict(_os.environ)
        env["PYTHONPATH"] = _os.getcwd()
        procs = [
            subprocess.Popen(
                [
                    _sys.executable, child_py, str(pid), str(port),
                    str(tmp_path / f"gw{pid}.npy"),
                    str(tmp_path / f"game{pid}.npz"),
                ],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
            )
            for pid in range(2)
        ]
        for pid, proc in enumerate(procs):
            out, err = proc.communicate(timeout=600)
            assert proc.returncode == 0, (
                f"game child {pid} rc={proc.returncode}\n{out}\n{err}"
            )

        # both processes converged on identical global state
        w0 = np.load(tmp_path / "gw0.npy")
        w1 = np.load(tmp_path / "gw1.npy")
        t0 = np.load(tmp_path / "gw0_table.npy")
        t1 = np.load(tmp_path / "gw1_table.npy")
        np.testing.assert_allclose(w0, w1, atol=1e-12)
        np.testing.assert_allclose(t0, t1, atol=1e-12)

        # single-process oracle: same pass over the concatenated data
        import jax.numpy as jnp

        from photon_ml_tpu.core.tasks import TaskType
        from photon_ml_tpu.game import (
            CoordinateConfig,
            CoordinateDescent,
            FixedEffectCoordinate,
            GameData,
            RandomEffectCoordinate,
            build_bucketed_random_effect_design,
        )
        from photon_ml_tpu.models.training import OptimizerType

        gd = GameData.create(
            features={"g": xg, "u": xu}, labels=y,
            entity_ids={"userId": users},
        )
        design = build_bucketed_random_effect_design(
            gd, "userId", "u", 32, num_buckets=1, dtype=jnp.float64
        )
        fe_cfg = CoordinateConfig(
            shard="g", task=TaskType.LOGISTIC_REGRESSION,
            optimizer=OptimizerType.NEWTON, reg_weight=1.0, max_iters=8,
            tolerance=1e-9,
        )
        re_cfg = CoordinateConfig(
            shard="u", task=TaskType.LOGISTIC_REGRESSION,
            optimizer=OptimizerType.NEWTON, reg_weight=5.0, max_iters=8,
            tolerance=1e-9, random_effect="userId",
        )
        cd = CoordinateDescent(
            coordinates={
                "fixed": FixedEffectCoordinate(
                    gd.fixed_effect_batch("g", dtype=jnp.float64), fe_cfg
                ),
                "re": RandomEffectCoordinate(
                    design=design,
                    row_features=jnp.asarray(xu, jnp.float64),
                    row_entities=jnp.asarray(users),
                    full_offsets_base=jnp.zeros(y.shape[0]),
                    config=re_cfg,
                ),
            },
            labels=jnp.asarray(y, jnp.float64),
            base_offsets=jnp.zeros(y.shape[0]),
            weights=jnp.ones(y.shape[0]),
            task=TaskType.LOGISTIC_REGRESSION,
        )
        model, hist = cd.run(num_iterations=1)
        np.testing.assert_allclose(
            w0, np.asarray(model.params["fixed"]), atol=1e-7
        )
        np.testing.assert_allclose(
            t0, np.asarray(model.params["re"]), atol=1e-7
        )
        obj0 = np.load(tmp_path / "gw0_obj.npy")
        np.testing.assert_allclose(
            obj0, [h.objective for h in hist], rtol=1e-8
        )


class TestTwoProcessGameDriver:
    """Driver leg: a REAL 2-process invocation of
    the GAME training CLI — each process ingests its entity-partitioned
    part file, the driver assembles global designs, and the saved model
    equals a single-process run over both files."""

    def test_two_process_driver_matches_single(self, rng, tmp_path):
        import json as _json
        import socket
        import subprocess
        import sys as _sys

        import os as _os

        from tests.test_drivers import (
            make_game_records,
            write_feature_file,
            write_records,
        )

        records, truth = make_game_records(
            rng, n_users=12, rows_per_user=20, d_g=4, d_u=2
        )
        # ENTITY-PARTITIONED splits: users 0-5 -> part-0, 6-11 -> part-1
        parts = [[], []]
        for r in records:
            u = int(r["metadataMap"]["userId"][4:])
            parts[0 if u < 6 else 1].append(r)
        paths = [
            write_records(str(tmp_path / f"part-{i}.avro"), parts[i])
            for i in range(2)
        ]
        gshard = write_feature_file(
            str(tmp_path / "global.features"), [f"gf{j}" for j in range(4)]
        )
        ushard = write_feature_file(
            str(tmp_path / "user.features"), [f"uf{j}" for j in range(2)]
        )

        def config(out):
            return {
                "train_input": paths,
                "validate_input": [],
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
                        "tolerance": 1e-9,
                    },
                    "per-user": {
                        "shard": "ushard",
                        "random_effect": "userId",
                        "optimizer": "TRON",
                        "reg_weights": [1.0],
                        "max_iters": 20,
                        "tolerance": 1e-9,
                        "num_buckets": 1,
                    },
                },
            }

        with socket.socket() as s:
            s.bind(("localhost", 0))
            port = s.getsockname()[1]
        procs = []
        for pid in range(2):
            cfg_path = str(tmp_path / f"cfg{pid}.json")
            with open(cfg_path, "w") as f:
                _json.dump(config(str(tmp_path / f"out{pid}")), f)
            env = dict(_os.environ)
            env.update(
                PYTHONPATH=_os.getcwd(),
                JAX_PLATFORMS="cpu",
                JAX_NUM_CPU_DEVICES="4",
                JAX_ENABLE_X64="true",
                JAX_COORDINATOR_ADDRESS=f"localhost:{port}",
                JAX_NUM_PROCESSES="2",
                JAX_PROCESS_ID=str(pid),
            )
            procs.append(
                subprocess.Popen(
                    [
                        _sys.executable, "-m",
                        "photon_ml_tpu.cli.game_train",
                        "--config", cfg_path,
                    ],
                    env=env,
                    stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE,
                    text=True,
                )
            )
        for pid, proc in enumerate(procs):
            out, err = proc.communicate(timeout=600)
            assert proc.returncode == 0, (
                f"driver child {pid} rc={proc.returncode}\n{out}\n{err}"
            )

        # single-process oracle over both files, identical config
        from photon_ml_tpu.cli.game_train import run_game_training

        oracle = run_game_training(config(str(tmp_path / "oracle")))
        o_model = oracle.sweep[0]["model"]

        # load process 0's saved model through the ORACLE's vocabs so
        # entity-table rows align by RAW id regardless of per-process
        # vocab order (non-zero processes skip writes — shared output
        # dirs would race)
        import os as _os2

        from photon_ml_tpu.io.models import load_game_model

        assert not _os2.path.isdir(str(tmp_path / "out1" / "best"))
        coord_vocabs = {
            "global": oracle.shard_vocabs["gshard"],
            "per-user": oracle.shard_vocabs["ushard"],
        }
        loaded, _, _, _ = load_game_model(
            str(tmp_path / "out0" / "best"),
            coord_vocabs,
            {"per-user": oracle.entity_vocabs["userId"]},
        )
        np.testing.assert_allclose(
            np.asarray(loaded["global"]),
            np.asarray(o_model.params["global"]),
            atol=1e-6,
        )
        np.testing.assert_allclose(
            np.asarray(loaded["per-user"]),
            np.asarray(o_model.params["per-user"]),
            atol=1e-6,
        )


class TestMultihost:
    def test_single_process_noop(self, monkeypatch):
        from photon_ml_tpu.parallel import initialize_multihost
        from photon_ml_tpu.parallel import multihost

        # hermetic: strip any ambient cluster config so the guard path is
        # the one under test (pod-ish env vars exist on dev machines)
        for var in (
            "JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES", "JAX_PROCESS_ID"
        ):
            monkeypatch.delenv(var, raising=False)
        monkeypatch.setattr(multihost, "_INITIALIZED", False)
        assert initialize_multihost() is False

    def test_process_local_rows_single(self):
        from photon_ml_tpu.parallel import process_local_rows

        r = process_local_rows(103)
        assert list(r) == list(range(103))

    @pytest.mark.parametrize(
        "total,n_proc", [(103, 4), (4, 103), (0, 3), (8, 8), (7, 2)]
    )
    def test_split_rows_disjoint_covering(self, total, n_proc):
        from photon_ml_tpu.parallel.multihost import split_rows

        ranges = [split_rows(total, n_proc, p) for p in range(n_proc)]
        flat = [i for r in ranges for i in r]
        assert flat == list(range(total))

    def test_process_local_paths_single(self, monkeypatch):
        from photon_ml_tpu.parallel import process_local_paths

        for var in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES"):
            monkeypatch.delenv(var, raising=False)
        paths = [f"part-{i}.avro" for i in range(5)]
        assert process_local_paths(paths) == sorted(paths)
        with pytest.raises(ValueError, match="part files"):
            process_local_paths([])

    def test_process_local_paths_guard(self, monkeypatch):
        from photon_ml_tpu.parallel import (
            process_local_paths,
            process_local_rows,
        )

        # either join trigger alone must arm the guard
        monkeypatch.setenv("JAX_NUM_PROCESSES", "4")
        with pytest.raises(RuntimeError, match="has not joined"):
            process_local_paths(["a.avro"])
        monkeypatch.delenv("JAX_NUM_PROCESSES")
        monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "10.0.0.1:8476")
        with pytest.raises(RuntimeError, match="has not joined"):
            process_local_paths(["a.avro"])
        with pytest.raises(RuntimeError, match="has not joined"):
            process_local_rows(10)
