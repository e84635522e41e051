"""Hybrid dense-hot / sparse-cold features (ops.sparse.HybridFeatures):
the power-law split must be algebraically invisible — every kernel,
statistic, validator, and full solve agrees with the plain-ELL (and hence
dense) semantics on the same matrix. The representation exists purely for
the TPU cost model measured in r5 (every ELL SLOT pays ~8 ns of
irregular access; a dense slab column rides the MXU at full bandwidth),
so rows live in a permuted, cold-count-bucketed order — ``row_perm``
maps stored back to original."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.core.types import LabeledBatch
from photon_ml_tpu.ops.sparse import (
    SparseFeatures,
    cold_as_single_ell,
    colsum,
    from_coo,
    matvec,
    rmatvec,
    stored_cold_entries,
    to_dense,
    to_hybrid,
)


def zipf_sparse(rng, n, d, nnz):
    """Power-law columns — the data shape the hybrid split exists for."""
    rows = np.repeat(np.arange(n), nnz)
    ranks = rng.zipf(1.3, size=n * nnz)
    cols = (ranks - 1) % d
    vals = rng.normal(size=n * nnz)
    return rows, cols, vals


@pytest.fixture
def sf(rng):
    n, d, nnz = 128, 80, 6
    return from_coo(*zipf_sparse(rng, n, d, nnz), n, d, dtype=jnp.float64)


class TestHybridKernels:
    @pytest.mark.parametrize("hot_columns", [-1, 1, 5, 80])
    def test_split_preserves_matrix(self, sf, hot_columns):
        hf = to_hybrid(sf, hot_columns=hot_columns)
        np.testing.assert_allclose(
            to_dense(hf), to_dense(sf), rtol=1e-12, atol=1e-12
        )

    @pytest.mark.parametrize("num_row_buckets", [1, 3, 8])
    def test_kernels_match_ell(self, sf, rng, num_row_buckets):
        hf = to_hybrid(sf, num_row_buckets=num_row_buckets)
        perm = np.asarray(hf.row_perm)
        n, d = sf.shape
        w = jnp.asarray(rng.normal(size=d))
        a = jnp.asarray(rng.normal(size=n))
        # hybrid results are in STORED order; compare through the perm
        np.testing.assert_allclose(
            np.asarray(matvec(hf, w)),
            np.asarray(matvec(sf, w))[perm],
            rtol=1e-10,
        )
        np.testing.assert_allclose(
            np.asarray(rmatvec(hf, a[perm])),
            np.asarray(rmatvec(sf, a)),
            rtol=1e-10, atol=1e-12,
        )
        for square in (False, True):
            np.testing.assert_allclose(
                np.asarray(colsum(hf, a[perm], square=square)),
                np.asarray(colsum(sf, a, square=square)),
                rtol=1e-10, atol=1e-12,
            )

    def test_bucketing_reduces_padded_slots(self, rng):
        n, d, nnz = 512, 200, 10
        sf = from_coo(*zipf_sparse(rng, n, d, nnz), n, d, dtype=jnp.float64)
        one = to_hybrid(sf, num_row_buckets=1)
        many = to_hybrid(sf, num_row_buckets=8)

        def slots(hf):
            return sum(
                int(np.prod(seg.indices.shape)) for seg in hf.cold_segments
            )

        assert slots(many) < slots(one)
        # and both still represent the same matrix
        np.testing.assert_allclose(
            to_dense(many), to_dense(one), rtol=1e-12
        )

    def test_auto_split_moves_hot_mass(self, sf):
        hf = to_hybrid(sf, hot_columns=-1, min_count=8)
        # the head of a Zipf distribution must land in the slab
        stored_total = int(np.sum(np.asarray(sf.indices) < sf.d))
        assert stored_cold_entries(hf) < stored_total
        assert hf.dense.shape[1] >= 1
        # slab columns and cold columns are disjoint
        for seg in hf.cold_segments:
            cold_cols = np.asarray(seg.indices)
            cold_cols = np.unique(cold_cols[cold_cols < seg.d])
            assert not np.intersect1d(
                cold_cols, np.asarray(hf.hot_ids)
            ).size

    def test_all_hot_degrades_gracefully(self, sf):
        hf = to_hybrid(sf, hot_columns=80)
        assert stored_cold_entries(hf) == 0
        np.testing.assert_allclose(to_dense(hf), to_dense(sf), rtol=1e-12)

    def test_duplicate_slots_rejected(self):
        """Duplicate (row, col) slots would square differently in the
        slab vs the ELL (Hessian-diagonal/variance divergence) — refuse
        them instead (from_coo-dedup'd input is the invariant)."""
        sf = SparseFeatures(
            indices=jnp.asarray([[0, 0, 2], [1, 2, 3]], jnp.int32),
            values=jnp.asarray(
                [[1.0, 2.0, 3.0], [1.0, 1.0, 1.0]], jnp.float64
            ),
            d=4,
        )
        with pytest.raises(ValueError, match="dedup-summed"):
            to_hybrid(sf)

    def test_cold_as_single_ell_round_trip(self, sf):
        hf = to_hybrid(sf)
        merged = cold_as_single_ell(hf)
        stored = np.concatenate(
            [to_dense(seg) for seg in hf.cold_segments]
        )
        np.testing.assert_allclose(to_dense(merged), stored, rtol=1e-12)


def _hybrid_batch(sf, y):
    """Build a CONSISTENT hybrid batch: rows permuted with the features."""
    hf = to_hybrid(sf)
    perm = np.asarray(hf.row_perm)
    return LabeledBatch.create(hf, np.asarray(y)[perm], dtype=jnp.float64)


class TestHybridBatch:
    def _batches(self, rng, sf):
        n = sf.shape[0]
        y = (rng.uniform(size=n) > 0.5).astype(np.float64)
        b_ell = LabeledBatch.create(sf, y, dtype=jnp.float64)
        b_hyb = _hybrid_batch(sf, y)
        return b_ell, b_hyb

    def test_stats_match(self, rng, sf):
        from photon_ml_tpu.ops.stats import summarize_features

        b_ell, b_hyb = self._batches(rng, sf)
        s1 = summarize_features(b_ell)
        s2 = summarize_features(b_hyb)
        for field in (
            "mean", "variance", "min", "max", "norm_l1", "norm_l2",
            "mean_abs", "num_nonzeros",
        ):
            np.testing.assert_allclose(
                np.asarray(getattr(s2, field)),
                np.asarray(getattr(s1, field)),
                rtol=1e-9, atol=1e-12, err_msg=field,
            )

    def test_pad_to(self, rng, sf):
        b_ell, b_hyb = self._batches(rng, sf)
        p_ell = LabeledBatch.pad_to(b_ell, 160)
        p_hyb = LabeledBatch.pad_to(b_hyb, 160)
        np.testing.assert_allclose(
            to_dense(p_hyb.features), to_dense(p_ell.features), rtol=1e-12
        )
        assert int(p_hyb.mask.sum()) == int(p_ell.mask.sum())

    def test_validators_see_nonfinite_slab_and_cold(self, rng, sf):
        from photon_ml_tpu.core.tasks import TaskType
        from photon_ml_tpu.core.validators import sanity_check_data

        _, b_hyb = self._batches(rng, sf)
        sanity_check_data(b_hyb, TaskType.LOGISTIC_REGRESSION)  # clean: ok
        # poison one slab value
        hf = b_hyb.features
        bad_dense = hf.dense.at[3, 0].set(jnp.nan)
        bad = dataclasses.replace(
            b_hyb, features=dataclasses.replace(hf, dense=bad_dense)
        )
        with pytest.raises(ValueError, match="finite_features"):
            sanity_check_data(bad, TaskType.LOGISTIC_REGRESSION)
        # poison one cold value in the last (widest) segment
        seg = hf.cold_segments[-1]
        bad_seg = dataclasses.replace(
            seg, values=seg.values.at[0, 0].set(jnp.inf)
        )
        bad = dataclasses.replace(
            b_hyb,
            features=dataclasses.replace(
                hf,
                cold_segments=hf.cold_segments[:-1] + (bad_seg,),
            ),
        )
        with pytest.raises(ValueError, match="finite_features"):
            sanity_check_data(bad, TaskType.LOGISTIC_REGRESSION)


class TestHybridTraining:
    def test_solve_matches_ell(self, rng):
        from photon_ml_tpu.models import (
            GLMTrainingConfig,
            OptimizerType,
            TaskType,
            train_glm,
        )
        from photon_ml_tpu.ops import RegularizationContext

        n, d, nnz = 400, 60, 8
        sf = from_coo(
            *zipf_sparse(rng, n, d, nnz), n, d, dtype=jnp.float64
        )
        w_true = rng.normal(size=d)
        z = to_dense(sf) @ w_true
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-z))).astype(np.float64)
        cfg = GLMTrainingConfig(
            task=TaskType.LOGISTIC_REGRESSION,
            optimizer=OptimizerType.TRON,
            regularization=RegularizationContext("L2"),
            reg_weights=(1.0,),
            tolerance=1e-10,
            max_iters=100,
        )
        (ell,) = train_glm(LabeledBatch.create(sf, y, dtype=jnp.float64), cfg)
        (hyb,) = train_glm(_hybrid_batch(sf, y), cfg)
        np.testing.assert_allclose(
            np.asarray(hyb.model.coefficients.means),
            np.asarray(ell.model.coefficients.means),
            rtol=1e-6, atol=1e-8,
        )


class TestHybridDriver:
    def test_hot_columns_knob(self, rng, tmp_path):
        from photon_ml_tpu.cli.train import run_glm_training
        from photon_ml_tpu.io.avro import write_avro_file
        from photon_ml_tpu.io.ingest import make_training_example
        from photon_ml_tpu.io.schemas import TRAINING_EXAMPLE_SCHEMA

        n, d = 300, 40
        recs = []
        for i in range(n):
            ranks = (rng.zipf(1.3, size=6) - 1) % d
            feats = {
                (f"f{int(j)}", ""): float(rng.normal()) for j in set(ranks)
            }
            recs.append(
                make_training_example(
                    label=float(i % 2),
                    features=feats,
                    offset=float(rng.normal()) * 0.1,
                    weight=float(rng.uniform(0.5, 2.0)),
                )
            )
        write_avro_file(
            str(tmp_path / "train" / "p.avro"), TRAINING_EXAMPLE_SCHEMA, recs
        )
        common = {
            "train_input": [str(tmp_path / "train")],
            "validate_input": [str(tmp_path / "train")],
            "task": "LOGISTIC_REGRESSION",
            "optimizer": "TRON",
            "reg_weights": [1.0],
            "max_iters": 60,
            "tolerance": 1e-10,
            "sparse": True,
        }
        r_ell = run_glm_training(
            {**common, "output_dir": str(tmp_path / "out_ell")}
        )
        r_hyb = run_glm_training(
            {**common, "output_dir": str(tmp_path / "out_hyb"),
             "hot_columns": -1}
        )
        # identical solution AND identical validation metrics: the
        # row permutation stayed aligned with labels/offsets/weights
        np.testing.assert_allclose(
            np.asarray(r_hyb.models[0].model.coefficients.means),
            np.asarray(r_ell.models[0].model.coefficients.means),
            rtol=1e-6, atol=1e-8,
        )
        for k, v in r_ell.validation_metrics[0].items():
            np.testing.assert_allclose(
                r_hyb.validation_metrics[0][k], v, rtol=1e-6,
                err_msg=k,
            )

    def test_knob_requires_sparse(self):
        from photon_ml_tpu.cli.config import GLMDriverParams

        p = GLMDriverParams(
            train_input=["x"], output_dir="y", hot_columns=4
        )
        with pytest.raises(ValueError, match="hot_columns requires sparse"):
            p.validate()

    def test_knob_rejects_newton_and_mesh(self):
        from photon_ml_tpu.cli.config import GLMDriverParams

        p = GLMDriverParams(
            train_input=["x"], output_dir="y", sparse=True,
            hot_columns=-1, optimizer="NEWTON",
        )
        with pytest.raises(ValueError, match="NEWTON"):
            p.validate()
        p = GLMDriverParams(
            train_input=["x"], output_dir="y", sparse=True,
            hot_columns=-1, mesh_shape={"data": 2},
        )
        with pytest.raises(ValueError, match="single-device"):
            p.validate()
