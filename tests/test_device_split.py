"""The device-side hot/cold split (``ops.sparse.split_on_device``) and the
rule that decides it (``ops.sparse.split_hot_cold``, consulted by
``train_glm`` under the ``glm.layout`` span).

The split must be invisible: the hybrid it makes gives the plain ELL's
numbers for all three contractions, also on rows that store a (row, column)
pair twice, and a solve on it gives the coefficients of a solve on the
unsplit design. The rule must leave every design it declines exactly as it
came, and say why. The CPU has no measured rates, so the tests that want the
split engaged stand the v5e's profile in (``_device_profile``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.core.types import LabeledBatch
from photon_ml_tpu.models import training
from photon_ml_tpu.models.training import (
    GLMTrainingConfig,
    OptimizerType,
    train_glm,
)
from photon_ml_tpu.ops import sparse as sparse_ops
from photon_ml_tpu.ops.objective import RegularizationContext
from photon_ml_tpu.ops.sparse import (
    SparseFeatures,
    cold_as_single_ell,
    colsum,
    from_coo,
    matvec,
    rmatvec,
    split_hot_cold,
    split_on_device,
    to_dense,
    to_hybrid,
)

V5E = sparse_ops._SPLIT_RATES["TPU v5 lite"]


@pytest.fixture
def v5e_profile(monkeypatch):
    """The rule sees a v5e with 12 GB free wherever it looks."""
    monkeypatch.setattr(
        sparse_ops, "_device_profile", lambda device: (V5E, 12 << 30, 0)
    )


def awkward_design(seed=0, n=96, k=7, d=40, dtype=np.float32):
    """Zipf columns drawn slot by slot, so rows store pairs twice; a fifth
    of the slots are padding; row 0 holds only the most frequent column
    (twice), row 1 only columns that occur nowhere else, row 2 nothing."""
    rng = np.random.default_rng(seed)
    idx = np.minimum(rng.zipf(1.5, (n, k)) - 1, d - 4).astype(np.int32)
    idx[rng.uniform(size=(n, k)) < 0.2] = d
    idx[0] = [0, 0] + [d] * (k - 2)
    idx[1] = [d - 1, d - 2, d - 3] + [d] * (k - 3)
    idx[2] = d
    val = np.where(idx < d, rng.normal(size=(n, k)), 0.0).astype(dtype)
    return SparseFeatures(jnp.asarray(idx), jnp.asarray(val), d)


def observed_columns(sf):
    idx = np.asarray(sf.indices)
    return int(np.unique(idx[idx < sf.d]).size)


def top_ids(sf):
    _, ids = sparse_ops._top_column_counts(sf.indices, d=sf.d, h_max=sf.d)
    return ids


def split(sf, h, **kw):
    return split_on_device(sf, top_ids(sf), h, **kw)[0]


CONTRACTIONS = {
    "matvec": lambda x, w, a: matvec(x, w),
    "rmatvec": lambda x, w, a: rmatvec(x, a),
    "colsum": lambda x, w, a: colsum(x, a),
    "colsum_square": lambda x, w, a: colsum(x, a, square=True),
}


class TestSplitMatchesEll:
    @pytest.mark.parametrize("contraction", sorted(CONTRACTIONS))
    @pytest.mark.parametrize("hot", ["one", "some", "all_observed"])
    def test_same_numbers_as_the_ell(self, contraction, hot):
        """Float32, 1e-6 relative, with the later copies of a pair kept
        cold (``exact_squares``): then all four are the ELL's."""
        sf = awkward_design()
        h = {"one": 1, "some": 6, "all_observed": observed_columns(sf)}[hot]
        hf = split(sf, h, exact_squares=True)
        assert hf.dense.shape == (sf.shape[0], h)
        assert hf.dense.dtype == jnp.float32
        rng = np.random.default_rng(1)
        w = jnp.asarray(rng.normal(size=sf.d), jnp.float32)
        a = jnp.asarray(rng.normal(size=sf.shape[0]), jnp.float32)
        perm = np.asarray(hf.row_perm)
        f = CONTRACTIONS[contraction]
        want = np.asarray(f(sf, w, a))
        got = np.asarray(f(hf, w, a[perm]))
        if contraction == "matvec":
            want = want[perm]
        scale = np.abs(want).max()
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6 * scale)

    @pytest.mark.parametrize("contraction", ["matvec", "rmatvec", "colsum"])
    def test_a_pair_stored_twice_sums_in_the_slab(self, contraction):
        """Without ``exact_squares`` both copies go to the slab cell: the
        linear contractions do not see it."""
        sf = awkward_design()
        hf = split(sf, 6)
        assert float(hf.dense[np.argsort(np.asarray(hf.row_perm))[0]].sum()) \
            == pytest.approx(float(sf.values[0].sum()), rel=1e-6)
        rng = np.random.default_rng(2)
        w = jnp.asarray(rng.normal(size=sf.d), jnp.float32)
        a = jnp.asarray(rng.normal(size=sf.shape[0]), jnp.float32)
        perm = np.asarray(hf.row_perm)
        f = CONTRACTIONS[contraction]
        want = np.asarray(f(sf, w, a))
        got = np.asarray(f(hf, w, a[perm]))
        if contraction == "matvec":
            want = want[perm]
        np.testing.assert_allclose(
            got, want, rtol=1e-6, atol=1e-6 * np.abs(want).max()
        )

    def test_squared_sums_are_why_exact_squares_exists(self):
        """A summed slab cell squares as (v1 + v2)^2, the ELL as
        v1^2 + v2^2: only the split that keeps later copies cold agrees."""
        sf = awkward_design()
        a = jnp.ones((sf.shape[0],), jnp.float32)
        want = np.asarray(colsum(sf, a, square=True))
        summed = split(sf, 6)
        exact = split(sf, 6, exact_squares=True)
        got_summed = np.asarray(colsum(summed, a, square=True))
        got_exact = np.asarray(colsum(exact, a, square=True))
        np.testing.assert_allclose(got_exact, want, rtol=1e-6, atol=1e-6)
        assert np.abs(got_summed - want).max() > 1e-2
        # and the later copies are what stayed cold
        assert sparse_ops.stored_cold_entries(exact) > \
            sparse_ops.stored_cold_entries(summed)

    @pytest.mark.parametrize("exact_squares", [False, True])
    def test_split_preserves_the_matrix(self, exact_squares):
        sf = awkward_design(seed=3)
        hf = split(sf, 5, exact_squares=exact_squares)
        np.testing.assert_allclose(
            to_dense(hf), to_dense(sf), rtol=1e-6, atol=1e-6
        )
        # rows in ascending cold count, segments tile them
        assert hf.segment_bounds()[-1][1] == sf.shape[0]
        widths = [seg.nnz_per_row for seg in hf.cold_segments]
        assert widths == sorted(widths)

    def test_bfloat16_design_keeps_its_dtype(self):
        sf = awkward_design()
        sf = dataclasses.replace(sf, values=sf.values.astype(jnp.bfloat16))
        hf = split(sf, 4)
        assert hf.dense.dtype == jnp.bfloat16
        assert all(s.values.dtype == jnp.bfloat16 for s in hf.cold_segments)


class TestAgainstHostToHybrid:
    @pytest.mark.parametrize("h", [1, 3, 9])
    def test_same_hot_set_same_cold_entries(self, h):
        """On dedup-summed input (what ``to_hybrid`` accepts) the device
        split picks the columns ``to_hybrid`` picks and leaves the cold
        entries it leaves."""
        rng = np.random.default_rng(5)
        n, d, nnz = 160, 50, 6
        rows = np.repeat(np.arange(n), nnz)
        cols = (rng.zipf(1.3, size=n * nnz) - 1) % d
        sf = from_coo(rows, cols, rng.normal(size=n * nnz), n, d,
                      dtype=jnp.float32)
        counts = np.bincount(
            np.asarray(sf.indices)[np.asarray(sf.indices) < d], minlength=d
        )
        ranked = np.sort(counts)[::-1]
        while ranked[h - 1] == ranked[h]:  # cut where no two columns tie
            h += 1
        host = to_hybrid(sf, hot_columns=h)
        dev = split(sf, h)
        assert sorted(np.asarray(dev.hot_ids)) == sorted(
            np.asarray(host.hot_ids)
        )

        def cold_in_row_order(hf):
            stored = to_dense(cold_as_single_ell(hf))
            out = np.empty_like(stored)
            out[np.asarray(hf.row_perm)] = stored
            return out

        np.testing.assert_array_equal(
            cold_in_row_order(dev), cold_in_row_order(host)
        )
        assert sparse_ops.cold_padded_slots(dev) == \
            sparse_ops.cold_padded_slots(host)
        np.testing.assert_allclose(to_dense(dev), to_dense(host), rtol=1e-6)


class TestFloat32Slab:
    def test_slab_products_stay_float32(self):
        """Values that bfloat16 cannot hold: the hybrid products agree with
        a float64 product to float32 level, three orders under what one
        bfloat16 pass would give."""
        rng = np.random.default_rng(7)
        n, h, d = 512, 64, 64
        x = (1.0 + rng.uniform(size=(n, h)) * 2.0 ** -9).astype(np.float32)
        assert np.abs(
            np.asarray(jnp.asarray(x).astype(jnp.bfloat16), np.float32) - x
        ).max() > 1e-4
        sf = sparse_ops.from_dense(x)
        hf = split(sf, h)
        assert sparse_ops.stored_cold_entries(hf) == 0
        w = rng.normal(size=d).astype(np.float32)
        a = rng.normal(size=n).astype(np.float32)
        perm = np.asarray(hf.row_perm)
        z = np.asarray(matvec(hf, jnp.asarray(w)))
        want_z = (x.astype(np.float64) @ w.astype(np.float64))[perm]
        # 1e-6 of the summed magnitudes: float32 accumulation of 64 and
        # 512 terms; one bfloat16 pass would miss by 2^-9 of them
        np.testing.assert_allclose(
            z, want_z, rtol=1e-6, atol=1e-6 * (np.abs(x) @ np.abs(w)).max()
        )
        g = np.asarray(rmatvec(hf, jnp.asarray(a[perm])))
        want_g = x.astype(np.float64).T @ a.astype(np.float64)
        np.testing.assert_allclose(
            g, want_g, rtol=1e-6, atol=1e-6 * (np.abs(x).T @ np.abs(a)).max()
        )

    @pytest.mark.parametrize("contraction", sorted(CONTRACTIONS))
    def test_slab_products_ask_for_highest_precision(self, contraction):
        """What keeps the chip's MXU from one bfloat16 pass over a float32
        slab is the precision the product is traced with."""
        hf = split(awkward_design(), 6)
        w = jnp.zeros((hf.d,), jnp.float32)
        a = jnp.zeros((hf.shape[0],), jnp.float32)
        closed = jax.make_jaxpr(CONTRACTIONS[contraction])(hf, w, a)

        def dots(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "dot_general":
                    yield eqn.params["precision"]
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from dots(sub)

        found = list(dots(closed.jaxpr))
        assert found and all(
            p is not None and set(p) == {jax.lax.Precision.HIGHEST}
            for p in found
        )


# -- the rule ----------------------------------------------------------------


def zipf_batch(n=2048, k=12, d=4096, seed=11, relabel=None, dtype=np.float32):
    """A Zipf toy with a planted model; ``relabel`` = (column bijection,
    row order) gives the same problem at other addresses."""
    rng = np.random.default_rng(seed)
    ranks = np.minimum(rng.zipf(1.2, (n, k)) - 1, d - 1)
    val = rng.uniform(0.5, 1.5, size=(n, k)).astype(dtype)
    w_true = np.cos(np.arange(d) * 0.7)
    z = 0.5 * np.sum(val * w_true[ranks], axis=1)
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-z))).astype(dtype)
    cols = ranks
    if relabel is not None:
        col_map, order = relabel
        cols, val, y = col_map[ranks][order], val[order], y[order]
    sf = SparseFeatures(jnp.asarray(cols, jnp.int32), jnp.asarray(val), d)
    return LabeledBatch.create(sf, y, dtype=jnp.dtype(dtype))


def lbfgs_config(**kw):
    base = dict(
        optimizer=OptimizerType.LBFGS,
        regularization=RegularizationContext("L2"),
        reg_weights=(1.0,),
        max_iters=40,
        tolerance=1e-9,
    )
    base.update(kw)
    return GLMTrainingConfig(**base)


def counters():
    reg = obs.registry()
    return {n: reg.counter(n).value for n in reg.names("sparse.split.")}


def booked_since(before):
    after = counters()
    return {
        n: v - before.get(n, 0.0) for n, v in after.items()
        if v != before.get(n, 0.0)
    }


def layout_spans():
    return [r for r in obs.recent_spans() if r[0] == "glm.layout"]


class TestTrainGlmOnTheSplit:
    @pytest.mark.parametrize("compute_variances", [False, True])
    def test_solve_on_the_split_matches_the_unsplit_solve(
        self, monkeypatch, compute_variances
    ):
        # float64, so that the two solves walk one trajectory and their
        # evaluation counts can be compared (float32 is pinned above)
        batch = zipf_batch(dtype=np.float64)
        cfg = lbfgs_config(
            compute_variances=compute_variances, max_iters=200,
            tolerance=1e-8,
        )
        (plain,) = train_glm(batch, cfg)  # the CPU has no rates: unsplit
        assert layout_spans()[-1][6]["reason"] == "no_rates"
        monkeypatch.setattr(
            sparse_ops, "_device_profile",
            lambda device: (V5E, 12 << 30, 0),
        )
        before = counters()
        (split_,) = train_glm(batch, cfg)
        assert booked_since(before) == {"sparse.split.engaged": 1.0}
        np.testing.assert_allclose(
            np.asarray(split_.model.coefficients.means),
            np.asarray(plain.model.coefficients.means),
            rtol=1e-5, atol=1e-5,
        )
        assert int(split_.result.evals) == int(plain.result.evals)
        if compute_variances:
            np.testing.assert_allclose(
                np.asarray(split_.model.coefficients.variances),
                np.asarray(plain.model.coefficients.variances),
                rtol=1e-5,
            )

    def test_layout_span_and_counter_once_a_call(self, v5e_profile):
        batch = zipf_batch()
        cfg = lbfgs_config()
        before = counters()
        for _ in range(2):
            train_glm(batch, cfg)
        assert booked_since(before) == {"sparse.split.engaged": 2.0}
        spans = obs.recent_spans()
        layouts = [r for r in spans if r[0] == "glm.layout"][-2:]
        paths = [r for r in spans if r[0] == "glm.solve_path"][-2:]
        for layout, path in zip(layouts, paths):
            attrs = layout[6]
            assert attrs["hot_columns"] >= 1
            assert 0.0 < attrs["hot_slot_share"] <= 1.0
            assert attrs["segments"] >= 1
            assert attrs["cold_padded_slots"] < 2048 * 12
            assert layout[2] <= path[1]  # ends before the solve opens
        # nothing of the split outlives the call: the batch is as it came
        assert sparse_ops.is_sparse(batch.features)

    def test_relabelled_designs_split_alike(self, v5e_profile):
        """Seeds of a benchmark cell are one problem under a relabelling
        and a row order: they must get the same hot count and the same
        segment shapes, or every seed compiles its own programs."""
        rng = np.random.default_rng(3)
        a = zipf_batch()
        b = zipf_batch(relabel=(rng.permutation(4096), rng.permutation(2048)))
        ha, ia = split_hot_cold(a.features, evaluations=40)
        hb, ib = split_hot_cold(b.features, evaluations=40)
        assert ia == ib
        assert [s.indices.shape for s in ha.cold_segments] == \
            [s.indices.shape for s in hb.cold_segments]
        assert ha.dense.shape == hb.dense.shape


class TestRule:
    def test_slab_is_whole_lanes_wide_and_the_spare_columns_are_empty(
        self, v5e_profile
    ):
        """The rule rounds the slab up to whole 128-lane tiles (off them
        the chip's solve program kept a second copy of the slab); the
        spare columns take the next ids and hold nothing."""
        sf = zipf_batch().features
        hf, info = split_hot_cold(sf, evaluations=40)
        h, width = info["hot_columns"], hf.dense.shape[1]
        assert h < width and width % 128 == 0 and width - h < 128
        assert hf.hot_ids.shape == (width,)
        assert len(set(np.asarray(hf.hot_ids).tolist())) == width
        assert not np.asarray(hf.dense[:, h:]).any()
        np.testing.assert_allclose(to_dense(hf), to_dense(sf), rtol=1e-6)

    def test_break_even_at_the_cell_s_size(self):
        """At the cell's 2^20 float32 rows a dense column costs what about
        670 gathered-and-scattered entries cost (ISSUE 27 reckoned 550, at
        the published HBM peak; a slab product reads at 670 GB/s), before
        the split's own cost; over a dozen evaluations that adds a half."""
        pure = sparse_ops.hot_column_break_even(
            1 << 20, 39, 4, V5E, evaluations=float("inf")
        )
        assert 600 < pure < 750
        dozen = sparse_ops.hot_column_break_even(
            1 << 20, 39, 4, V5E, evaluations=12
        )
        assert pure < dozen < 4 * pure

    @staticmethod
    def _room_for(columns, sf):
        """Free bytes at which the rule finds room for ``columns`` slab
        columns: each twice (XLA may keep a second copy of the slab),
        beside the solve's and the split's scratch a slot."""
        n = sf.shape[0]
        a_slot = (sparse_ops._SOLVE_SCRATCH_BYTES_A_SLOT
                  + sparse_ops._SPLIT_SCRATCH_BYTES_A_SLOT)
        return 2 * columns * n * 4 + a_slot * sf.indices.size

    @staticmethod
    def _columns_of(counts, n=4096, d=64):
        idx = np.full((n, len(counts)), d, np.int32)
        for c, cnt in enumerate(counts):
            idx[:cnt, c] = c
        return SparseFeatures(
            jnp.asarray(idx), jnp.asarray((idx < d).astype(np.float32)), d
        )

    def test_memory_cap_cuts_at_a_whole_count(self, monkeypatch):
        """Room for 5 columns is a cap of 4 (a power of two), and columns
        3..6 tie: the tie stays out."""
        sf = self._columns_of([4096, 3000, 2000, 900, 900, 900, 900, 10])
        monkeypatch.setattr(
            sparse_ops, "_device_profile",
            lambda device: (V5E, self._room_for(5, sf), 0),
        )
        hf, info = split_hot_cold(sf, evaluations=1000)
        assert info["hot_columns"] == 3
        assert sorted(np.asarray(hf.hot_ids)) == [0, 1, 2]

    @pytest.mark.parametrize("room,reserved", [(8, 0), (11, 0), (15, 0),
                                               (11, 1 << 20)])
    def test_memory_cap_moves_in_powers_of_two(
        self, monkeypatch, room, reserved
    ):
        """What is free moves with the process's state; the cap must not
        follow it in small steps, or the jobs of one process get different
        shapes and each compiles its own programs. Bytes the runtime
        already holds reserved for the loaded solve count towards the
        solve's scratch, not against the slab a second time."""
        sf = self._columns_of(list(range(4000, 2400, -100)))  # 16, no tie
        free = self._room_for(room, sf) - reserved
        monkeypatch.setattr(
            sparse_ops, "_device_profile",
            lambda device: (V5E, free, reserved),
        )
        hf, info = split_hot_cold(sf, evaluations=1000)
        assert info["hot_columns"] == 8
        assert hf.dense.shape[1] == 8

    def test_no_memory_for_one_column(self, monkeypatch):
        sf = self._columns_of([4096, 3000])
        monkeypatch.setattr(
            sparse_ops, "_device_profile",
            lambda device: (V5E, self._room_for(1, sf) - 1, 0),
        )
        assert split_hot_cold(sf, evaluations=1000) == (
            None, {"reason": "no_memory"}
        )

    @pytest.mark.parametrize("n,k", [(300, 10), (200, 12), (512, 6)])
    def test_fewer_slots_than_counts_fetched(self, monkeypatch, n, k):
        """A design of 2-3 thousand slots in a hashed space wider than the
        4,096 counts the rule fetches passes the first gate at 80
        iterations: ``top_k`` must not be asked for more than there are
        slots, and the solve is the unsplit one's."""
        assert n * k < sparse_ops._TOP_COLUMNS
        batch = zipf_batch(n=n, k=k, d=1 << 18, dtype=np.float64)
        cfg = lbfgs_config(max_iters=80, tolerance=1e-8)
        (plain,) = train_glm(batch, cfg)
        monkeypatch.setattr(
            sparse_ops, "_device_profile",
            lambda device: (V5E, 12 << 30, 0),
        )
        asked = []
        counts = sparse_ops._top_column_counts
        monkeypatch.setattr(
            sparse_ops, "_top_column_counts",
            lambda i, **kw: asked.append(kw["h_max"]) or counts(i, **kw),
        )
        before = counters()
        (got,) = train_glm(batch, cfg)
        assert asked == [n * k]
        assert booked_since(before) == {"sparse.split.engaged": 1.0}
        np.testing.assert_allclose(
            np.asarray(got.model.coefficients.means),
            np.asarray(plain.model.coefficients.means),
            rtol=1e-5, atol=1e-5,
        )


def _declined(batch, cfg, reason):
    """``train_glm``'s layout step leaves ``batch`` as it came (the same
    object, so the solve program and its cache key are the unsplit ones)
    and books the reason."""
    before = counters()
    out = training._hot_cold_layout(batch, cfg)
    assert out is batch
    assert booked_since(before) == {
        "sparse.split.skipped": 1.0,
        "sparse.split.skipped." + reason: 1.0,
    }
    attrs = layout_spans()[-1][6]
    assert attrs["reason"] == reason and attrs["hot_columns"] == 0


class TestRuleDeclines:
    def test_no_rates_on_this_device(self):
        _declined(zipf_batch(), lbfgs_config(), "no_rates")

    def test_uniform_columns(self, v5e_profile):
        rng = np.random.default_rng(0)
        n, k, d = 2048, 12, 1 << 16
        sf = SparseFeatures(
            jnp.asarray(rng.permutation(n * k).reshape(n, k) % d, jnp.int32),
            jnp.ones((n, k), jnp.float32), d,
        )
        batch = LabeledBatch.create(
            sf, rng.integers(0, 2, n).astype(np.float32), dtype=jnp.float32
        )
        _declined(batch, lbfgs_config(), "no_hot_column")

    def test_tiny_n(self, v5e_profile):
        batch = zipf_batch(n=64, k=4, d=128)
        _declined(batch, lbfgs_config(), "does_not_pay")

    def test_a_single_evaluation(self, v5e_profile):
        _declined(zipf_batch(), lbfgs_config(max_iters=1), "does_not_pay")

    def test_newton(self, v5e_profile):
        cfg = lbfgs_config(optimizer=OptimizerType.NEWTON)
        _declined(zipf_batch(), cfg, "dense_hessian")

    def test_arrays_sharded_over_two_devices(self, v5e_profile):
        from photon_ml_tpu.parallel.mesh import make_mesh, shard_batch

        sharded = shard_batch(zipf_batch(), make_mesh(2))
        assert len(sharded.features.indices.sharding.device_set) == 2
        _declined(sharded, lbfgs_config(), "sharded")

    def test_hybrid_features_in(self, v5e_profile):
        batch = zipf_batch()
        hf, _ = split_hot_cold(batch.features, evaluations=40)
        perm = hf.row_perm
        hybrid = dataclasses.replace(
            batch, features=hf, labels=batch.labels[perm]
        )
        _declined(hybrid, lbfgs_config(), "hybrid")

    def test_dense_features_in(self, v5e_profile):
        rng = np.random.default_rng(0)
        batch = LabeledBatch.create(
            rng.normal(size=(64, 8)).astype(np.float32),
            rng.integers(0, 2, 64).astype(np.float32), dtype=jnp.float32,
        )
        _declined(batch, lbfgs_config(), "dense")

    def test_host_arrays(self, v5e_profile):
        batch = zipf_batch()
        sf = batch.features
        host = dataclasses.replace(
            batch,
            features=SparseFeatures(
                np.asarray(sf.indices), np.asarray(sf.values), sf.d
            ),
        )
        _declined(host, lbfgs_config(), "not_on_device")

    @pytest.mark.parametrize("case", ["tiny_n", "no_rates"])
    def test_declined_solve_is_bit_identical(self, monkeypatch, case):
        """The solve of a design the rule declines is the solve of the
        tree before the rule existed: the batch goes through untouched."""
        batch = zipf_batch(n=64, k=4, d=128) if case == "tiny_n" \
            else zipf_batch()
        cfg = lbfgs_config()
        monkeypatch.setattr(training, "_hot_cold_layout", lambda b, c: b)
        (want,) = train_glm(batch, cfg)
        monkeypatch.undo()
        if case == "tiny_n":
            monkeypatch.setattr(
                sparse_ops, "_device_profile",
                lambda device: (V5E, 12 << 30, 0),
            )
        (got,) = train_glm(batch, cfg)
        np.testing.assert_array_equal(
            np.asarray(got.model.coefficients.means),
            np.asarray(want.model.coefficients.means),
        )
        assert int(got.result.evals) == int(want.result.evals)
