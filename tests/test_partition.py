"""Overlap-scaled multi-device partitioning drills (docs/PARALLEL.md):

- the PHOTON_COLLECTIVE_MODE={fused,overlap} equivalence oracle: the
  chunked reduce-scatter/all-gather pipeline + row-balanced blocked
  layout must match the PR-5 fused formulation per-op and per-solve;
- bucketed-reduction drills at 2/4/8-device emulated meshes (the r06
  suite only asserted width 2) with collective-count assertions on the
  compiled HLO;
- hierarchical two-level (ICI-then-DCN) reductions on a ('host',
  'device') mesh == the flat psum == the local objective;
- entity-sharded GAME descent == single-device descent <= 1e-10 across
  widths 2/4/8, incl. a shard-count-not-dividing-entity-count remainder
  case and resume-from-sharded-checkpoint at a DIFFERENT width, with a
  zero-collective assertion on the compiled random-effect update.

All drills run on the 8-virtual-CPU-device tier-1 pod
(``jax_num_cpu_devices`` via conftest).
"""

import dataclasses

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np
import pytest

from photon_ml_tpu.core.tasks import TaskType
from photon_ml_tpu.core.types import LabeledBatch
from photon_ml_tpu.models import GLMTrainingConfig, train_glm
from photon_ml_tpu.models.training import OptimizerType
from photon_ml_tpu.obs.xla_cost import count_collectives
from photon_ml_tpu.ops import RegularizationContext
from photon_ml_tpu.ops import sparse as sparse_ops
from photon_ml_tpu.ops.losses import LOGISTIC_LOSS
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.parallel import (
    feature_sharded_train_glm,
    make_feature_mesh,
    make_mesh,
    shard_batch,
    shard_map_value_and_grad,
)
from photon_ml_tpu.parallel.mesh import (
    DATA_AXIS,
    FEATURE_AXIS,
    batch_sharding,
    make_entity_mesh,
    make_host_device_mesh,
)
from photon_ml_tpu.parallel.overlap import (
    COLLECTIVE_MODE_ENV,
    OVERLAP_CHUNKS_ENV,
    collective_mode,
    feature_block_sum,
    overlap_chunks,
)

pytestmark = pytest.mark.partition

# Mesh widths drilled: tier-1 keeps 4 (the four-chip host's width, and a
# remainder case for the 17-entity drill); 2 and 8 run in the slow tier
# — same code, same assertions, only the width differs (ROADMAP D10).
WIDTHS = [
    pytest.param(2, marks=pytest.mark.slow),
    4,
    pytest.param(8, marks=pytest.mark.slow),
]


def _sparse_problem(rng, n=257, d=93, nnz=7):
    rows = np.repeat(np.arange(n), nnz)
    cols = rng.integers(0, d, size=n * nnz)
    vals = rng.normal(size=n * nnz)
    sf = sparse_ops.from_coo(rows, cols, vals, n, d, dtype=jnp.float64)
    w = rng.normal(size=d) * (rng.uniform(size=d) < 0.5)
    z = np.asarray(sparse_ops.matvec(sf, jnp.asarray(w))) * 0.5
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-z))).astype(float)
    return sf, y


class TestCollectiveModeKnob:
    def test_default_is_overlap(self, monkeypatch):
        monkeypatch.delenv(COLLECTIVE_MODE_ENV, raising=False)
        assert collective_mode() == "overlap"

    def test_invalid_mode_rejected(self, monkeypatch):
        monkeypatch.setenv(COLLECTIVE_MODE_ENV, "async")
        with pytest.raises(ValueError, match="fused"):
            collective_mode()

    def test_chunk_knob(self, monkeypatch):
        monkeypatch.setenv(OVERLAP_CHUNKS_ENV, "7")
        assert overlap_chunks() == 7
        monkeypatch.setenv(OVERLAP_CHUNKS_ENV, "junk")
        assert overlap_chunks() == 4  # default on unparseable

    def test_block_sum_no_mesh_equals_plain_sum(self, rng, monkeypatch):
        monkeypatch.setenv(COLLECTIVE_MODE_ENV, "overlap")
        payload = jnp.asarray(rng.normal(size=(4, 37)))
        np.testing.assert_array_equal(
            np.asarray(feature_block_sum(payload)),
            np.asarray(jnp.sum(payload, axis=0)),
        )

    def test_block_sum_chunked_under_mesh(self, rng, devices, monkeypatch):
        monkeypatch.setenv(COLLECTIVE_MODE_ENV, "overlap")
        mesh = make_feature_mesh(1, 4)
        from jax.sharding import NamedSharding, PartitionSpec as P

        payload = jax.device_put(
            jnp.asarray(rng.normal(size=(4, 37))),
            NamedSharding(mesh, P(FEATURE_AXIS, None)),
        )
        with jax.set_mesh(mesh):
            comp = jax.jit(feature_block_sum).lower(payload).compile()
        out = comp(payload)
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(jnp.sum(payload, axis=0)),
            rtol=1e-12,
        )
        # the chunked schedule really is in the program: one collective
        # per chunk (+ the re-replication), not a single trailing op
        colls = count_collectives(comp.as_text())
        assert sum(colls.values()) >= overlap_chunks()


class TestBalancedBlockedLayout:
    """The overlap strategy's row-balanced column-blocked container:
    bit-compatible contractions with the flat layout at every width."""

    @pytest.mark.parametrize("f_shards", WIDTHS)
    def test_kernels_match_flat_layout(self, rng, f_shards):
        sf, _ = _sparse_problem(rng)
        flat = sparse_ops.shard_columns(sf, f_shards)
        bal = sparse_ops.shard_columns(sf, f_shards, balance_rows=True)
        assert bal.is_balanced and bal.aligned_rows == sf.shape[0]
        # the balanced layout exists to shrink padded slots — assert it
        # actually stores fewer than the flat max-width layout
        assert np.prod(bal.indices.shape) < np.prod(flat.indices.shape)
        w = jnp.asarray(rng.normal(size=f_shards * flat.d_shard))
        a = jnp.asarray(rng.normal(size=sf.shape[0]))
        np.testing.assert_allclose(
            np.asarray(sparse_ops.matvec(bal, w)),
            np.asarray(sparse_ops.matvec(flat, w)),
            atol=1e-12,
        )
        np.testing.assert_allclose(
            np.asarray(sparse_ops.rmatvec(bal, a)),
            np.asarray(sparse_ops.rmatvec(flat, a)),
            atol=1e-12,
        )
        np.testing.assert_allclose(
            np.asarray(sparse_ops.colsum(bal, a, square=True)),
            np.asarray(sparse_ops.colsum(flat, a, square=True)),
            atol=1e-12,
        )
        np.testing.assert_allclose(
            sparse_ops.to_dense(bal), sparse_ops.to_dense(flat), atol=1e-12
        )

    @pytest.mark.parametrize("f_shards", WIDTHS)
    def test_bucketed_reduction_matches_across_widths(
        self, rng, f_shards
    ):
        """matvec_and_feature_dots equivalence beyond the historical
        2-device drill: 4/8-block containers, both layouts."""
        sf, _ = _sparse_problem(rng, n=128, d=61, nnz=5)
        w = jnp.asarray(rng.normal(size=0))
        for layout in (False, True):
            fs = sparse_ops.shard_columns(
                sf, f_shards, balance_rows=layout
            )
            d_block = f_shards * fs.d_shard
            w = jnp.asarray(rng.normal(size=d_block))
            u = jnp.asarray(rng.normal(size=d_block))
            z, (du, dw) = sparse_ops.matvec_and_feature_dots(
                fs, w, ((u, w), (w, w))
            )
            np.testing.assert_allclose(
                np.asarray(z),
                np.asarray(sparse_ops.matvec(fs, w)),
                atol=1e-12,
            )
            np.testing.assert_allclose(
                float(du), float(jnp.vdot(u, w)), rtol=1e-12
            )
            np.testing.assert_allclose(
                float(dw), float(jnp.vdot(w, w)), rtol=1e-12
            )

    @pytest.mark.parametrize("f_shards", WIDTHS)
    def test_traced_note_records_width(
        self, rng, devices, f_shards
    ):
        """The bucketed-reduction trace note covers every width (the
        r06 drill only asserted w2)."""
        from photon_ml_tpu import obs
        from photon_ml_tpu.obs.metrics import MetricsRegistry

        reg = MetricsRegistry()
        prev = obs.set_registry(reg)
        try:
            sf, _ = _sparse_problem(rng, n=64, d=32, nnz=4)
            blocked = sparse_ops.shard_columns(sf, f_shards)
            w = jnp.zeros((f_shards * blocked.d_shard,), jnp.float64)

            def fn(w, x):
                z, (dot,) = sparse_ops.matvec_and_feature_dots(
                    x, w, [(w, w)]
                )
                return z.sum() + dot

            jax.jit(fn).lower(w, blocked)
            snap = reg.snapshot()
            key = (
                f"collective.traced.matvec_and_feature_dots.w{f_shards}"
            )
            assert snap["counters"][f"{key}.count"] >= 1
            assert snap["counters"][f"{key}.bytes"] > 0
        finally:
            obs.set_registry(prev)

    @pytest.mark.parametrize("mode", ["fused", "overlap"])
    @pytest.mark.parametrize("f_shards", WIDTHS)
    def test_collective_structure_per_mode(
        self, rng, devices, f_shards, mode, monkeypatch
    ):
        """Compiled-HLO collective counts: the fused oracle keeps ONE
        bucketed all-reduce; the overlap pipeline chunks the reduction
        (>= chunk count collectives, all smaller)."""
        monkeypatch.setenv(COLLECTIVE_MODE_ENV, mode)
        from jax.sharding import NamedSharding, PartitionSpec as P

        sf, y = _sparse_problem(rng, n=256, d=64, nnz=5)
        mesh = make_feature_mesh(1, f_shards)
        blocked = sparse_ops.shard_columns(
            sf, f_shards, balance_rows=(mode == "overlap")
        )
        spec3 = NamedSharding(mesh, P(DATA_AXIS, FEATURE_AXIS, None))
        placed = dataclasses.replace(
            blocked,
            indices=jax.device_put(blocked.indices, spec3),
            values=jax.device_put(blocked.values, spec3),
            row_map=(
                None
                if blocked.row_map is None
                else jax.device_put(
                    blocked.row_map,
                    NamedSharding(mesh, P(None, FEATURE_AXIS)),
                )
            ),
        )
        batch = LabeledBatch.create(placed, y, dtype=jnp.float64)
        w0 = jax.device_put(
            jnp.zeros((f_shards * blocked.d_shard,), jnp.float64),
            NamedSharding(mesh, P(FEATURE_AXIS)),
        )
        obj = GLMObjective(loss=LOGISTIC_LOSS, l2_weight=1.0)
        with jax.set_mesh(mesh):
            comp = (
                jax.jit(lambda w, b: obj.value_and_grad(w, b))
                .lower(w0, batch)
                .compile()
            )
        colls = count_collectives(comp.as_text())
        if mode == "fused":
            assert colls == {"all-reduce": 1}, colls
        else:
            assert sum(colls.values()) >= overlap_chunks(), colls

    @pytest.mark.parametrize("optimizer", ["TRON", "LBFGS"])
    def test_overlap_solve_equals_fused_and_local(
        self, rng, devices, optimizer, monkeypatch
    ):
        """THE equivalence oracle: PHOTON_COLLECTIVE_MODE=overlap ==
        fused == the local unsharded solve (f64 <= 1e-8; the f32 bench
        shape agrees <= 1e-6)."""
        sf, y = _sparse_problem(rng, n=500, d=83, nnz=6)
        batch = LabeledBatch.create(sf, y, dtype=jnp.float64)
        cfg = GLMTrainingConfig(
            task=TaskType.LOGISTIC_REGRESSION,
            optimizer=OptimizerType[optimizer],
            regularization=RegularizationContext("L2"),
            reg_weights=(1.0,),
            max_iters=60,
            tolerance=1e-12,
            track_states=False,
        )
        mesh = make_feature_mesh(1, 8)
        sols = {}
        for mode in ("fused", "overlap"):
            monkeypatch.setenv(COLLECTIVE_MODE_ENV, mode)
            (dist,) = feature_sharded_train_glm(batch, cfg, mesh)
            sols[mode] = np.asarray(dist.model.coefficients.means)
        (local,) = train_glm(batch, cfg)
        np.testing.assert_allclose(
            sols["overlap"], sols["fused"], atol=1e-10
        )
        np.testing.assert_allclose(
            sols["overlap"],
            np.asarray(local.model.coefficients.means),
            atol=1e-8,
        )

    def test_balanced_pads_rows_through_data_axis(
        self, rng, devices, monkeypatch
    ):
        """fused oracle on a (2, 4) mesh (row padding through the
        balanced container is data-axis-sharded only in fused mode;
        overlap requires the feature-only mesh and falls back)."""
        monkeypatch.setenv(COLLECTIVE_MODE_ENV, "overlap")
        sf, y = _sparse_problem(rng, n=401, d=53, nnz=6)
        cfg = GLMTrainingConfig(
            optimizer=OptimizerType.TRON,
            regularization=RegularizationContext("L2"),
            reg_weights=(1.0,),
            max_iters=40,
            tolerance=1e-10,
            track_states=False,
        )
        batch = LabeledBatch.create(sf, y, dtype=jnp.float64)
        (dist,) = feature_sharded_train_glm(
            batch, cfg, make_feature_mesh(2, 4)
        )
        (local,) = train_glm(batch, cfg)
        np.testing.assert_allclose(
            np.asarray(dist.model.coefficients.means),
            np.asarray(local.model.coefficients.means),
            atol=1e-8,
        )


class TestHierarchicalReductions:
    """Two-level ICI-then-DCN reductions on the ('host', 'device') mesh
    (single-process emulation — the same program a pod runs)."""

    def test_hierarchical_psum_equals_flat(self, rng, devices):
        from photon_ml_tpu.parallel.multihost import hierarchical_psum
        from jax.sharding import PartitionSpec as P

        mesh = make_host_device_mesh(2, 4)
        # deliberately awkward payload sizes: scalar, odd-length vector
        # (pads to the intra-axis size), 2-D leaf
        tree = (
            jnp.asarray(rng.normal(size=(16,))),
            {
                "m": jnp.asarray(rng.normal(size=(16, 5))),
                "s": jnp.asarray(rng.normal(size=(16, 3))),
            },
        )

        def flat(x):
            return jtu.tree_map(
                lambda v: jax.lax.psum(
                    jnp.sum(v, axis=0), ("host", "device")
                ),
                x,
            )

        def hier(x):
            return hierarchical_psum(
                jtu.tree_map(lambda v: jnp.sum(v, axis=0), x)
            )

        def run(fn):
            return jax.shard_map(
                fn,
                mesh=mesh,
                in_specs=(
                    jtu.tree_map(lambda v: P(("host", "device")), tree),
                ),
                out_specs=jtu.tree_map(lambda v: P(), tree),
                check_vma=False,
            )(tree)

        out_f = run(flat)
        out_h = run(hier)
        for a, b in zip(jtu.tree_leaves(out_f), jtu.tree_leaves(out_h)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-12
            )

    def test_hierarchical_value_and_grad(self, rng, devices):
        from photon_ml_tpu.parallel.distributed import (
            hierarchical_value_and_grad,
        )

        x = rng.normal(size=(400, 12))
        y = (rng.uniform(size=400) < 0.5).astype(float)
        batch = LabeledBatch.create(x, y, dtype=jnp.float64)
        obj = GLMObjective(loss=LOGISTIC_LOSS, l2_weight=0.7)
        w = jnp.asarray(rng.normal(size=12))
        v_local, g_local = obj.value_and_grad(w, batch)

        mesh = make_host_device_mesh(2, 4)
        sharded = shard_batch(batch, mesh)
        vg = hierarchical_value_and_grad(obj, mesh)
        comp = jax.jit(vg).lower(w, sharded).compile()
        v_h, g_h = comp(w, sharded)
        np.testing.assert_allclose(float(v_h), float(v_local), rtol=1e-12)
        np.testing.assert_allclose(
            np.asarray(g_h), np.asarray(g_local), rtol=1e-10
        )
        # the HIERARCHY is really in the program: reduce-scatter (intra)
        # + all-reduce (inter) + all-gather (intra), not one flat psum
        colls = count_collectives(comp.as_text())
        assert colls.get("reduce-scatter", 0) >= 1, colls
        assert colls.get("all-gather", 0) >= 1, colls

        # flat psum oracle on the 1-D mesh
        vg_flat = shard_map_value_and_grad(obj, make_mesh())
        v_f, g_f = jax.jit(vg_flat)(
            w, shard_batch(batch, make_mesh())
        )
        np.testing.assert_allclose(float(v_h), float(v_f), rtol=1e-12)
        np.testing.assert_allclose(
            np.asarray(g_h), np.asarray(g_f), rtol=1e-10
        )

    def test_rejects_wrong_mesh(self, rng, devices):
        from photon_ml_tpu.parallel.distributed import (
            hierarchical_value_and_grad,
        )

        obj = GLMObjective(loss=LOGISTIC_LOSS)
        with pytest.raises(ValueError, match="host"):
            hierarchical_value_and_grad(obj, make_mesh())


def _mixed_effects(rng, n_users=17, rows_per_user=11):
    import sys

    sys.path.insert(0, "tests")
    from test_game import make_mixed_effects_data

    return make_mixed_effects_data(
        rng, n_users=n_users, rows_per_user=rows_per_user
    )


def _build_local_cd(data, n_users, fe_cfg, re_cfg):
    from photon_ml_tpu.game import (
        CoordinateDescent,
        FixedEffectCoordinate,
        RandomEffectCoordinate,
        build_bucketed_random_effect_design,
    )

    design = build_bucketed_random_effect_design(
        data, "userId", "per_user", n_users, num_buckets=2,
        dtype=jnp.float64,
    )
    fe = FixedEffectCoordinate(
        data.fixed_effect_batch("global", jnp.float64), fe_cfg
    )
    re = RandomEffectCoordinate(
        design=design,
        row_features=jnp.asarray(data.features["per_user"], jnp.float64),
        row_entities=jnp.asarray(data.entity_ids["userId"]),
        full_offsets_base=jnp.asarray(data.offsets, jnp.float64),
        config=re_cfg,
    )
    return CoordinateDescent(
        {"fixed": fe, "per-user": re},
        labels=jnp.asarray(data.labels, jnp.float64),
        base_offsets=jnp.asarray(data.offsets, jnp.float64),
        weights=jnp.asarray(data.weights, jnp.float64),
        task=TaskType.LOGISTIC_REGRESSION,
    )


def _build_sharded_cd(data, n_users, n_shards, fe_cfg, re_cfg, **run_kw):
    from photon_ml_tpu.game import (
        CoordinateDescent,
        EntityShardedRandomEffectCoordinate,
        FixedEffectCoordinate,
        build_bucketed_random_effect_design,
        entity_partition_game_data,
        entity_shard_assignment,
    )

    mesh = make_entity_mesh(n_shards, devices=jax.devices()[:n_shards])
    assignment = entity_shard_assignment(n_users, n_shards)
    pdata, part = entity_partition_game_data(data, "userId", assignment)
    design = build_bucketed_random_effect_design(
        pdata, "userId", "per_user", n_users, num_buckets=2,
        dtype=jnp.float64,
    )
    put = lambda x: jax.device_put(
        jnp.asarray(x), batch_sharding(mesh, np.ndim(x))
    )
    fe_batch = jtu.tree_map(
        lambda x: jax.device_put(
            x, batch_sharding(mesh, np.ndim(x))
        ),
        pdata.fixed_effect_batch("global", jnp.float64),
    )
    fe = FixedEffectCoordinate(fe_batch, fe_cfg)
    re = EntityShardedRandomEffectCoordinate(
        design=design,
        row_features=jnp.asarray(pdata.features["per_user"], jnp.float64),
        row_entities=jnp.asarray(pdata.entity_ids["userId"]),
        full_offsets_base=jnp.asarray(pdata.offsets, jnp.float64),
        config=re_cfg,
        mesh=mesh,
        assignment=assignment,
        partition=part,
    )
    cd = CoordinateDescent(
        {"fixed": fe, "per-user": re},
        labels=put(pdata.labels),
        base_offsets=put(pdata.offsets),
        weights=put(pdata.weights),
        task=TaskType.LOGISTIC_REGRESSION,
    )
    return cd, re, part, assignment


_FE_CFG = dict(shard="global", reg_weight=0.1, max_iters=25, tolerance=1e-10)
_RE_CFG = dict(
    shard="per_user",
    random_effect="userId",
    reg_weight=0.5,
    max_iters=25,
    tolerance=1e-10,
)


class TestEntityShardedGame:
    """shard_map'd GAME: entity-sharded descent == single-device descent
    <= 1e-10, with ZERO collectives in the random-effect update."""

    @pytest.mark.parametrize("n_shards", WIDTHS)
    def test_matches_unsharded(self, rng, devices, n_shards):
        from photon_ml_tpu.game import CoordinateConfig

        fe_cfg = CoordinateConfig(**_FE_CFG)
        re_cfg = CoordinateConfig(**_RE_CFG)
        # 17 entities: the remainder case for every width drilled here
        data, _, n_users = _mixed_effects(rng, n_users=17)
        m_local, h_local = _build_local_cd(
            data, n_users, fe_cfg, re_cfg
        ).run(num_iterations=2)
        cd, re, part, assignment = _build_sharded_cd(
            data, n_users, n_shards, fe_cfg, re_cfg
        )
        m_sh, h_sh = cd.run(num_iterations=2)
        np.testing.assert_allclose(
            np.asarray(m_sh.params["fixed"]),
            np.asarray(m_local.params["fixed"]),
            atol=1e-10,
        )
        np.testing.assert_allclose(
            re.global_table(m_sh.params["per-user"]),
            np.asarray(m_local.params["per-user"]),
            atol=1e-10,
        )
        np.testing.assert_allclose(
            h_sh[-1].objective, h_local[-1].objective, rtol=1e-10
        )

    @pytest.mark.parametrize("n_shards", [2, 4])
    def test_newton_matches_unsharded(self, rng, devices, n_shards):
        """The batched Newton solve under ``shard_map`` (its small-d
        algebra entity-minor: ``hessian_row_sum`` and the unrolled
        Cholesky solve) gives the unsharded coordinate's numbers, with
        every shard's buckets booked as ``entity_minor``."""
        from photon_ml_tpu import obs
        from photon_ml_tpu.game import CoordinateConfig
        from photon_ml_tpu.models.training import OptimizerType

        from photon_ml_tpu.game import coordinates as coordinates_mod

        # a solve traced by an earlier test would book nothing here
        coordinates_mod._make_solve_cached.cache_clear()
        newton = dict(optimizer=OptimizerType.NEWTON, max_iters=6)
        fe_cfg = CoordinateConfig(**{**_FE_CFG, **newton})
        re_cfg = CoordinateConfig(**{**_RE_CFG, **newton})
        data, _, n_users = _mixed_effects(rng, n_users=17)
        m_local, h_local = _build_local_cd(
            data, n_users, fe_cfg, re_cfg
        ).run(num_iterations=2)
        cd, re, _, _ = _build_sharded_cd(
            data, n_users, n_shards, fe_cfg, re_cfg
        )
        m_sh, h_sh = cd.run(num_iterations=2)
        np.testing.assert_allclose(
            np.asarray(m_sh.params["fixed"]),
            np.asarray(m_local.params["fixed"]),
            atol=1e-10,
        )
        np.testing.assert_allclose(
            re.global_table(m_sh.params["per-user"]),
            np.asarray(m_local.params["per-user"]),
            atol=1e-10,
        )
        np.testing.assert_allclose(
            h_sh[-1].objective, h_local[-1].objective, rtol=1e-10
        )
        layouts = {
            s[6]["layout"] for s in obs.recent_spans()
            if s[0] == "game.solve_layout"
        }
        assert layouts == {"entity_minor"}

    def test_zero_collectives_in_re_update(self, rng, devices):
        from photon_ml_tpu.game import CoordinateConfig

        data, _, n_users = _mixed_effects(rng, n_users=16)
        cd, re, part, _ = _build_sharded_cd(
            data, n_users, 4,
            CoordinateConfig(**_FE_CFG), CoordinateConfig(**_RE_CFG),
        )
        table0 = re.initial_params()
        ps = jax.device_put(
            jnp.zeros(part.padded_rows),
            batch_sharding(re.mesh, 1),
        )
        comp = re._update_all.lower(
            table0,
            re.reg_weights,
            re.full_offsets_base + ps,
            re._entity_indices,
            re._buckets,
            re.row_features,
            re.row_entities_local,
        ).compile()
        assert count_collectives(comp.as_text()) == {}

    def test_superpass_composes(self, rng, devices):
        """The shard_map'd coordinate rides the PR-8 superpass (K passes
        per dispatch) with identical results."""
        from photon_ml_tpu.game import CoordinateConfig

        data, _, n_users = _mixed_effects(rng, n_users=8)
        fe_cfg = CoordinateConfig(**_FE_CFG)
        re_cfg = CoordinateConfig(**_RE_CFG)
        cd1, re1, _, _ = _build_sharded_cd(
            data, n_users, 2, fe_cfg, re_cfg
        )
        m1, _ = cd1.run(num_iterations=4)
        cd2, re2, _, _ = _build_sharded_cd(
            data, n_users, 2, fe_cfg, re_cfg
        )
        m2, _ = cd2.run(num_iterations=4, passes_per_dispatch=2)
        np.testing.assert_allclose(
            np.asarray(m1.params["per-user"]),
            np.asarray(m2.params["per-user"]),
            atol=1e-12,
        )

    def test_shard_layout_matches_checkpoint_rule(self, devices):
        """The device ownership rule IS the sharded-checkpoint row rule
        (io.checkpoint.shard_rows) — the layouts cannot drift."""
        from photon_ml_tpu.game import entity_shard_assignment
        from photon_ml_tpu.io.checkpoint import shard_rows

        for e, p_count in ((17, 4), (16, 4), (5, 8)):
            assignment = entity_shard_assignment(e, p_count)
            for p in range(p_count):
                lo = p * assignment.rows_per_shard
                hi = lo + assignment.rows_per_shard
                stored = assignment.stored_to_global[lo:hi]
                expect = list(shard_rows(e, p, p_count))
                got = [int(g) for g in stored if g < e]
                assert got == expect

    def test_resume_sharded_checkpoint_at_different_width(
        self, rng, devices, tmp_path
    ):
        """Train 2 passes at width 2 with sharded checkpoints, resume at
        width 4: the continued run equals the uninterrupted width-2 run
        <= 1e-10 (entity-keyed restore re-keys the stored tables)."""
        from photon_ml_tpu.game import CoordinateConfig

        fe_cfg = CoordinateConfig(**_FE_CFG)
        re_cfg = CoordinateConfig(**_RE_CFG)
        data, _, n_users = _mixed_effects(rng, n_users=10)
        keys = [f"user:{i}" for i in range(n_users)]
        ckpt = str(tmp_path / "ckpt")

        def run(n_shards, iters, resume):
            cd, re, part, assignment = _build_sharded_cd(
                data, n_users, n_shards, fe_cfg, re_cfg
            )
            model, _ = cd.run(
                num_iterations=iters,
                checkpoint_dir=ckpt,
                checkpoint_every=1,
                resume=resume,
                sharded_checkpoints=n_shards,
                entity_keys={
                    "per-user": assignment.stored_entity_keys(keys)
                },
            )
            return re.global_table(model.params["per-user"]), np.asarray(
                model.params["fixed"]
            )

        run(2, 2, resume=False)  # 2 passes at width 2, checkpointed
        table_resumed, fixed_resumed = run(4, 4, resume=True)

        import shutil

        shutil.rmtree(ckpt)
        cd, re, _, assignment = _build_sharded_cd(
            data, n_users, 2, fe_cfg, re_cfg
        )
        model_full, _ = cd.run(num_iterations=4)
        np.testing.assert_allclose(
            table_resumed,
            re.global_table(model_full.params["per-user"]),
            atol=1e-10,
        )
        np.testing.assert_allclose(
            fixed_resumed, np.asarray(model_full.params["fixed"]),
            atol=1e-10,
        )


class TestSentinelAndTaxonomy:
    def test_raised_scaling_floors(self):
        from photon_ml_tpu.obs.sentinel import metric_floor

        assert metric_floor(
            "extra.sparse_fs_scaling.2.scaling_efficiency"
        ) == pytest.approx(0.25)
        assert metric_floor(
            "extra.sparse_fs_scaling.4.scaling_efficiency"
        ) == pytest.approx(0.12)
        assert metric_floor(
            "extra.sparse_fs_scaling.8.scaling_efficiency"
        ) == pytest.approx(0.055)
        # every raised floor is ABOVE the old 0.25/N rule
        for w, floor in ((2, 0.25), (4, 0.12), (8, 0.055)):
            assert floor > 0.25 / w

    def test_wall_frac_direction(self):
        from photon_ml_tpu.obs.sentinel import (
            LOWER_IS_BETTER,
            metric_direction,
        )

        assert (
            metric_direction("extra.bench_overlap.8.collective_wall_frac")
            == LOWER_IS_BETTER
        )
        assert (
            metric_direction(
                "collective.overlap.objective_pass.w8.wall_frac"
            )
            == LOWER_IS_BETTER
        )

    def test_taxonomy_binds_new_names(self):
        from photon_ml_tpu.obs import taxonomy

        assert taxonomy.matches("partition.entity_layout")
        assert taxonomy.matches(
            "collective.overlap.objective_pass.w8.wall_frac"
        )

    def test_collective_share_gauge(self):
        from photon_ml_tpu.obs.collectives import record_collective_share
        from photon_ml_tpu.obs.metrics import MetricsRegistry

        reg = MetricsRegistry()
        frac = record_collective_share(
            "overlap.objective_pass",
            mesh_width=4,
            collective_wall_s=0.05,
            pass_wall_s=0.2,
            registry=reg,
        )
        assert frac == pytest.approx(0.25)
        snap = reg.snapshot()
        assert snap["gauges"][
            "collective.overlap.objective_pass.w4.wall_frac"
        ] == pytest.approx(0.25)
        # degenerate pass wall: clamps instead of dividing by zero
        assert (
            record_collective_share("x.y", 2, 1.0, 0.0, registry=reg)
            == 0.0
        )


class TestShardSkewDrill:
    def test_shard_skew_drill_passes(self, devices):
        from photon_ml_tpu.resilience.drills import DRILLS

        out = DRILLS["shard_skew"](True)
        assert out["stalls_recorded"] >= 1
        assert out["skew_recovery_s"] < 1.9
        assert out["sharded_run_completed"] is True


class TestBalancedNormalization:
    def test_overlap_standardization_matches_local(
        self, rng, devices, monkeypatch
    ):
        """STANDARDIZATION over the balanced layout on a (1, 8) mesh:
        the blocked statistics path (feature_sharded_as_ell rebuilds
        host-side through the row map) + the shift algebra riding the
        bucketed reduction."""
        from photon_ml_tpu.core.normalization import NormalizationType

        monkeypatch.setenv(COLLECTIVE_MODE_ENV, "overlap")
        d = 31
        rng2 = np.random.default_rng(5)
        sf, y = _sparse_problem(rng2, n=400, d=d, nnz=5)
        # intercept column so standardization has its anchor
        ind = np.asarray(sf.indices)
        val = np.asarray(sf.values)
        ind = np.concatenate(
            [ind, np.full((400, 1), d - 1, ind.dtype)], axis=1
        )
        val = np.concatenate([val, np.ones((400, 1))], axis=1)
        sf = sparse_ops.SparseFeatures(
            indices=jnp.asarray(ind), values=jnp.asarray(val), d=d
        )
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
        batch = LabeledBatch.create(sf, y, dtype=jnp.float64)
        (dist,) = feature_sharded_train_glm(
            batch, cfg, make_feature_mesh(1, 8)
        )
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
