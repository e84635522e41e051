"""Pallas sparse-kernel suite drills: interpret-mode equivalence vs the
XLA ELL lowering (tier-1 CPU proves kernel semantics — `pallas` marker),
dispatch eligibility, fused-pass design-read accounting, and the
feature-sharded bucketed reduction.

Tolerances per ISSUE 5: f32 <= 1e-6 (relative), bf16 <= 1e-2. Edge
shapes: all-padding rows, d not a multiple of the 128-lane tile,
nnz_per_row=1, empty batch, duplicate columns within a row, and the
``HybridFeatures`` cold slab.
"""

import contextlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import kernels
from photon_ml_tpu.core.normalization import (
    NormalizationContext,
    no_normalization,
)
from photon_ml_tpu.core.types import LabeledBatch
from photon_ml_tpu.kernels import dispatch
from photon_ml_tpu.ops.losses import LOGISTIC_LOSS
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.ops.sparse import (
    SparseFeatures,
    colsum,
    from_coo,
    matvec,
    matvec_and_feature_dots,
    rmatvec,
    shard_columns,
    to_hybrid,
)

pytestmark = pytest.mark.pallas


@contextlib.contextmanager
def kernel_mode(mode):
    """Pin PHOTON_SPARSE_KERNEL for a block."""
    old = os.environ.get(dispatch.ENV_VAR)
    os.environ[dispatch.ENV_VAR] = mode
    try:
        yield
    finally:
        if old is None:
            os.environ.pop(dispatch.ENV_VAR, None)
        else:
            os.environ[dispatch.ENV_VAR] = old


def _random_ell(rng, n, k, d, dtype=np.float32, pad_rows=0, dup_row=False):
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


def _ops_both_modes(sf, w, a, c):
    """(matvec, rmatvec, colsum, colsum-squared) under the active mode."""
    return (
        np.asarray(matvec(sf, w)),
        np.asarray(rmatvec(sf, a)),
        np.asarray(colsum(sf, c)),
        np.asarray(colsum(sf, c, square=True)),
    )


EDGE_SHAPES = [
    # (n, k, d, pad_rows, dup_row) — d=300/157 break the 128-lane tile
    (37, 5, 300, 0, False),
    (37, 5, 300, 7, False),  # leading all-padding rows
    (16, 4, 300, 16, False),  # EVERY row is padding
    (23, 1, 157, 0, False),  # nnz_per_row=1
    (12, 6, 157, 0, True),  # duplicate columns within a row
    (9, 3, 1, 0, False),  # single-column design
    (40, 8, 128, 0, False),  # d exactly one lane tile
]


class TestEllKernelEquivalence:
    @pytest.mark.parametrize("n,k,d,pad,dup", EDGE_SHAPES)
    def test_f32_matches_xla(self, rng, n, k, d, pad, dup):
        sf = _random_ell(rng, n, k, d, pad_rows=pad, dup_row=dup)
        w = jnp.asarray(rng.standard_normal(d).astype(np.float32))
        a = jnp.asarray(rng.standard_normal(n).astype(np.float32))
        c = jnp.asarray(rng.uniform(0.1, 1.0, n).astype(np.float32))
        with kernel_mode("xla"):
            ref = _ops_both_modes(sf, w, a, c)
        with kernel_mode("pallas"):
            got = _ops_both_modes(sf, w, a, c)
        for r, g in zip(ref, got):
            np.testing.assert_allclose(g, r, rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("w_dtype", [np.float32, jnp.bfloat16])
    def test_bf16_values_match_xla(self, rng, w_dtype):
        n, k, d = 33, 4, 270
        sf = _random_ell(rng, n, k, d)
        sf = SparseFeatures(
            indices=sf.indices, values=sf.values.astype(jnp.bfloat16), d=d
        )
        w = jnp.asarray(rng.standard_normal(d), dtype=w_dtype)
        a = jnp.asarray(rng.standard_normal(n), dtype=w_dtype)
        c = jnp.asarray(rng.uniform(0.1, 1.0, n), dtype=w_dtype)
        with kernel_mode("xla"):
            ref = _ops_both_modes(sf, w, a, c)
        with kernel_mode("pallas"):
            got = _ops_both_modes(sf, w, a, c)
        for r, g in zip(ref, got):
            scale = max(1.0, float(np.max(np.abs(r.astype(np.float64)))))
            np.testing.assert_allclose(
                g.astype(np.float64),
                r.astype(np.float64),
                atol=1e-2 * scale,
            )

    def test_empty_batch_dispatches_to_xla_result(self, rng):
        # n=0 is excluded from Pallas eligibility; the public ops must
        # still return the exact XLA result under forced pallas mode
        sf = _random_ell(rng, 0, 4, 90)
        w = jnp.asarray(rng.standard_normal(90).astype(np.float32))
        a = jnp.zeros((0,), jnp.float32)
        with kernel_mode("pallas"):
            assert matvec(sf, w).shape == (0,)
            g = np.asarray(rmatvec(sf, a))
            s = np.asarray(colsum(sf, a))
        assert g.shape == (90,) and not g.any()
        assert s.shape == (90,) and not s.any()

    def test_hybrid_cold_segments_are_xla_in_every_mode(self, rng):
        # Zipf-ish columns so to_hybrid finds a hot head; the cold
        # segments are contracted by one flat XLA gather / scatter-add
        # whatever the mode (the suite's kernels take one ELL at a time)
        n, k, d = 60, 6, 210
        zr = rng.zipf(1.3, size=(n, k))
        cols = ((zr - 1) % d).astype(np.int64)
        vals = rng.standard_normal((n, k)).astype(np.float32)
        rows = np.repeat(np.arange(n), k)
        sf = from_coo(rows, cols.ravel(), vals.ravel(), n, d)
        hf = to_hybrid(sf, hot_columns=8)
        w = jnp.asarray(rng.standard_normal(d).astype(np.float32))
        a = jnp.asarray(rng.standard_normal(n).astype(np.float32))
        with kernel_mode("xla"):
            ref = _ops_both_modes(hf, w, a, a)
        with kernel_mode("pallas"):
            got = _ops_both_modes(hf, w, a, a)
        for r, g in zip(ref, got):
            np.testing.assert_allclose(g, r, rtol=1e-6, atol=1e-6)

    def test_auto_is_bitwise_xla(self, rng):
        # PHOTON_SPARSE_KERNEL=auto never changes a bit relative to the
        # XLA lowering (the dispatch rule: auto == xla on every platform)
        sf = _random_ell(rng, 41, 5, 230)
        w = jnp.asarray(rng.standard_normal(230).astype(np.float32))
        a = jnp.asarray(rng.standard_normal(41).astype(np.float32))
        with kernel_mode("xla"):
            ref = _ops_both_modes(sf, w, a, a)
        with kernel_mode("auto"):
            got = _ops_both_modes(sf, w, a, a)
        for r, g in zip(ref, got):
            np.testing.assert_array_equal(g, r)


def _objective(l2=0.5, norm=None):
    return GLMObjective(
        loss=LOGISTIC_LOSS,
        normalization=norm if norm is not None else no_normalization(),
        l2_weight=l2,
    )


def _batch(rng, sf, n):
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    off = rng.standard_normal(n).astype(np.float32) * 0.1
    wgt = rng.uniform(0.5, 2.0, n).astype(np.float32)
    return LabeledBatch.create(sf, y, offsets=off, weights=wgt)


class TestFusedObjectivePasses:
    @pytest.mark.parametrize("with_norm", [False, True])
    def test_value_grad_curvature(self, rng, with_norm):
        n, k, d = 48, 4, 190
        sf = _random_ell(rng, n, k, d, pad_rows=3)
        batch = _batch(rng, sf, n)
        norm = None
        if with_norm:
            norm = NormalizationContext(
                factors=jnp.asarray(
                    rng.uniform(0.5, 2.0, d).astype(np.float32)
                ),
                shifts=jnp.asarray(
                    (rng.standard_normal(d) * 0.05).astype(np.float32)
                ),
            )
        obj = _objective(norm=norm)
        w = jnp.asarray(rng.standard_normal(d).astype(np.float32) * 0.1)
        with kernel_mode("xla"):
            v0, g0, c0 = obj.value_grad_curvature(w, batch)
        with kernel_mode("pallas"):
            assert obj._use_fused_kernel(batch.features, w.dtype)
            v1, g1, c1 = obj.value_grad_curvature(w, batch)
        np.testing.assert_allclose(
            float(v1), float(v0), rtol=1e-6, atol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(g1), np.asarray(g0), rtol=1e-6, atol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(c1), np.asarray(c0), rtol=1e-6, atol=1e-6
        )

    @pytest.mark.parametrize("with_norm", [False, True])
    def test_hessian_vector(self, rng, with_norm):
        n, k, d = 32, 5, 140
        sf = _random_ell(rng, n, k, d)
        batch = _batch(rng, sf, n)
        norm = None
        if with_norm:
            norm = NormalizationContext(
                factors=jnp.asarray(
                    rng.uniform(0.5, 2.0, d).astype(np.float32)
                ),
                shifts=jnp.asarray(
                    (rng.standard_normal(d) * 0.05).astype(np.float32)
                ),
            )
        obj = _objective(norm=norm)
        w = jnp.asarray(rng.standard_normal(d).astype(np.float32) * 0.1)
        v = jnp.asarray(rng.standard_normal(d).astype(np.float32))
        with kernel_mode("xla"):
            _, _, c = obj.value_grad_curvature(w, batch)
            hv0 = obj.hessian_vector_at(c, v, batch)
        with kernel_mode("pallas"):
            hv1 = obj.hessian_vector_at(c, v, batch)
        np.testing.assert_allclose(
            np.asarray(hv1), np.asarray(hv0), rtol=1e-6, atol=1e-6
        )

    @pytest.mark.parametrize("with_norm", [False, True])
    def test_hessian_diagonal(self, rng, with_norm):
        n, k, d = 32, 5, 140
        sf = _random_ell(rng, n, k, d, dup_row=True)
        batch = _batch(rng, sf, n)
        norm = None
        if with_norm:
            norm = NormalizationContext(
                factors=jnp.asarray(
                    rng.uniform(0.5, 2.0, d).astype(np.float32)
                ),
                shifts=jnp.asarray(
                    (rng.standard_normal(d) * 0.05).astype(np.float32)
                ),
            )
        obj = _objective(norm=norm)
        w = jnp.asarray(rng.standard_normal(d).astype(np.float32) * 0.1)
        with kernel_mode("xla"):
            d0 = obj.hessian_diagonal(w, batch)
        with kernel_mode("pallas"):
            d1 = obj.hessian_diagonal(w, batch)
        np.testing.assert_allclose(
            np.asarray(d1), np.asarray(d0), rtol=1e-6, atol=1e-6
        )

    def test_solver_end_to_end_matches_xla(self, rng):
        # whole LBFGS solve through the fused passes: coefficients agree
        # with the XLA-path solve to solver precision
        from photon_ml_tpu.models import (
            GLMTrainingConfig,
            OptimizerType,
            TaskType,
            train_glm,
        )
        from photon_ml_tpu.ops import RegularizationContext

        n, k, d = 120, 6, 260
        sf = _random_ell(rng, n, k, d)
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        batch = LabeledBatch.create(sf, y)
        cfg = GLMTrainingConfig(
            task=TaskType.LOGISTIC_REGRESSION,
            optimizer=OptimizerType.TRON,
            regularization=RegularizationContext("L2"),
            reg_weights=(1.0,),
            tolerance=1e-8,
            max_iters=30,
            track_states=False,
        )
        with kernel_mode("xla"):
            (tm0,) = train_glm(batch, cfg)
        with kernel_mode("pallas"):
            (tm1,) = train_glm(batch, cfg)
        np.testing.assert_allclose(
            np.asarray(tm1.model.coefficients.means),
            np.asarray(tm0.model.coefficients.means),
            rtol=1e-4,
            atol=1e-5,
        )


class TestDesignReadAccounting:
    def test_fused_pass_saves_two_design_reads(self):
        # acceptance: the fused pass performs >= 2 fewer design reads per
        # TRON iteration than the matvec+rmatvec+colsum sequence
        seq = (
            dispatch.design_reads("ell_matvec")
            + dispatch.design_reads("ell_rmatvec")
            + dispatch.design_reads("ell_colsum")
        )
        assert seq - dispatch.design_reads("fused_vgc") >= 2
        assert seq - dispatch.design_reads("fused_hdiag") >= 2
        assert dispatch.design_reads("fused_hvp") == 1

    def test_cost_book_pins_one_design_read(self, rng):
        # the booked roofline traffic of a fused pass is exactly ONE
        # stored-design read (indices + values), counted via CostBook
        from photon_ml_tpu.obs.xla_cost import (
            CostBook,
            cost_book,
            set_cost_book,
        )

        n, k, d = 29, 3, 113  # unique shape: dodge the once-per-key dedup
        sf = _random_ell(rng, n, k, d)
        batch = _batch(rng, sf, n)
        obj = _objective()
        w = jnp.zeros((d,), jnp.float32)
        prior = cost_book()
        set_cost_book(CostBook())
        try:
            with dispatch._record_lock:
                dispatch._recorded.clear()
            with kernel_mode("pallas"):
                obj.value_grad_curvature(w, batch)
                matvec(sf, w)
                rmatvec(sf, jnp.zeros((n,), jnp.float32))
                colsum(sf, jnp.zeros((n,), jnp.float32))
            book = cost_book()
            design_bytes = n * k * (4 + 4)  # int32 ids + f32 payload
            fused = book.lookup("kernels.fused_vgc", f"{n}x{k}x{d}")
            assert fused is not None
            assert fused.roofline_bytes == pytest.approx(design_bytes)
            per_op = sum(
                book.lookup(f"kernels.{kn}", f"{n}x{k}x{d}").roofline_bytes
                for kn in ("ell_matvec", "ell_rmatvec", "ell_colsum")
            )
            # the sequence the fused pass replaces costs >= 2 more reads
            assert per_op - fused.roofline_bytes >= 2 * design_bytes
        finally:
            set_cost_book(prior)


class TestDispatch:
    def test_invalid_mode_raises(self):
        with kernel_mode("mosaic"):
            with pytest.raises(ValueError, match="PHOTON_SPARSE_KERNEL"):
                dispatch.kernel_mode()

    def test_xla_mode_pins_xla(self):
        with kernel_mode("xla"):
            assert not dispatch.use_pallas(d=100, n=10, nnz_per_row=4)

    def test_degenerate_shapes_stay_xla(self):
        with kernel_mode("pallas"):
            assert not dispatch.use_pallas(d=100, n=0, nnz_per_row=4)
            assert not dispatch.use_pallas(d=100, n=10, nnz_per_row=0)
            assert dispatch.use_pallas(d=100, n=10, nnz_per_row=4)

    def test_vmem_cap_excludes_wide_tables(self):
        old = os.environ.get(dispatch.VMEM_CAP_ENV)
        os.environ[dispatch.VMEM_CAP_ENV] = str(64 << 10)  # 64 KiB
        try:
            with kernel_mode("pallas"):
                assert dispatch.use_pallas(d=1_000, n=10, nnz_per_row=4)
                assert not dispatch.use_pallas(
                    d=1_000_000, n=10, nnz_per_row=4
                )
        finally:
            if old is None:
                os.environ.pop(dispatch.VMEM_CAP_ENV, None)
            else:
                os.environ[dispatch.VMEM_CAP_ENV] = old

    def test_active_mesh_excludes_pallas(self, devices):
        from photon_ml_tpu.parallel import make_feature_mesh

        with kernel_mode("pallas"):
            assert dispatch.use_pallas(d=100, n=10, nnz_per_row=4)
            with jax.set_mesh(make_feature_mesh(1, 2)):
                assert not dispatch.use_pallas(d=100, n=10, nnz_per_row=4)

    @pytest.mark.parametrize("backend", ["cpu", "tpu"])
    def test_auto_selects_xla_by_rule_on_every_platform(
        self, monkeypatch, backend
    ):
        # the rule, not a probe: `auto` answers from the mode alone —
        # no kernel is built or run to find out, on TPU or off it
        # (TestTpuLowering records why TPU cannot take the suite today)
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        monkeypatch.setattr(
            kernels.ell,
            "ell_matvec",
            lambda *a, **k: pytest.fail("dispatch probed a kernel"),
        )
        with kernel_mode("auto"):
            assert not dispatch.use_pallas(d=100, n=10, nnz_per_row=4)
        with kernel_mode("pallas"):
            # forced means forced: on TPU the lowering error surfaces
            assert dispatch.use_pallas(d=100, n=10, nnz_per_row=4)

    def test_sentinel_tracks_kernel_microbench(self):
        from photon_ml_tpu.obs.sentinel import (
            LOWER_IS_BETTER,
            metric_direction,
        )

        for kn in ("matvec", "rmatvec", "colsum", "fused"):
            for backend in ("xla", "pallas"):
                assert (
                    metric_direction(f"sparse_pass_ms.{kn}.{backend}_ms")
                    == LOWER_IS_BETTER
                )


# The shape chip_smoke.py's sparse phase runs (width and nnz/row of the
# bench's sparse cell; rows cut to a few row blocks).
_TPU_N, _TPU_K, _TPU_D = 2048, 32, 120_000

_GATHER_MSG = "Only 2D gather is supported"
_DYNAMIC_SLICE_MSG = (
    "Unimplemented primitive in Pallas TPU lowering for KernelType.TC: "
    "dynamic_slice"
)


def _tpu_lowering_cases():
    from photon_ml_tpu.kernels import ell, fused

    d = _TPU_D
    idx = jax.ShapeDtypeStruct((_TPU_N, _TPU_K), jnp.int32)
    val = jax.ShapeDtypeStruct((_TPU_N, _TPU_K), jnp.float32)
    w = jax.ShapeDtypeStruct((d,), jnp.float32)
    r = jax.ShapeDtypeStruct((_TPU_N,), jnp.float32)
    return {
        "ell_matvec": (
            lambda i, v, ww: ell.ell_matvec(i, v, ww, d),
            (idx, val, w),
            _GATHER_MSG,
        ),
        "ell_rmatvec": (
            lambda i, v, a: ell.ell_rmatvec(i, v, a, d),
            (idx, val, r),
            _DYNAMIC_SLICE_MSG,
        ),
        "ell_colsum": (
            lambda i, v, a: ell.ell_colsum(i, v, a, d, square=True),
            (idx, val, r),
            _DYNAMIC_SLICE_MSG,
        ),
        "fused_vgc": (
            lambda i, v, y, o, e, ww: fused.fused_value_grad_curvature(
                i, v, y, o, e, ww, d, LOGISTIC_LOSS
            ),
            (idx, val, r, r, r, w),
            _GATHER_MSG,
        ),
        "fused_hvp": (
            lambda i, v, c, vv: fused.fused_hessian_vector(
                i, v, c, vv, jnp.float32(0.0), d
            ),
            (idx, val, r, w),
            _GATHER_MSG,
        ),
        "fused_hdiag": (
            lambda i, v, y, o, e, ww: fused.fused_hessian_diagonal(
                i, v, y, o, e, ww, d, LOGISTIC_LOSS
            ),
            (idx, val, r, r, r, w),
            _GATHER_MSG,
        ),
    }


class TestTpuLowering:
    """CPU guard on what the TPU would be handed: each Pallas kernel is
    lowered for platform "tpu" (``jax.export``, interpret OFF) at a
    real-width shape. Today none of the six gets past the Pallas->Mosaic
    lowering on jax 0.9.0 — which is WHY ``kernels.dispatch`` sends
    ``auto`` to XLA on TPU. The xfails are strict: a repair that makes a
    kernel lower turns its test red, and whoever repairs it must revisit
    the dispatch rule (and docs/KERNELS.md) in the same change. Any
    kernel the TPU rule selects must pass here un-xfailed."""

    @pytest.mark.xfail(
        strict=True,
        raises=NotImplementedError,
        reason="Pallas TPU lowering (jax 0.9.0): in-kernel 1-D table "
        "gather / per-row dynamic_slice are unimplemented",
    )
    @pytest.mark.parametrize(
        "kernel",
        [
            "ell_matvec",
            "ell_rmatvec",
            "ell_colsum",
            "fused_vgc",
            "fused_hvp",
            "fused_hdiag",
        ],
    )
    def test_kernel_lowers_for_tpu(self, monkeypatch, kernel):
        monkeypatch.setattr(dispatch, "interpret_mode", lambda: False)
        fn, args, recorded = _tpu_lowering_cases()[kernel]
        try:
            # x64 off, as the drivers run on the chip (the suite's
            # conftest turns it on for the float64 oracles)
            with jax.enable_x64(False):
                jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
        except NotImplementedError as e:
            # pin the RECORDED error: a different one is a new failure
            # mode, not the known one, and must not hide in the xfail
            assert recorded in str(e), str(e)
            raise

    def test_tpu_rule_selects_no_unlowerable_kernel(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.delenv(dispatch.ENV_VAR, raising=False)
        assert not dispatch.use_pallas(
            d=_TPU_D, n=_TPU_N, nnz_per_row=_TPU_K
        )


class TestFeatureShardedBucketedReduction:
    def test_unsharded_is_bit_identical(self, rng):
        sf = _random_ell(rng, 21, 4, 97)
        w = jnp.asarray(rng.standard_normal(97).astype(np.float32))
        u = jnp.asarray(rng.standard_normal(97).astype(np.float32))
        with kernel_mode("xla"):
            z, (du, dw) = matvec_and_feature_dots(
                sf, w, ((u, w), (w, w))
            )
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
        sf = _random_ell(rng, n, k, d)
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
        import dataclasses as dc

        from jax.sharding import NamedSharding, PartitionSpec as P

        from photon_ml_tpu.obs.xla_cost import count_collectives
        from photon_ml_tpu.ops import sparse as sparse_ops
        from photon_ml_tpu.parallel import make_feature_mesh
        from photon_ml_tpu.parallel.mesh import DATA_AXIS, FEATURE_AXIS

        n, k, d = 64, 4, 256
        sf = _random_ell(rng, n, k, d)
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        batch = LabeledBatch.create(sf, y)
        mesh = make_feature_mesh(1, 2)
        blocked = sparse_ops.shard_columns(batch.features, 2)
        spec = NamedSharding(mesh, P(DATA_AXIS, FEATURE_AXIS, None))
        placed = sparse_ops.FeatureShardedSparse(
            indices=jax.device_put(blocked.indices, spec),
            values=jax.device_put(blocked.values, spec),
            d_shard=blocked.d_shard,
            d_orig=blocked.d_orig,
        )
        pb = dc.replace(batch, features=placed)
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
