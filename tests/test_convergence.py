"""Convergence-observability drills: solver tapes, masked decode, fleet
summaries, and the end-to-end --convergence-report surface.

Covers the PR-7 layer (obs/convergence.py + the solver-carry tapes):
tape semantics under vmap must match entity-by-entity solves (the
telemetry that survives fully device-resident solver loops), the
masked-history contract, the batched design_passes fix, and the driver /
photon-obs rendering path.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.obs import convergence as conv
from photon_ml_tpu.solvers import (
    ConvergenceReason,
    SolverConfig,
    design_passes,
    mask_tape,
    minimize_lbfgs,
    minimize_newton,
    minimize_tron,
)

pytestmark = [pytest.mark.convergence, pytest.mark.obs]


def quadratic(rng, d=6):
    m = rng.normal(size=(d, d))
    a = jnp.asarray(m @ m.T + d * np.eye(d))
    c = jnp.asarray(rng.normal(size=d))

    def vg(w):
        r = a @ (w - c)
        return 0.5 * jnp.vdot(w - c, r), r

    return vg, (lambda w, v: a @ v), (lambda w: a), c


# ---------------------------------------------------------------------------
# Solver tapes
# ---------------------------------------------------------------------------


class TestSolverTapes:
    def test_tron_radius_and_cg_tapes(self, rng):
        vg, hvp, _, _ = quadratic(rng)
        res = minimize_tron(vg, hvp, jnp.zeros(6), SolverConfig(max_iters=15))
        iters = int(res.iterations)
        assert iters >= 1
        radius = mask_tape(res.radius_tape, res.iterations)
        cg = mask_tape(res.cg_tape, res.iterations)
        assert radius.shape == (iters + 1,) == cg.shape
        assert np.all(np.isfinite(radius)) and np.all(radius > 0)
        # slot 0 = initial radius = ||g0||; slot 0 CG work = 0
        _, g0 = vg(jnp.zeros(6))
        np.testing.assert_allclose(
            radius[0], float(jnp.linalg.norm(g0)), rtol=1e-6
        )
        assert cg[0] == 0.0
        assert np.all(cg[1:] >= 1.0)
        # the per-step CG tape sums to the total the result already counts
        np.testing.assert_allclose(cg.sum(), float(res.cg_iterations))
        # entries past `iterations` are the +inf unwritten sentinel
        full = np.asarray(res.radius_tape)
        if iters + 1 < full.shape[0]:
            assert np.all(np.isinf(full[iters + 1 :]))

    def test_lbfgs_step_and_eval_tapes(self, rng):
        vg, _, _, _ = quadratic(rng)
        res = minimize_lbfgs(vg, jnp.zeros(6), SolverConfig(max_iters=40))
        iters = int(res.iterations)
        step = mask_tape(res.step_tape, res.iterations)
        evals = mask_tape(res.eval_tape, res.iterations)
        assert step.shape == (iters + 1,) == evals.shape
        assert step[0] == 0.0  # no step before the first iteration
        assert evals[0] == 1.0  # the initial value/grad pass
        assert np.all(step[1:] > 0.0)
        assert np.all(evals[1:] >= 1.0)
        # the per-iteration eval tape sums to the counted total
        np.testing.assert_allclose(evals.sum(), float(res.evals))

    def test_newton_tapes(self, rng):
        vg, _, hess, _ = quadratic(rng)
        res = minimize_newton(vg, hess, jnp.zeros(6), SolverConfig(max_iters=10))
        step = mask_tape(res.step_tape, res.iterations)
        # exact Newton on a quadratic: full step accepted immediately
        assert step[-1] == 1.0
        evals = mask_tape(res.eval_tape, res.iterations)
        np.testing.assert_allclose(evals.sum(), float(res.evals))

    def test_track_states_off_collapses_tapes(self, rng):
        vg, hvp, _, _ = quadratic(rng)
        res = minimize_tron(
            vg, hvp, jnp.zeros(6),
            SolverConfig(max_iters=15, track_states=False),
        )
        assert res.radius_tape.shape == (1,)
        assert res.cg_tape.shape == (1,)
        assert res.values.shape == (1,)
        # the one slot holds the LATEST state, still decodable
        assert mask_tape(res.radius_tape, res.iterations).shape == (1,)


class TestMaskedHistory:
    def test_scalar_truncation(self, rng):
        vg, _, _, _ = quadratic(rng)
        res = minimize_lbfgs(vg, jnp.zeros(6))
        iters = int(res.iterations)
        values, grad_norms = res.masked_history()
        assert values.shape == (iters + 1,) == grad_norms.shape
        assert np.all(np.isfinite(values))
        assert np.all(np.diff(values) <= 1e-10)  # quadratic: monotone

    def test_max_iters_edge(self, rng):
        """A solve that runs out of iterations keeps the FULL buffer —
        the `iterations == max_iters` edge of the truncation contract."""
        vg, _, _, _ = quadratic(rng)
        cfg = SolverConfig(max_iters=2, tolerance=1e-300)
        res = minimize_lbfgs(vg, jnp.zeros(6), cfg)
        assert int(res.iterations) == 2
        assert int(res.reason) == ConvergenceReason.MAX_ITERATIONS
        values, grad_norms = res.masked_history()
        assert values.shape == (3,)
        assert np.all(np.isfinite(values))

    def test_w_history_third_element(self, rng):
        vg, _, _, c = quadratic(rng)
        cfg = SolverConfig(max_iters=40, track_models=True)
        res = minimize_lbfgs(vg, jnp.zeros(6), cfg)
        out = res.masked_history()
        assert len(out) == 3
        wh = out[2]
        assert wh.shape == (int(res.iterations) + 1, 6)
        np.testing.assert_allclose(wh[0], np.zeros(6))  # w0 snapshot
        np.testing.assert_allclose(wh[-1], np.asarray(res.w))

    def test_batched_nan_masking(self, rng):
        """Vmapped results NaN-mask past each lane's iterations instead
        of ragged truncation."""
        vg, hvp, _, _ = quadratic(rng)

        def solve_one(w0):
            return minimize_tron(vg, hvp, w0, SolverConfig(max_iters=15))

        w0s = jnp.asarray(rng.normal(size=(3, 6)))
        batched = jax.jit(jax.vmap(solve_one))(w0s)
        values, grad_norms = batched.masked_history()
        assert values.shape == (3, 16)
        iters = np.asarray(batched.iterations)
        for lane in range(3):
            assert np.all(np.isfinite(values[lane, : iters[lane] + 1]))
            assert np.all(np.isnan(values[lane, iters[lane] + 1 :]))


class TestDesignPasses:
    def test_vmapped_tron_sums_over_batch(self, rng):
        """Regression (PR-7 satellite): design_passes used to call
        float() on a vmapped result's non-scalar iterations and raise;
        it must sum counted passes over the batch lanes."""
        vg, hvp, _, _ = quadratic(rng)

        def solve_one(w0):
            return minimize_tron(vg, hvp, w0, SolverConfig(max_iters=15))

        w0s = jnp.asarray(rng.normal(size=(4, 6)))
        batched = jax.jit(jax.vmap(solve_one))(w0s)
        total = design_passes(batched)  # must not raise
        expected = sum(
            design_passes(solve_one(w0s[i])) for i in range(4)
        )
        np.testing.assert_allclose(total, expected)

    def test_vmapped_evals_result(self, rng):
        vg, _, _, _ = quadratic(rng)

        def solve_one(w0):
            return minimize_lbfgs(vg, w0, SolverConfig(max_iters=30))

        w0s = jnp.asarray(rng.normal(size=(3, 6)))
        batched = jax.jit(jax.vmap(solve_one))(w0s)
        np.testing.assert_allclose(
            design_passes(batched),
            sum(design_passes(solve_one(w0s[i])) for i in range(3)),
        )


# ---------------------------------------------------------------------------
# Tape semantics under vmap: the GAME per-entity regime
# ---------------------------------------------------------------------------


class TestVmapTapeEquivalence:
    @pytest.mark.parametrize("optimizer", ["TRON", "LBFGS", "NEWTON"])
    def test_bucket_solve_tapes_match_individual(self, rng, optimizer):
        """Per-entity tapes from ONE vmapped GAME bucket solve must equal
        the tapes of the same entities solved individually (f32 <= 1e-6),
        including a never-converging entity that hits max_iters."""
        from photon_ml_tpu.game.coordinates import (
            CoordinateConfig,
            _make_solve,
        )
        from photon_ml_tpu.models.training import OptimizerType
        from photon_ml_tpu.core.tasks import TaskType

        E, r, d = 5, 30, 3
        cfg = CoordinateConfig(
            shard="s",
            task=TaskType.LOGISTIC_REGRESSION,
            optimizer=OptimizerType[optimizer],
            reg_weight=1.0,
            max_iters=4,  # low cap: some entities hit MAX_ITERATIONS
            tolerance=1e-10,
            random_effect="e",
            track_states=True,
        )
        feats = rng.normal(size=(E, r, d)).astype(np.float32)
        labels = (rng.uniform(size=(E, r)) < 0.5).astype(np.float32)
        offsets = np.zeros((E, r), np.float32)
        weights = np.ones((E, r), np.float32)
        mask = np.ones((E, r), np.float32)
        # entity 0: a SEPARABLE lane (labels = margin sign, near-zero
        # regularization) — the logistic MLE diverges, so it cannot
        # converge in 4 iterations and hits MAX_ITERATIONS
        feats[0] *= 4.0
        labels[0] = (feats[0] @ np.ones(d, np.float32) > 0).astype(
            np.float32
        )
        lam = np.full((E,), 1e-4, np.float32)
        w0 = np.zeros((E, d), np.float32)

        batched = _make_solve(cfg, batched=True)
        single = _make_solve(cfg, batched=False)
        bres = batched(
            jnp.asarray(w0), jnp.asarray(lam), jnp.asarray(feats),
            jnp.asarray(labels), jnp.asarray(offsets),
            jnp.asarray(weights), jnp.asarray(mask),
        )
        reasons = np.asarray(bres.reason)
        assert ConvergenceReason.MAX_ITERATIONS in reasons, (
            "fixture must include a never-converging entity"
        )
        # per-field tolerances: the state tapes hold the spec's f32 1e-6;
        # iteration/eval COUNTS must be bit-identical; the step/radius
        # tapes are line-search / trust-region outputs whose cubic
        # minimizer amplifies f32 reduction-order noise a few ulps
        if optimizer == "TRON":
            tape_tols = {
                "values": 1e-6, "grad_norms": 1e-6,
                "radius_tape": 1e-5, "cg_tape": 0.0,
            }
        else:
            tape_tols = {
                "values": 1e-6, "grad_norms": 1e-6,
                "step_tape": 1e-5, "eval_tape": 0.0,
            }
        for e in range(E):
            sres = single(
                jnp.asarray(w0[e]), jnp.asarray(lam[e]),
                jnp.asarray(feats[e]), jnp.asarray(labels[e]),
                jnp.asarray(offsets[e]), jnp.asarray(weights[e]),
                jnp.asarray(mask[e]),
            )
            assert int(np.asarray(bres.iterations)[e]) == int(
                sres.iterations
            )
            assert int(reasons[e]) == int(sres.reason)
            n = int(sres.iterations) + 1
            for field, tol in tape_tols.items():
                b_tape = np.asarray(getattr(bres, field))[e][:n]
                s_tape = np.asarray(getattr(sres, field))[:n]
                if tol == 0.0:
                    np.testing.assert_array_equal(
                        b_tape, s_tape,
                        err_msg=f"{optimizer} entity {e} tape {field}",
                    )
                else:
                    np.testing.assert_allclose(
                        b_tape, s_tape, rtol=tol, atol=tol,
                        err_msg=f"{optimizer} entity {e} tape {field}",
                    )


# ---------------------------------------------------------------------------
# Decode: reports, rates, fleet summaries
# ---------------------------------------------------------------------------


class TestAnalyzeHistory:
    def test_linear_rate(self):
        g = 10.0 * 0.5 ** np.arange(12)
        v = 1.0 + g**2
        out = conv.analyze_history(v, g)
        assert out["order"] == "linear"
        assert abs(out["rate"] - 0.5) < 0.05
        assert out["oscillations"] == 0

    def test_superlinear(self):
        # quadratic convergence: g_{k+1} = g_k^2
        g = [1e-1, 1e-2, 1e-4, 1e-8, 1e-16]
        v = [1 + x for x in g]
        out = conv.analyze_history(v, g)
        assert out["order"] == "superlinear"

    def test_stalled_and_plateau(self):
        g = [1.0] * 8  # gradient going nowhere
        v = [5.0, 4.0] + [3.0] * 6  # objective flat-lined
        out = conv.analyze_history(v, g)
        assert out["order"] == "stalled"
        assert out["plateau_iters"] >= 5

    def test_oscillations_counted(self):
        v = [5.0, 4.0, 4.5, 3.0, 3.5, 2.0]
        g = [1.0, 0.9, 0.95, 0.5, 0.6, 0.2]
        out = conv.analyze_history(v, g)
        assert out["oscillations"] == 2

    def test_decode_result_tron(self, rng):
        vg, hvp, _, _ = quadratic(rng)
        res = minimize_tron(vg, hvp, jnp.zeros(6), SolverConfig(max_iters=15))
        rep = conv.decode_result(res, optimizer="tron")
        assert rep.optimizer == "tron"
        assert rep.iterations == int(res.iterations)
        assert rep.reason in (
            "FUNCTION_VALUES_CONVERGED", "GRADIENT_CONVERGED"
        )
        assert sorted(rep.tapes) == ["cg", "radius"]
        assert len(rep.values) == rep.iterations + 1
        assert np.isfinite(rep.final_grad_norm)


class TestFleetSummary:
    def test_histogram_nonconverged_and_worst(self):
        reasons = np.asarray([2, 2, 1, 0, 3, 2], np.int32)
        iters = np.asarray([3, 3, 8, 8, 2, 4], np.int32)
        gns = np.asarray([1e-6, 2e-6, 0.5, np.inf, 1e-7, 3e-6])
        ids = np.asarray([10, 11, 12, 13, 14, 15])
        s = conv.fleet_summary(
            reasons, iters, gns, ids, coordinate="c", iteration=1,
            worst_k=3,
        )
        assert s.entities == 6
        assert s.nonconverged == 2  # MAX_ITERATIONS + NOT_CONVERGED
        assert abs(s.nonconverged_frac - 2 / 6) < 1e-12
        assert s.iters_histogram == {3: 2, 8: 2, 2: 1, 4: 1}
        assert s.median_iters == 3.5
        assert s.reason_counts["MAX_ITERATIONS"] == 1
        assert s.nonfinite_grad_norms == 1
        # non-finite entity ranks worst of all, then the 0.5 one
        assert [e for e, _ in s.worst] == [13, 12, 15]

    def test_note_update_metrics_and_precursor(self):
        from photon_ml_tpu.obs.metrics import MetricsRegistry

        reg = MetricsRegistry()
        reasons = np.asarray([1, 1, 1, 2], np.int32)  # 75% nonconverged
        iters = np.asarray([8, 8, 8, 3], np.int32)
        gns = np.asarray([0.5, 0.4, 0.3, 1e-7])
        s = conv.note_update(
            "per-user", 0, reasons, iters, gns, registry=reg, emit=False
        )
        assert s.nonconverged == 3
        snap = reg.snapshot()
        assert snap["counters"]["convergence.solves"] == 4.0
        assert snap["counters"]["convergence.nonconverged"] == 3.0
        assert snap["counters"]["convergence.precursors"] == 1.0
        assert (
            snap["gauges"]["convergence.per-user.nonconverged_frac"] == 0.75
        )
        assert snap["gauges"]["convergence.per-user.median_iters"] == 8.0

    def test_tracker_aggregation(self):
        tracker = conv.ConvergenceTracker(last_n=4)
        for i in range(6):
            tracker.note_fleet(
                conv.fleet_summary(
                    np.asarray([2, 1]), np.asarray([2, 8]),
                    np.asarray([1e-6, 0.9]), np.asarray([0, 1]),
                    coordinate="c", iteration=i,
                )
            )
        rep = tracker.report()
        assert rep["updates"] == 6
        assert len(rep["last_fleet"]) == 6  # under the 256 floor
        assert rep["coordinates"]["c"]["entities"] == 12
        assert rep["coordinates"]["c"]["nonconverged"] == 6
        assert rep["coordinates"]["c"]["worst_entities"][0][0] == 1
        assert rep["nonconverged_frac"] == 0.5


# ---------------------------------------------------------------------------
# End-to-end: GAME descent -> metrics/events -> photon-obs convergence
# ---------------------------------------------------------------------------


def _build_smoke_cd(rng, track_states=False):
    from photon_ml_tpu.core.tasks import TaskType
    from photon_ml_tpu.game import (
        CoordinateConfig,
        CoordinateDescent,
        FixedEffectCoordinate,
        GameData,
        RandomEffectCoordinate,
        build_random_effect_design,
    )
    from photon_ml_tpu.models.training import OptimizerType

    n, d, E, du = 1500, 6, 20, 3
    user = rng.integers(0, E, size=n).astype(np.int32)
    xg = rng.standard_normal((n, d)).astype(np.float32)
    xu = rng.standard_normal((n, du)).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    data = GameData.create(
        features={"g": xg, "u": xu}, labels=y, entity_ids={"userId": user}
    )
    base = dict(
        task=TaskType.LOGISTIC_REGRESSION, max_iters=5, tolerance=1e-6,
        track_states=track_states,
    )
    fixed = FixedEffectCoordinate(
        data.fixed_effect_batch("g", jnp.float32),
        CoordinateConfig(
            shard="g", optimizer=OptimizerType.NEWTON, reg_weight=1.0,
            **base,
        ),
    )
    design = build_random_effect_design(
        data, "userId", "u", E, dtype=jnp.float32
    )
    rand = RandomEffectCoordinate(
        design=design,
        row_features=jnp.asarray(xu),
        row_entities=jnp.asarray(user),
        full_offsets_base=jnp.zeros((n,), jnp.float32),
        config=CoordinateConfig(
            shard="u", optimizer=OptimizerType.NEWTON, reg_weight=10.0,
            random_effect="userId", **base,
        ),
    )
    return CoordinateDescent(
        coordinates={"fixed": fixed, "per-user": rand},
        labels=jnp.asarray(y),
        base_offsets=jnp.zeros((n,), jnp.float32),
        weights=jnp.ones((n,), jnp.float32),
        task=TaskType.LOGISTIC_REGRESSION,
    )


class TestConvergenceEndToEnd:
    def test_game_fleet_summaries_into_artifacts(self, rng, tmp_path):
        """The acceptance path: a GAME run with the tracker installed
        emits per-coordinate fleet summaries into metrics + events.jsonl,
        the run report aggregates them, and `photon-obs convergence`
        renders the events."""
        from photon_ml_tpu.cli import obs_tools
        from photon_ml_tpu.obs.metrics import MetricsRegistry, set_registry

        cd = _build_smoke_cd(rng)
        trace_dir = str(tmp_path / "trace")
        reg = MetricsRegistry()
        prev = set_registry(reg)
        tracker = obs.install_convergence_tracker()
        try:
            with obs.observe(trace_dir=trace_dir):
                cd.run(num_iterations=2)
        finally:
            obs.uninstall_convergence_tracker()
            set_registry(prev)
        # registry carries the convergence taxonomy
        snap = reg.snapshot()
        assert snap["counters"]["convergence.solves"] >= 40  # 20 x 2 + fe
        assert "convergence.per-user.median_iters" in snap["gauges"]
        assert "convergence.per-user.nonconverged_frac" in snap["gauges"]
        # metrics.json (written by the observe envelope) has them too
        mpath = os.path.join(trace_dir, "metrics.json")
        with open(mpath) as f:
            dumped = json.load(f)
        assert any(
            k.startswith("convergence.") for k in dumped["counters"]
        )
        # events.jsonl carries one fleet event per coordinate per pass
        fleet = []
        with open(os.path.join(trace_dir, "events.jsonl")) as f:
            for line in f:
                rec = json.loads(line)
                if (
                    rec.get("kind") == "event"
                    and rec.get("name") == "convergence.fleet"
                ):
                    fleet.append(rec)
        assert len(fleet) == 4  # 2 coordinates x 2 passes
        per_user = [r for r in fleet if r["coordinate"] == "per-user"]
        assert per_user and per_user[0]["entities"] == 20
        assert per_user[0]["iters_histogram"]
        assert len(per_user[0]["worst"]) == 5
        # the run-level report aggregates the same data
        rep = tracker.report()
        assert rep["coordinates"]["per-user"]["entities"] == 40
        assert 0.0 <= rep["nonconverged_frac"] <= 1.0
        # photon-obs convergence renders the events (exit 0)
        assert obs_tools.main(["convergence", trace_dir]) == 0

    def test_obs_tools_exit_2_without_records(self, tmp_path):
        from photon_ml_tpu.cli import obs_tools

        ev = tmp_path / "events.jsonl"
        ev.write_text('{"kind": "event", "name": "other"}\n')
        assert obs_tools.main(["convergence", str(tmp_path)]) == 2

    def test_traced_train_glm_emits_solve_reports(self, rng, tmp_path):
        """GLM path: traced train_glm decodes every solve — structured
        convergence.solve events with tapes, plus a counter track laid
        across the solve span window."""
        from photon_ml_tpu.core.types import LabeledBatch
        from photon_ml_tpu.models.training import (
            GLMTrainingConfig,
            OptimizerType,
            train_glm,
        )

        n, d = 800, 5
        x = rng.standard_normal((n, d))
        w_true = rng.normal(size=d)
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-x @ w_true))).astype(
            float
        )
        batch = LabeledBatch(
            jnp.asarray(x), jnp.asarray(y), jnp.zeros(n), jnp.ones(n),
            jnp.ones(n),
        )
        cfg = GLMTrainingConfig(
            optimizer=OptimizerType.TRON, reg_weights=(1.0,),
            max_iters=20, tolerance=1e-8,
        )
        trace_dir = str(tmp_path / "trace")
        with obs.observe(trace_dir=trace_dir):
            train_glm(batch, cfg)
        solves = []
        with open(os.path.join(trace_dir, "events.jsonl")) as f:
            for line in f:
                rec = json.loads(line)
                if (
                    rec.get("kind") == "event"
                    and rec.get("name") == "convergence.solve"
                ):
                    solves.append(rec)
        assert len(solves) == 1
        rep = solves[0]
        assert rep["optimizer"] == "tron"
        assert rep["reason"] in (
            "FUNCTION_VALUES_CONVERGED", "GRADIENT_CONVERGED"
        )
        assert len(rep["values"]) == rep["iterations"] + 1
        assert "radius" in rep["tapes"] and "cg" in rep["tapes"]
        # the counter track replays the curve inside the span window
        with open(os.path.join(trace_dir, "trace.json")) as f:
            doc = json.load(f)
        counters = [
            e for e in doc["traceEvents"]
            if e.get("ph") == "C" and e["name"] == "convergence.solve"
        ]
        assert len(counters) == rep["iterations"] + 1
        ts = [e["ts"] for e in counters]
        assert ts == sorted(ts)
        spans = [
            e for e in doc["traceEvents"]
            if e.get("ph") == "X" and e["name"] == "glm.solve"
        ]
        assert spans and spans[0]["args"]["convergence_reason"] == rep[
            "reason"
        ]
        # counter samples land inside the solve span's window
        s = spans[0]
        assert ts[0] >= s["ts"] - 1.0
        assert ts[-1] <= s["ts"] + s["dur"] + 1.0

    def test_convergence_report_driver_flag(self, rng, tmp_path):
        """run_glm_training(convergence_report=True) without tracing:
        convergence-report.json + metrics.json land in the output dir."""
        from photon_ml_tpu.cli.train import run_glm_training
        from photon_ml_tpu.io.avro import write_avro_file
        from photon_ml_tpu.io.schemas import TRAINING_EXAMPLE_SCHEMA

        w_true = rng.normal(size=4) * 1.5
        x = rng.normal(size=(200, 4))
        y = (rng.uniform(size=200) < 1 / (1 + np.exp(-x @ w_true))).astype(
            float
        )
        records = [
            {
                "uid": f"row{i}",
                "label": float(y[i]),
                "features": [
                    {"name": f"f{j}", "term": "", "value": float(x[i, j])}
                    for j in range(4)
                ],
                "metadataMap": None,
                "weight": None,
                "offset": None,
            }
            for i in range(200)
        ]
        train = str(tmp_path / "train.avro")
        write_avro_file(train, TRAINING_EXAMPLE_SCHEMA, records)
        out = tmp_path / "out"
        run_glm_training(
            {
                "train_input": [train],
                "output_dir": str(out),
                "optimizer": "TRON",
                "reg_weights": [1.0],
                "max_iters": 25,
                "convergence_report": True,
            }
        )
        with open(out / "convergence-report.json") as f:
            rep = json.load(f)
        assert rep["solves"] == 1
        assert rep["last_solves"][0]["reason"] in (
            "FUNCTION_VALUES_CONVERGED", "GRADIENT_CONVERGED",
            "MAX_ITERATIONS",
        )
        assert rep["last_solves"][0]["grad_norms"]
        with open(out / "metrics.json") as f:
            metrics = json.load(f)
        assert any(
            k.startswith("convergence.") for k in metrics["counters"]
        )


class TestSentinelDirections:
    def test_convergence_metrics_tracked_lower_is_better(self):
        from photon_ml_tpu.obs.sentinel import (
            LOWER_IS_BETTER,
            metric_direction,
        )

        assert (
            metric_direction("extra.convergence.median_iters")
            == LOWER_IS_BETTER
        )
        assert (
            metric_direction("extra.convergence.nonconverged_frac")
            == LOWER_IS_BETTER
        )

    def test_history_not_flagged(self):
        """The new convergence.* metrics must not flag the committed
        r03-r05 history (they are new; growth is not a regression)."""
        import glob

        from photon_ml_tpu.obs.sentinel import run_sentinel

        hist = sorted(glob.glob("BENCH_r*.json"))
        if len(hist) < 3:
            pytest.skip("needs committed BENCH history")
        from photon_ml_tpu.obs.sentinel import load_bench_record

        current = load_bench_record(hist[-1])
        regs, baselines, n = run_sentinel(hist[:-1], current)
        assert not [
            r for r in regs if "convergence." in r.metric
        ]
