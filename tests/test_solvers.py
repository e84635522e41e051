"""Solver tests: convergence on analytic objectives from many random starts.

Mirrors the reference's test strategy (``optimization/LBFGSTest.scala``,
``optimization/OptimizerIntegTest.scala``, SURVEY §4): optimizers must reach
the known optimum of convex objectives from multiple starts, and the batched
(vmapped) instantiation must agree with the sequential one — the TPU analog
of the RDD-vs-local `Either` duality contract.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.optimize

from photon_ml_tpu.core.tasks import TaskType
from photon_ml_tpu.core.types import LabeledBatch
from photon_ml_tpu.solvers import (
    ConvergenceReason,
    SolverConfig,
    minimize_lbfgs,
    minimize_owlqn,
    minimize_tron,
)
from photon_ml_tpu.solvers.tron import TRON_DEFAULT_CONFIG


def quadratic_problem(rng, d=8):
    """0.5 (w-c)' A (w-c) with SPD A."""
    m = rng.normal(size=(d, d))
    a = m @ m.T + d * np.eye(d)
    c = rng.normal(size=(d,))
    a_j, c_j = jnp.asarray(a), jnp.asarray(c)

    def vg(w):
        r = a_j @ (w - c_j)
        return 0.5 * jnp.vdot(w - c_j, r), r

    def hvp(w, v):
        return a_j @ v

    return vg, hvp, c


def logistic_problem(rng, n=200, d=10, l2=0.1):
    x = rng.normal(size=(n, d))
    w_true = rng.normal(size=(d,))
    p = 1.0 / (1.0 + np.exp(-x @ w_true))
    y = (rng.uniform(size=n) < p).astype(np.float64)
    x_j, y_j = jnp.asarray(x), jnp.asarray(y)

    def vg(w):
        z = x_j @ w
        val = jnp.sum(jax.nn.softplus(z) - y_j * z) + 0.5 * l2 * jnp.vdot(w, w)
        g = x_j.T @ (jax.nn.sigmoid(z) - y_j) + l2 * w
        return val, g

    def hvp(w, v):
        z = x_j @ w
        s = jax.nn.sigmoid(z)
        return x_j.T @ (s * (1 - s) * (x_j @ v)) + l2 * v

    def np_obj(w):
        z = x @ w
        return float(
            np.sum(np.logaddexp(0.0, z) - y * z) + 0.5 * l2 * np.dot(w, w)
        )

    return vg, hvp, np_obj, d


class TestLBFGS:
    def test_quadratic_many_starts(self, rng):
        vg, _, c = quadratic_problem(rng)
        for _ in range(5):
            w0 = jnp.asarray(rng.normal(size=c.shape) * 5)
            # tolerance is relative to the initial state (AbstractOptimizer
            # semantics); tighten it so the far starts still reach the optimum
            cfg = SolverConfig(tolerance=1e-12)
            res = jax.jit(lambda w: minimize_lbfgs(vg, w, cfg))(w0)
            np.testing.assert_allclose(np.asarray(res.w), c, atol=1e-5)
            assert int(res.reason) in (
                ConvergenceReason.FUNCTION_VALUES_CONVERGED,
                ConvergenceReason.GRADIENT_CONVERGED,
            )

    def test_logistic_matches_scipy(self, rng):
        vg, _, np_obj, d = logistic_problem(rng)
        res = minimize_lbfgs(vg, jnp.zeros(d))
        sp = scipy.optimize.minimize(np_obj, np.zeros(d), method="L-BFGS-B")
        assert float(res.value) <= sp.fun + 1e-6

    def test_tracker_buffers(self, rng):
        vg, _, _ = quadratic_problem(rng, d=4)
        res = minimize_lbfgs(vg, jnp.zeros(4))
        # masked_history applies the entries-past-iterations contract
        vals, _ = res.masked_history()
        assert vals.shape == (int(res.iterations) + 1,)
        assert np.all(np.isfinite(vals))
        # objective decreases monotonically on a quadratic
        assert np.all(np.diff(vals) <= 1e-12)

    def test_box_constraints(self, rng):
        vg, _, c = quadratic_problem(rng)
        lb = jnp.asarray(np.full(c.shape, -0.1))
        ub = jnp.asarray(np.full(c.shape, 0.1))
        cfg = SolverConfig(lower_bounds=lb, upper_bounds=ub)
        res = minimize_lbfgs(vg, jnp.zeros(c.shape[0]), cfg)
        w = np.asarray(res.w)
        assert np.all(w >= -0.1 - 1e-12) and np.all(w <= 0.1 + 1e-12)

    def test_vmapped_batch_solve_matches_sequential(self, rng):
        """The per-entity batched regime == the sequential regime."""
        d = 6
        probs = [quadratic_problem(rng, d) for _ in range(4)]
        a_stack = []
        c_stack = []
        for _, _, c in probs:
            c_stack.append(c)
        # rebuild as stacked arrays for a single vmapped objective
        mats = []
        for _ in range(4):
            m = rng.normal(size=(d, d))
            mats.append(m @ m.T + d * np.eye(d))
        a_stack = jnp.asarray(np.stack(mats))
        c_stack = jnp.asarray(np.stack(c_stack))

        def solve_one(a, c, w0):
            def vg(w):
                r = a @ (w - c)
                return 0.5 * jnp.vdot(w - c, r), r

            return minimize_lbfgs(vg, w0, SolverConfig(max_iters=60))

        w0s = jnp.asarray(rng.normal(size=(4, d)))
        batched = jax.jit(jax.vmap(solve_one))(a_stack, c_stack, w0s)
        for i in range(4):
            single = solve_one(a_stack[i], c_stack[i], w0s[i])
            np.testing.assert_allclose(
                np.asarray(batched.w[i]), np.asarray(single.w), atol=1e-5
            )


class TestOWLQN:
    def test_lasso_matches_sklearn(self, rng):
        from sklearn.linear_model import Lasso

        n, d = 120, 15
        x = rng.normal(size=(n, d))
        w_true = np.zeros(d)
        w_true[:3] = [2.0, -3.0, 1.5]
        y = x @ w_true + 0.01 * rng.normal(size=n)
        alpha = 0.1
        x_j, y_j = jnp.asarray(x), jnp.asarray(y)

        def vg(w):  # smooth part: (1/2n)||Xw - y||^2  (sklearn's scaling)
            r = x_j @ w - y_j
            return 0.5 * jnp.vdot(r, r) / n, x_j.T @ r / n

        res = minimize_owlqn(vg, jnp.zeros(d), alpha, SolverConfig(max_iters=200))
        skl = Lasso(alpha=alpha, fit_intercept=False, tol=1e-10).fit(x, y)

        def full_obj(w):
            return 0.5 * np.sum((x @ w - y) ** 2) / n + alpha * np.sum(np.abs(w))

        ours, theirs = full_obj(np.asarray(res.w)), full_obj(skl.coef_)
        assert ours <= theirs + 1e-6
        # sparsity pattern recovered
        assert np.sum(np.abs(np.asarray(res.w)) > 1e-6) <= 6

    def test_l1_logistic_sparsity(self, rng):
        n, d = 300, 20
        x = rng.normal(size=(n, d))
        w_true = np.zeros(d)
        w_true[:2] = [3.0, -3.0]
        p = 1.0 / (1.0 + np.exp(-x @ w_true))
        y = (rng.uniform(size=n) < p).astype(np.float64)
        x_j, y_j = jnp.asarray(x), jnp.asarray(y)

        def vg(w):
            z = x_j @ w
            return (
                jnp.sum(jax.nn.softplus(z) - y_j * z),
                x_j.T @ (jax.nn.sigmoid(z) - y_j),
            )

        res = minimize_owlqn(vg, jnp.zeros(d), 20.0, SolverConfig(max_iters=200))
        w = np.asarray(res.w)
        assert np.abs(w[0]) > 1e-3 and np.abs(w[1]) > 1e-3
        assert np.sum(np.abs(w) > 1e-8) < d  # some exact zeros

    def test_zero_l1_matches_lbfgs(self, rng):
        vg, _, np_obj, d = logistic_problem(rng)
        res_owl = minimize_owlqn(vg, jnp.zeros(d), 0.0)
        res_lb = minimize_lbfgs(vg, jnp.zeros(d))
        np.testing.assert_allclose(
            float(res_owl.value), float(res_lb.value), rtol=1e-6
        )


class TestTRON:
    def test_quadratic_one_newton_step_region(self, rng):
        vg, hvp, c = quadratic_problem(rng)
        cfg = SolverConfig(max_iters=30, tolerance=1e-12)
        res = minimize_tron(vg, hvp, jnp.asarray(rng.normal(size=c.shape)), cfg)
        np.testing.assert_allclose(np.asarray(res.w), c, atol=1e-5)

    def test_logistic_matches_scipy(self, rng):
        vg, hvp, np_obj, d = logistic_problem(rng)
        res = minimize_tron(vg, hvp, jnp.zeros(d), TRON_DEFAULT_CONFIG)
        sp = scipy.optimize.minimize(np_obj, np.zeros(d), method="L-BFGS-B")
        assert float(res.value) <= sp.fun + 1e-5

    def test_many_starts(self, rng):
        vg, hvp, np_obj, d = logistic_problem(rng)
        values = []
        for _ in range(4):
            w0 = jnp.asarray(rng.normal(size=(d,)) * 3)
            res = minimize_tron(vg, hvp, w0)
            values.append(float(res.value))
        assert np.ptp(values) < 1e-4  # all starts reach the same optimum

    @pytest.mark.parametrize("dtype", [jnp.float64, jnp.float32])
    def test_tolerance_zero_makes_the_budget_the_rule(self, rng, dtype):
        """Under tolerance 0 neither the outer tests nor the CG's relative
        residual test stop anything: the same problem in another order of
        its rows (other rounding) makes the same number of Hessian-vector
        passes, which a residual near 0.1 |g| would otherwise decide."""
        n, d = 400, 12
        x = rng.normal(size=(n, d))
        y = (rng.uniform(size=n) < 0.5).astype(float)

        def solve(order, tolerance):
            xo, yo = jnp.asarray(x[order], dtype), jnp.asarray(y[order], dtype)

            def vg(w):
                z = xo @ w
                p = jax.nn.sigmoid(z)
                value = jnp.sum(jax.nn.softplus(z) - yo * z) + 5.0 * (w @ w)
                return value, xo.T @ (p - yo) + 10.0 * w

            def hvp(w, v):
                p = jax.nn.sigmoid(xo @ w)
                return xo.T @ (p * (1 - p) * (xo @ v)) + 10.0 * v

            return minimize_tron(
                vg, hvp, jnp.zeros(d, dtype),
                SolverConfig(max_iters=3, tolerance=tolerance, tron_max_cg=6),
            )

        runs = [solve(rng.permutation(n), 0.0) for _ in range(3)]
        assert [int(r.iterations) for r in runs] == [3, 3, 3]
        # the regulariser keeps every step inside the region: all 6, thrice
        assert [int(r.cg_iterations) for r in runs] == [18, 18, 18]
        assert all(int(r.reason) == ConvergenceReason.MAX_ITERATIONS
                   for r in runs)
        loose = solve(np.arange(n), 1e-12)
        assert int(loose.cg_iterations) < 18  # the residual test still stops
        np.testing.assert_allclose(
            np.asarray(runs[0].w), np.asarray(loose.w),
            atol=1e-4 if dtype == jnp.float32 else 1e-6)

    def test_vmapped_tron(self, rng):
        d = 5
        mats = np.stack(
            [
                (lambda m: m @ m.T + d * np.eye(d))(rng.normal(size=(d, d)))
                for _ in range(3)
            ]
        )
        cs = rng.normal(size=(3, d))
        a_j, c_j = jnp.asarray(mats), jnp.asarray(cs)

        def solve_one(a, c):
            def vg(w):
                r = a @ (w - c)
                return 0.5 * jnp.vdot(w - c, r), r

            return minimize_tron(
                vg,
                lambda w, v: a @ v,
                jnp.zeros(d),
                SolverConfig(max_iters=30, tolerance=1e-12),
            )

        out = jax.jit(jax.vmap(solve_one))(a_j, c_j)
        np.testing.assert_allclose(np.asarray(out.w), cs, atol=1e-5)


class TestConvergenceSemantics:
    def test_max_iterations_reason(self, rng):
        vg, _, _, d = logistic_problem(rng)
        res = minimize_lbfgs(vg, jnp.zeros(d), SolverConfig(max_iters=2, tolerance=0.0))
        assert int(res.reason) == ConvergenceReason.MAX_ITERATIONS
        assert int(res.iterations) == 2

    def test_already_converged_at_start(self):
        def vg(w):
            return jnp.vdot(w, w) * 0.5, w

        res = minimize_lbfgs(vg, jnp.zeros(3))
        assert int(res.reason) == ConvergenceReason.GRADIENT_CONVERGED
        assert int(res.iterations) == 0


class TestNewton:
    """Exact Newton-Cholesky: the TPU-native small-d optimizer. Oracles:
    TRON/sklearn solutions on the same objective."""

    def _logistic(self, rng, n=2000, d=12):
        x = rng.normal(size=(n, d))
        w = rng.normal(size=d)
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-x @ w))).astype(float)
        return LabeledBatch.create(x, y, dtype=jnp.float64)

    def _solve(self, batch, optimizer, lam=1.0, task=None):
        from photon_ml_tpu.models import (
            GLMTrainingConfig,
            OptimizerType,
            TaskType,
            train_glm,
        )
        from photon_ml_tpu.ops import RegularizationContext

        (tm,) = train_glm(
            batch,
            GLMTrainingConfig(
                task=task or TaskType.LOGISTIC_REGRESSION,
                optimizer=OptimizerType[optimizer],
                regularization=RegularizationContext("L2"),
                reg_weights=(lam,),
                max_iters=60,
                tolerance=1e-12,
                track_states=False,
            ),
        )
        return tm

    def test_small_cho_solve_matches_scipy(self, rng):
        """The unrolled static-d Cholesky path (the batched lax Cholesky
        replacement measured ~50 ms/step at (30000,16,16) on TPU) must
        agree with scipy on SPD systems, alone and under vmap."""
        import scipy.linalg

        from photon_ml_tpu.solvers.newton import _small_cho_solve

        # jitted, as every solver call site runs it: the eager form
        # dispatches ~d^3/6 tiny programs one by one (55 s at d=32)
        solve = jax.jit(_small_cho_solve)
        for d in (1, 2, 4, 16, 32):
            a = rng.normal(size=(d, d))
            h = a @ a.T + 5.0 * np.eye(d)
            b = rng.normal(size=d)
            got = np.asarray(solve(jnp.asarray(h), jnp.asarray(b)))
            ref = scipy.linalg.cho_solve(scipy.linalg.cho_factor(h), b)
            np.testing.assert_allclose(got, ref, rtol=1e-9, atol=1e-10)
        # batched under vmap
        e, d = 64, 16
        a = rng.normal(size=(e, d, d))
        h = np.einsum("eij,ekj->eik", a, a) + 5.0 * np.eye(d)
        b = rng.normal(size=(e, d))
        got = np.asarray(
            jax.vmap(_small_cho_solve)(jnp.asarray(h), jnp.asarray(b))
        )
        ref = np.stack(
            [
                scipy.linalg.cho_solve(scipy.linalg.cho_factor(h[i]), b[i])
                for i in range(e)
            ]
        )
        np.testing.assert_allclose(got, ref, rtol=1e-8, atol=1e-9)

    def test_small_cho_solve_nan_on_indefinite(self):
        """Non-PD input must produce NaNs (the jitter-retry detection in
        _newton_direction keys on them, like the lax factorization)."""
        from photon_ml_tpu.solvers.newton import _small_cho_solve

        h = jnp.asarray(
            [[1.0, 2.0], [2.0, 1.0]]
        )  # eigenvalues 3, -1: indefinite
        out = np.asarray(_small_cho_solve(h, jnp.ones(2)))
        assert not np.all(np.isfinite(out))

    def test_matches_tron_solution(self, rng):
        batch = self._logistic(rng)
        newton = self._solve(batch, "NEWTON")
        tron = self._solve(batch, "TRON")
        np.testing.assert_allclose(
            np.asarray(newton.model.coefficients.means),
            np.asarray(tron.model.coefficients.means),
            atol=1e-7,
        )
        # the point of Newton: far fewer iterations than TRON
        assert int(newton.result.iterations) <= int(tron.result.iterations)
        assert int(newton.result.iterations) <= 12

    def test_matches_sklearn(self, rng):
        from sklearn.linear_model import LogisticRegression

        batch = self._logistic(rng, n=3000, d=8)
        newton = self._solve(batch, "NEWTON", lam=1.0)
        skl = LogisticRegression(
            C=1.0, fit_intercept=False, tol=1e-12, max_iter=500
        ).fit(np.asarray(batch.features), np.asarray(batch.labels))
        np.testing.assert_allclose(
            np.asarray(newton.model.coefficients.means),
            skl.coef_.ravel(),
            atol=1e-5,
        )

    def test_linear_regression_exact_in_two_iterations(self, rng):
        from photon_ml_tpu.models import TaskType

        n, d = 500, 6
        x = rng.normal(size=(n, d))
        y = x @ rng.normal(size=d) + 0.1 * rng.normal(size=n)
        batch = LabeledBatch.create(x, y, dtype=jnp.float64)
        newton = self._solve(
            batch, "NEWTON", lam=1.0, task=TaskType.LINEAR_REGRESSION
        )
        # quadratic objective: one Newton step reaches the optimum (the
        # second iteration only certifies convergence)
        assert int(newton.result.iterations) <= 2
        ridge = np.linalg.solve(x.T @ x + np.eye(d), x.T @ y)
        np.testing.assert_allclose(
            np.asarray(newton.model.coefficients.means), ridge, atol=1e-8
        )

    def test_vmapped_per_entity_solves(self, rng):
        """The GAME regime: batched Newton over many tiny subproblems."""
        from photon_ml_tpu.game.coordinates import (
            CoordinateConfig,
            _make_solve,
        )
        from photon_ml_tpu.models.training import OptimizerType

        e, r, d = 12, 30, 4
        x = rng.normal(size=(e, r, d))
        w = rng.normal(size=(e, d))
        y = (
            rng.uniform(size=(e, r))
            < 1 / (1 + np.exp(-np.einsum("erd,ed->er", x, w)))
        ).astype(float)
        args = (
            jnp.zeros((e, d)),
            jnp.full((e,), 1.0),
            jnp.asarray(x),
            jnp.asarray(y),
            jnp.zeros((e, r)),
            jnp.ones((e, r)),
            jnp.ones((e, r)),
        )
        cfg = dict(
            shard="s",
            task=TaskType.LOGISTIC_REGRESSION,
            reg_weight=1.0,
            max_iters=40,
            tolerance=1e-12,
        )
        newton = _make_solve(
            CoordinateConfig(optimizer=OptimizerType.NEWTON, **cfg), True
        )(*args)
        tron = _make_solve(
            CoordinateConfig(optimizer=OptimizerType.TRON, **cfg), True
        )(*args)
        np.testing.assert_allclose(
            np.asarray(newton.w), np.asarray(tron.w), atol=1e-7
        )

    def test_validation_guards(self, rng):
        from photon_ml_tpu.models import (
            GLMTrainingConfig,
            OptimizerType,
            TaskType,
        )
        from photon_ml_tpu.ops import RegularizationContext

        with pytest.raises(ValueError, match="L2 only"):
            GLMTrainingConfig(
                optimizer=OptimizerType.NEWTON,
                regularization=RegularizationContext("L1"),
            ).validate()
        with pytest.raises(ValueError, match="first-order"):
            GLMTrainingConfig(
                optimizer=OptimizerType.NEWTON,
                task=TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM,
            ).validate()
        with pytest.raises(ValueError, match="box constraints"):
            GLMTrainingConfig(
                optimizer=OptimizerType.NEWTON,
                lower_bounds=(0.0,),
            ).validate()
        with pytest.raises(ValueError, match="scale-only"):
            from photon_ml_tpu.core.normalization import NormalizationType

            GLMTrainingConfig(
                optimizer=OptimizerType.NEWTON,
                normalization=NormalizationType.STANDARDIZATION,
                intercept_index=0,
            ).validate()

    def test_game_coordinate_rejects_first_order_loss(self):
        from photon_ml_tpu.game.coordinates import (
            CoordinateConfig,
            _make_solve,
        )
        from photon_ml_tpu.models.training import OptimizerType

        for opt in (OptimizerType.NEWTON, OptimizerType.TRON):
            with pytest.raises(ValueError, match="first-order only"):
                _make_solve(
                    CoordinateConfig(
                        shard="s",
                        task=TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM,
                        optimizer=opt,
                    ),
                    batched=True,
                )


class TestTwoLoopGramForm:
    """The Gram-form two-loop recursion (one (m, m) Gram + batched
    history products, O(1) collectives per direction under a sharded
    coefficient axis — docs/PARALLEL.md) must reproduce the sequential
    recursion exactly, across ring-buffer fills and head positions."""

    def test_gram_equals_sequential(self, rng):
        import jax.numpy as jnp

        from photon_ml_tpu.solvers import lbfgs as lbfgs_mod

        m, d = 10, 53
        for count, head in (
            (0, 0), (1, 1), (3, 3), (10, 4), (7, 0), (10, 0)
        ):
            s = jnp.asarray(rng.normal(size=(m, d)))
            y = jnp.asarray(rng.normal(size=(m, d)))
            rho = jnp.asarray(
                1.0
                / np.einsum("md,md->m", np.asarray(s), np.asarray(y))
            )
            h = lbfgs_mod._History(
                s=s, y=y, rho=rho,
                count=jnp.int32(count), head=jnp.int32(head),
            )
            g = jnp.asarray(rng.normal(size=d))
            r_seq = np.asarray(lbfgs_mod._two_loop_sequential(h, g))
            r_gram = np.asarray(lbfgs_mod._two_loop(h, g))
            scale = max(1.0, float(np.max(np.abs(r_seq))))
            assert np.max(np.abs(r_seq - r_gram)) / scale < 1e-12, (
                count, head,
            )


class TestWorkDoesNotTurnOnRounding:
    """At a float32 solve's end neither the line search nor the stopping
    rule may read the rounding of the objective's own sum: under vmap one
    lane's luck is every lane's trip count (PERF.md section 6, PR 29)."""

    @staticmethod
    def _float32_problem(seed, n=4096, d=8, l2=10.0):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
        y = jnp.asarray(rng.uniform(size=n) < 0.5, jnp.float32)

        def vg(w):
            z = x @ w
            val = jnp.sum(jax.nn.softplus(z) - y * z) + 0.5 * l2 * jnp.vdot(w, w)
            return val, x.T @ (jax.nn.sigmoid(z) - y) + l2 * w

        def hess(w):
            s = jax.nn.sigmoid(x @ w)
            return jnp.einsum("ni,n,nj->ij", x, s * (1 - s), x) + l2 * jnp.eye(
                d, dtype=jnp.float32
            )

        return vg, hess, d

    @pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5])
    def test_newton_at_its_end_does_one_pass_an_iteration(self, seed):
        from photon_ml_tpu.solvers.newton import minimize_newton

        vg, hess, d = self._float32_problem(seed)
        cfg = SolverConfig(max_iters=2, tolerance=0.0)
        w = jnp.zeros((d,), jnp.float32)
        for _ in range(4):  # far past what float32 can still improve
            w = minimize_newton(vg, hess, w, cfg).w
        res = minimize_newton(vg, hess, w, cfg)
        assert int(res.iterations) == 2
        assert int(res.reason) == ConvergenceReason.MAX_ITERATIONS
        # the start's pass and one trial an iteration, none halved
        assert int(res.evals) == 3
        np.testing.assert_array_equal(np.asarray(res.step_tape)[-1:], 1.0)

    def test_armijo_still_halves_a_step_that_overshoots(self):
        from photon_ml_tpu.solvers.newton import minimize_newton

        # f(w) = sqrt(1 + w^2) from w = 2: the Newton step -w(1 + w^2) lands
        # at -8, a larger value by far more than any rounding
        def vg(w):
            r = jnp.sqrt(1.0 + jnp.vdot(w, w))
            return r, w / r

        def hess(w):
            return jnp.eye(1, dtype=w.dtype) / (1.0 + jnp.vdot(w, w)) ** 1.5

        res = minimize_newton(
            vg, hess, jnp.asarray([2.0], jnp.float32),
            SolverConfig(max_iters=1, tolerance=0.0),
        )
        assert float(res.value) < float(vg(jnp.asarray([2.0]))[0])
        assert int(res.evals) > 2

    def test_tolerance_zero_runs_the_whole_budget(self):
        from photon_ml_tpu.solvers.newton import minimize_newton

        c = jnp.asarray([1.0, -2.0, 3.0])
        res = minimize_newton(
            lambda w: (0.5 * jnp.vdot(w - c, w - c), w - c),
            lambda w: jnp.eye(3),
            jnp.zeros(3),
            SolverConfig(max_iters=3, tolerance=0.0),
        )
        # solved by the first step; the value cannot change again
        np.testing.assert_allclose(np.asarray(res.w), np.asarray(c))
        assert int(res.iterations) == 3
        assert int(res.reason) == ConvergenceReason.MAX_ITERATIONS
        with_tolerance = minimize_newton(
            lambda w: (0.5 * jnp.vdot(w - c, w - c), w - c),
            lambda w: jnp.eye(3),
            jnp.zeros(3),
            SolverConfig(max_iters=3, tolerance=1e-9),
        )
        assert int(with_tolerance.iterations) == 1
