"""Exact Newton (IRLS) with Cholesky solves, for the small-d regime.

A TPU-native optimizer the reference cannot have: Photon-ML's optimizers
are L-BFGS and Hessian-VECTOR TRON because a full (d, d) Hessian is a
d^2-sized treeAggregate — prohibitive on Spark. On TPU the explicit
cross-product X^T diag(c) X is one MXU pass and a (d, d) Cholesky is
microseconds for d up to a few thousand, so each Newton iteration costs
ONE data pass instead of a whole truncated-CG loop, and typical GLMs
converge in < 10 iterations. This is the right solver for GAME
fixed-effect coordinates (d ~ 10^1..10^3) and vmaps cleanly over the
per-entity random-effect subproblems (d ~ 10^1).

Damped for global convergence: backtracking halving on the Armijo
condition (``SolverConfig.ls_c1`` / ``ls_max_evals``, with room for the
evaluation's own noise: ``_ARMIJO_ROUNDING_ULPS``), plus a
Levenberg-style jitter retry when the Cholesky meets a non-PD matrix
(possible only with l2 = 0 on degenerate data). Convergence criteria
match ``AbstractOptimizer.scala:52-62`` exactly like the other solvers
(a tolerance of zero runs the whole iteration budget:
``common.check_convergence``).
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.solvers.common import (
    ConvergenceReason,
    SolverConfig,
    SolverResult,
    check_convergence,
    model_buffer,
    record_model,
    record_state,
    record_tape,
    tape_buffer,
    tracker_buffers,
)

ValueAndGrad = Callable[[jax.Array], Tuple[jax.Array, jax.Array]]
HessianFull = Callable[[jax.Array], jax.Array]

NEWTON_DEFAULT_CONFIG = SolverConfig(max_iters=25, tolerance=1e-7)


class _NewtonState(NamedTuple):
    w: jax.Array
    value: jax.Array
    grad: jax.Array
    iteration: jax.Array
    reason: jax.Array
    value_initial: jax.Array
    grad_norm_initial: jax.Array
    values: jax.Array
    grad_norms: jax.Array
    w_history: jax.Array
    evals: jax.Array  # total value_and_grad calls (full design passes)
    # per-iteration convergence tapes (track_states; one slot off):
    # accepted damping step size, line-search evaluations
    step_tape: jax.Array
    eval_tape: jax.Array


# Dimension bound for the unrolled Cholesky path. Measured on the real
# chip in r5: XLA's batched lax Cholesky on (30000, 16, 16) costs ~50 ms
# per factor+solve — it was ~80% of every vmapped per-entity Newton
# solve and THE random-effect throughput floor (the (E, r, d, d) Hessian
# einsums first blamed measure ~1-4 ms once the fetch latency is
# subtracted). The unrolled
# static-d factorization below lowers to plain elementwise/matvec ops
# that vmap into (E,)-wide kernels with no lax.linalg loop machinery and
# measures ~0 ms at the same shape (6.7e-4 max rel err, f32).
_UNROLLED_CHO_MAX_DIM = 32


def _small_cho_solve(h: jax.Array, b: jax.Array) -> jax.Array:
    """h (d, d) SPD, b (d,) -> h^{-1} b with the Cholesky factorization
    unrolled over the STATIC small d (column-Crout order, then forward /
    back substitution). A non-PD h yields NaNs exactly like the lax
    factorization, so the jitter-retry detection below is unchanged."""
    d = h.shape[-1]
    L = jnp.zeros_like(h)
    for j in range(d):
        col = h[j:, j] - L[j:, :j] @ L[j, :j]
        L = L.at[j:, j].set(col / jnp.sqrt(col[0]))
    y = jnp.zeros_like(b)
    for i in range(d):
        y = y.at[i].set((b[i] - L[i, :i] @ y[:i]) / L[i, i])
    x = jnp.zeros_like(b)
    for i in reversed(range(d)):
        x = x.at[i].set((y[i] - L[i + 1 :, i] @ x[i + 1 :]) / L[i, i])
    return x


# Room the Armijo test leaves for the noise of the objective's own
# evaluation, in units in the last place of the current value. Near a
# solve's end, and from its first step for an entity of a few rows, the
# decrease a Newton step promises falls under what two evaluations of the
# objective can tell apart: the test becomes a coin, and every tail costs
# a halving and a whole pass; vmapped over the entities of a bucket, one
# lane's tail is the trip count of all of them. Measured on the v5e
# (PERF.md section 6, PR 29): the same job took 5.12, 5.16 or 5.18 s by
# the seed's row order alone; two float32 evaluations there differ by
# 1e-5 of the value and more (with room for 32 ulps, 4e-6, 1,876 of a
# bucket's 457,222 lanes still lost the coin in the first pass; on the
# CPU none did). 1,024 ulps are 1.2e-4 of a float32 value and 2e-13 of a
# float64 one; a step that overshoots misses by far more and is halved
# as before.
_ARMIJO_ROUNDING_ULPS = 1024.0


def _newton_direction(h: jax.Array, grad: jax.Array) -> jax.Array:
    """Solve H p = -grad by Cholesky, retrying with a Levenberg jitter
    when H is not positive definite (all branchless: the jittered solve
    is selected where the plain factorization produced NaNs)."""
    eye = jnp.eye(h.shape[-1], dtype=h.dtype)

    def solve(mat):
        if mat.shape[-1] <= _UNROLLED_CHO_MAX_DIM:
            return _small_cho_solve(mat, -grad)
        factor = jax.scipy.linalg.cho_factor(mat)
        return jax.scipy.linalg.cho_solve(factor, -grad)

    p = solve(h)
    bad = ~jnp.all(jnp.isfinite(p))
    jitter = 1e-6 * (1.0 + jnp.trace(h) / h.shape[-1])
    p_jittered = solve(h + jitter * eye)
    return jnp.where(bad, p_jittered, p)


def minimize_newton(
    value_and_grad_fn: ValueAndGrad,
    hessian_fn: HessianFull,
    w0: jax.Array,
    config: SolverConfig = NEWTON_DEFAULT_CONFIG,
) -> SolverResult:
    """Minimize a twice-differentiable objective by damped exact Newton."""
    dtype = w0.dtype
    v0, g0 = value_and_grad_fn(w0)
    gnorm0 = jnp.linalg.norm(g0)
    values, grad_norms = tracker_buffers(
        config.max_iters, dtype, config.track_states
    )
    values, grad_norms = record_state(values, grad_norms, 0, v0, gnorm0)
    w_hist0 = model_buffer(config.max_iters, w0, config.track_models)
    step_tape0 = record_tape(
        tape_buffer(config.max_iters, dtype, config.track_states), 0, 0.0
    )
    eval_tape0 = record_tape(
        tape_buffer(config.max_iters, dtype, config.track_states), 0, 1.0
    )

    init = _NewtonState(
        w=w0,
        value=v0,
        grad=g0,
        iteration=jnp.int32(0),
        reason=jnp.where(
            gnorm0 == 0.0,
            jnp.int32(ConvergenceReason.GRADIENT_CONVERGED),
            jnp.int32(ConvergenceReason.NOT_CONVERGED),
        ),
        value_initial=v0,
        grad_norm_initial=gnorm0,
        values=values,
        grad_norms=grad_norms,
        w_history=w_hist0,
        evals=jnp.int32(1),
        step_tape=step_tape0,
        eval_tape=eval_tape0,
    )

    def body(s: _NewtonState) -> _NewtonState:
        h = hessian_fn(s.w)
        direction = _newton_direction(h, s.grad)
        dphi0 = jnp.vdot(s.grad, direction)
        # Non-descent (numerically possible with the jitter fallback):
        # fall back to steepest descent scaled to the Newton step length.
        bad_dir = dphi0 >= 0.0
        direction = jnp.where(
            bad_dir,
            -s.grad
            * (jnp.linalg.norm(direction) / jnp.maximum(jnp.linalg.norm(s.grad), 1e-30)),
            direction,
        )
        dphi0 = jnp.where(bad_dir, jnp.vdot(s.grad, direction), dphi0)

        # what the objective's own sum cannot resolve is no increase: see
        # _ARMIJO_ROUNDING_ULPS
        slack = _ARMIJO_ROUNDING_ULPS * jnp.finfo(dtype).eps * jnp.abs(s.value)

        def ls_cond(c):
            alpha, _, _, k, accepted = c
            return (~accepted) & (k < config.ls_max_evals)

        def ls_body(c):
            alpha, _, _, k, _ = c
            wt = s.w + alpha * direction
            vt, gt = value_and_grad_fn(wt)
            ok = vt <= s.value + config.ls_c1 * alpha * dphi0 + slack
            return (
                jnp.where(ok, alpha, alpha * 0.5),
                vt,
                gt,
                k + 1,
                ok,
            )

        w_full = s.w + direction
        v_full, g_full = value_and_grad_fn(w_full)
        acc0 = v_full <= s.value + config.ls_c1 * dphi0 + slack
        alpha, v_new, g_new, ls_evals, ls_ok = lax.while_loop(
            ls_cond,
            ls_body,
            (
                jnp.where(acc0, jnp.asarray(1.0, dtype), jnp.asarray(0.5, dtype)),
                v_full,
                g_full,
                jnp.int32(1),
                acc0,
            ),
        )
        w_new = s.w + alpha * direction
        w_new = jnp.where(ls_ok, w_new, s.w)
        v_new = jnp.where(ls_ok, v_new, s.value)
        g_new = jnp.where(ls_ok, g_new, s.grad)

        it = s.iteration + 1
        gnorm = jnp.linalg.norm(g_new)
        reason = check_convergence(
            s.value,
            v_new,
            gnorm,
            s.value_initial,
            s.grad_norm_initial,
            it,
            config.max_iters,
            config.tolerance,
        )
        reason = jnp.where(
            (~ls_ok)
            & (reason != ConvergenceReason.GRADIENT_CONVERGED)
            & (reason != ConvergenceReason.MAX_ITERATIONS),
            jnp.int32(ConvergenceReason.OBJECTIVE_NOT_IMPROVING),
            reason,
        )
        values, grad_norms = record_state(
            s.values, s.grad_norms, it, v_new, gnorm
        )
        return _NewtonState(
            w=w_new,
            value=v_new,
            grad=g_new,
            iteration=it,
            reason=reason,
            value_initial=s.value_initial,
            grad_norm_initial=s.grad_norm_initial,
            values=values,
            grad_norms=grad_norms,
            w_history=record_model(s.w_history, it, w_new),
            evals=s.evals + ls_evals,
            step_tape=record_tape(
                s.step_tape, it, jnp.where(ls_ok, alpha, 0.0)
            ),
            eval_tape=record_tape(
                s.eval_tape, it, ls_evals.astype(s.eval_tape.dtype)
            ),
        )

    final = lax.while_loop(
        lambda s: s.reason == ConvergenceReason.NOT_CONVERGED, body, init
    )
    return SolverResult(
        w=final.w,
        value=final.value,
        grad=final.grad,
        iterations=final.iteration,
        reason=final.reason,
        values=final.values,
        grad_norms=final.grad_norms,
        w_history=final.w_history if config.track_models else None,
        evals=final.evals,
        step_tape=final.step_tape,
        eval_tape=final.eval_tape,
    )
