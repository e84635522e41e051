"""TRON: trust-region Newton with truncated conjugate gradient, fully jitted.

Rebuild of ``optimization/TRON.scala:82-320`` (itself derived from
LIBLINEAR's tron.cpp — the algorithmic constants below are the ones the
reference fixes at ``TRON.scala:97-98,230-237``):

  - trust-region acceptance thresholds (eta0, eta1, eta2) = (1e-4, .25, .75)
  - radius update factors (sigma1, sigma2, sigma3) = (.25, .5, 4)
  - inner CG: <= 20 iterations, tolerance 0.1 * ||g|| (under a solver
    tolerance of zero the residual test is off: the budget is the rule)
  - <= 5 consecutive improvement failures, then give up
  - defaults maxIter 15, tol 1e-5 (gradient-based)

The inner CG is a ``lax.while_loop`` over Hessian-vector products — each HVP
is one fused analytic pass over the (sharded) batch
(``ops/objective.GLMObjective.hessian_vector``), the TPU analog of the
reference's per-CG-iteration broadcast + treeAggregate
(``TRON.scala:272-285``). The whole outer loop is also a while_loop, so a
complete TRON solve is ONE XLA computation: no host round-trips at all,
where the reference pays a cluster round-trip per CG step.

TRON is L2-only in the reference (enforced at
``optimization/game/OptimizationProblem.scala:155-161``); callers enforce
the same (models/training layer).
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
Hvp = Callable[[jax.Array, jax.Array], jax.Array]

_ETA0, _ETA1, _ETA2 = 1e-4, 0.25, 0.75
_SIGMA1, _SIGMA2, _SIGMA3 = 0.25, 0.5, 4.0

TRON_DEFAULT_CONFIG = SolverConfig(max_iters=15, tolerance=1e-5)

# Under a tolerance of zero (the budget is the rule) the ratio test gets
# the room the objective's own sum needs, as Newton's Armijo test has
# (``newton._ARMIJO_ROUNDING_ULPS``): a step whose PREDICTED reduction is
# under this many ulps of the value cannot be judged by evaluating the
# value twice, so it is taken and the radius is left alone. Without it the
# late outer iterations of a budgeted solve accept or reject on rounding,
# a rejection shrinks the radius, and the next CG ends on the boundary
# after one pass where it would have made its whole budget (61, 52 and 42
# CG iterations for 80 in one descent: PERF.md section 6, PR 36).
_RATIO_ROUNDING_ULPS = 1024.0


class _CGState(NamedTuple):
    step: jax.Array  # current solution s
    r: jax.Array  # residual -g - H s
    p: jax.Array  # search direction
    rtr: jax.Array
    i: jax.Array
    done: jax.Array


def _truncated_cg(
    hvp: Callable[[jax.Array], jax.Array],
    grad: jax.Array,
    delta: jax.Array,
    max_cg: int,
    cg_tol_factor: float,
):
    """Solve H s ~= -grad with ||s|| <= delta (``TRON.scala:252-319``).

    Returns (s, r). Exits on residual < cg_tol_factor * ||grad||, on hitting
    the trust-region boundary (step clipped to the sphere), or on max_cg.
    A ``cg_tol_factor`` of zero leaves the boundary and ``max_cg`` (and a
    residual of exactly zero, where there is nothing left to solve).
    """
    cg_tol = cg_tol_factor * jnp.linalg.norm(grad)

    init = _CGState(
        step=jnp.zeros_like(grad),
        r=-grad,
        p=-grad,
        rtr=jnp.vdot(grad, grad),
        i=jnp.int32(0),
        done=jnp.linalg.norm(grad) <= cg_tol,
    )

    def body(s: _CGState) -> _CGState:
        hp = hvp(s.p)
        php = jnp.vdot(s.p, hp)
        # Guard: non-positive curvature should not happen for convex GLM+L2,
        # but protect the division anyway; treat as boundary hit.
        alpha = s.rtr / jnp.where(php > 0.0, php, 1e-30)
        step_try = s.step + alpha * s.p
        outside = (jnp.linalg.norm(step_try) > delta) | (php <= 0.0)

        def to_boundary(_):
            # Backtrack to the sphere: find tau >= 0 with ||step + tau p|| = delta.
            sp = jnp.vdot(s.step, s.p)
            ss = jnp.vdot(s.step, s.step)
            pp = jnp.vdot(s.p, s.p)
            rad = jnp.sqrt(jnp.maximum(sp * sp + pp * (delta * delta - ss), 0.0))
            tau = jnp.where(
                sp >= 0.0,
                (delta * delta - ss) / jnp.maximum(sp + rad, 1e-30),
                (rad - sp) / jnp.maximum(pp, 1e-30),
            )
            return s._replace(
                step=s.step + tau * s.p,
                r=s.r - tau * hp,
                i=s.i + 1,
                done=jnp.bool_(True),
            )

        def interior(_):
            r_new = s.r - alpha * hp
            rtr_new = jnp.vdot(r_new, r_new)
            beta = rtr_new / jnp.maximum(s.rtr, 1e-30)
            return _CGState(
                step=step_try,
                r=r_new,
                p=r_new + beta * s.p,
                rtr=rtr_new,
                i=s.i + 1,
                done=jnp.sqrt(rtr_new) <= cg_tol,
            )

        return lax.cond(outside, to_boundary, interior, None)

    final = lax.while_loop(
        lambda s: (~s.done) & (s.i < max_cg), body, init
    )
    return final.step, final.r, final.i


class _TronState(NamedTuple):
    w: jax.Array
    value: jax.Array
    grad: jax.Array
    curv: jax.Array  # curvature carry for the CG (vgc mode; scalar 0 else)
    delta: jax.Array  # trust-region radius
    failures: jax.Array
    iteration: jax.Array
    reason: jax.Array
    value_initial: jax.Array
    grad_norm_initial: jax.Array
    values: jax.Array
    grad_norms: jax.Array
    cg_total: jax.Array
    w_history: jax.Array
    # per-outer-step convergence tapes (track_states; one slot off):
    # trust-region radius after the step's update, inner CG iterations
    radius_tape: jax.Array
    cg_tape: jax.Array


def minimize_tron(
    value_and_grad_fn: ValueAndGrad,
    hvp_fn: Hvp,
    w0: jax.Array,
    config: SolverConfig = TRON_DEFAULT_CONFIG,
    hvp_setup_fn=None,
    hvp_at_fn=None,
    vgc_fn=None,
) -> SolverResult:
    """Minimize a twice-differentiable objective via trust-region Newton-CG.

    ``hvp_setup_fn(w) -> carry`` / ``hvp_at_fn(carry, v) -> Hv`` split the
    Hessian-vector product into its w-only part (computed ONCE per outer
    iteration — for GLMs the (n,) curvature weights, one design pass) and
    the per-CG-step part (two design passes). Without them every CG step
    recomputes the w-only part through ``hvp_fn`` (three passes) — the
    reference pays the same structure per CG step as a broadcast +
    treeAggregate (``TRON.scala:272-285``).

    ``vgc_fn(w) -> (value, grad, carry)`` goes further: the acceptance
    evaluation at the trial point already computes the margins, so on
    acceptance the NEXT iteration's CG carry is free — no setup pass at
    all. Requires ``hvp_at_fn``; takes precedence over ``hvp_setup_fn``."""
    dtype = w0.dtype
    use_vgc = vgc_fn is not None and hvp_at_fn is not None
    # A tolerance of zero asks for the whole budget, of the inner CG as of
    # the outer loop (``check_convergence``): the CG's relative residual
    # test is a comparison of two sums over the data, and where a residual
    # falls near 0.1 ||g|| the order of the rows, or which rows of a capped
    # entity were sampled, decides whether one more Hessian-vector pass is
    # made (the factored coordinate's projection solve made 141, 148 and
    # 146 passes a job on three seeds of one problem: PERF.md section 6,
    # PR 36). With the test off a CG runs ``tron_max_cg`` iterations unless
    # it reaches the trust-region boundary.
    budget_is_the_rule = not config.tolerance > 0.0
    cg_tol_factor = 0.0 if budget_is_the_rule else config.tron_cg_tol
    if use_vgc:
        v0, g0, c0 = vgc_fn(w0)
    else:
        v0, g0 = value_and_grad_fn(w0)
        c0 = jnp.zeros((), dtype)
    gnorm0 = jnp.linalg.norm(g0)
    values, grad_norms = tracker_buffers(config.max_iters, dtype, config.track_states)
    values, grad_norms = record_state(values, grad_norms, 0, v0, gnorm0)
    w_hist0 = model_buffer(config.max_iters, w0, config.track_models)
    # slot 0 = initial radius / zero CG work before the first step
    radius_tape0 = record_tape(
        tape_buffer(config.max_iters, dtype, config.track_states), 0, gnorm0
    )
    cg_tape0 = record_tape(
        tape_buffer(config.max_iters, dtype, config.track_states), 0, 0.0
    )

    init = _TronState(
        w=w0,
        value=v0,
        grad=g0,
        curv=c0,
        delta=gnorm0,  # initial radius = ||g0|| per LIBLINEAR/TRON.scala:117
        failures=jnp.int32(0),
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
        cg_total=jnp.int32(0),
        w_history=w_hist0,
        radius_tape=radius_tape0,
        cg_tape=cg_tape0,
    )

    def body(s: _TronState) -> _TronState:
        if use_vgc:
            hvp_local = lambda v: hvp_at_fn(s.curv, v)
        elif hvp_setup_fn is not None and hvp_at_fn is not None:
            carry = hvp_setup_fn(s.w)  # loop-invariant across the CG
            hvp_local = lambda v: hvp_at_fn(carry, v)
        else:
            hvp_local = lambda v: hvp_fn(s.w, v)
        step, r, cg_iters = _truncated_cg(
            hvp_local,
            s.grad,
            s.delta,
            config.tron_max_cg,
            cg_tol_factor,
        )
        snorm = jnp.linalg.norm(step)
        gs = jnp.vdot(s.grad, step)
        prered = -0.5 * (gs - jnp.vdot(step, r))

        w_try = s.w + step
        if use_vgc:
            v_try, g_try, c_try = vgc_fn(w_try)
        else:
            v_try, g_try = value_and_grad_fn(w_try)
            c_try = s.curv
        actred = s.value - v_try

        # Radius update (``TRON.scala:136-224``, LIBLINEAR's alpha logic).
        denom = v_try - s.value - gs
        alpha_c = jnp.where(
            denom <= 0.0, _SIGMA3, jnp.maximum(_SIGMA1, -0.5 * (gs / denom))
        )
        # First iteration tightens the radius to the actual step length.
        delta = jnp.where(
            s.iteration == 0, jnp.minimum(s.delta, snorm), s.delta
        )
        alpha_snorm = alpha_c * snorm
        delta_in = delta
        delta = jnp.where(
            actred < _ETA0 * prered,
            jnp.minimum(jnp.maximum(alpha_snorm, _SIGMA1 * snorm), _SIGMA2 * delta),
            jnp.where(
                actred < _ETA1 * prered,
                jnp.maximum(_SIGMA1 * delta, jnp.minimum(alpha_snorm, _SIGMA2 * delta)),
                jnp.where(
                    actred < _ETA2 * prered,
                    jnp.maximum(_SIGMA1 * delta, jnp.minimum(alpha_snorm, _SIGMA3 * delta)),
                    jnp.maximum(delta, jnp.minimum(alpha_snorm, _SIGMA3 * delta)),
                ),
            ),
        )

        accept = actred > _ETA0 * prered
        if budget_is_the_rule:
            slack = _RATIO_ROUNDING_ULPS * jnp.finfo(dtype).eps * jnp.abs(
                s.value
            )
            unresolved = prered <= slack
            accept = accept | (unresolved & (actred >= -slack))
            delta = jnp.where(unresolved, delta_in, delta)
        w_new = jnp.where(accept, w_try, s.w)
        v_new = jnp.where(accept, v_try, s.value)
        g_new = jnp.where(accept, g_try, s.grad)
        c_new = jnp.where(accept, c_try, s.curv) if use_vgc else s.curv
        failures = jnp.where(accept, 0, s.failures + 1)

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
        # Function-value convergence only counts on accepted steps; a
        # rejected step has |dv| = 0 by construction, not by convergence.
        reason = jnp.where(
            (~accept)
            & (reason == ConvergenceReason.FUNCTION_VALUES_CONVERGED),
            jnp.int32(ConvergenceReason.NOT_CONVERGED),
            reason,
        )
        reason = jnp.where(
            (failures >= config.tron_max_failures)
            & (reason == ConvergenceReason.NOT_CONVERGED),
            jnp.int32(ConvergenceReason.OBJECTIVE_NOT_IMPROVING),
            reason,
        )
        values, grad_norms = record_state(
            s.values, s.grad_norms, it, v_new, gnorm
        )
        return _TronState(
            w=w_new,
            value=v_new,
            grad=g_new,
            curv=c_new,
            delta=delta,
            failures=failures,
            iteration=it,
            reason=reason,
            value_initial=s.value_initial,
            grad_norm_initial=s.grad_norm_initial,
            values=values,
            grad_norms=grad_norms,
            cg_total=s.cg_total + cg_iters,
            w_history=record_model(s.w_history, it, w_new),
            radius_tape=record_tape(s.radius_tape, it, delta),
            cg_tape=record_tape(
                s.cg_tape, it, cg_iters.astype(s.cg_tape.dtype)
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
        cg_iterations=final.cg_total,
        w_history=final.w_history if config.track_models else None,
        radius_tape=final.radius_tape,
        cg_tape=final.cg_tape,
    )


def record_solve_metrics(result: SolverResult, registry=None) -> None:
    """TRON counters into the obs registry: ``solver.tron.iterations``
    (outer trust-region steps) and ``solver.tron.cg_iterations`` (inner
    CG == Hessian-vector products — the FLOP-accounting basis). Host-side
    and synchronizing; callers gate on observability being enabled."""
    from photon_ml_tpu.solvers.common import record_solver_metrics

    record_solver_metrics("tron", result, registry)
