"""L-BFGS (two-loop recursion) and OWL-QN, from scratch as jitted JAX.

Rebuild of ``optimization/LBFGS.scala:41-133`` which wraps breeze's
``LBFGS``/``OWLQN``. No breeze here: the limited-memory history is a
fixed-size ring buffer of device arrays (static shapes for XLA), the
direction is the classic two-loop recursion, the line search is
solvers/linesearch.py's strong Wolfe (L-BFGS) or orthant-projected
backtracking (OWL-QN, after Andrew & Gao 2007 — breeze's algorithm).

Everything is a ``lax.while_loop`` over a pytree state: one instantiation
jits for the global sharded solve, the same code under ``jax.vmap`` is the
batched per-entity solver (masked trips after per-entity convergence cost
compute but preserve state — the standard TPU padding trade).

Defaults (maxIter 80, tol 1e-7, 10 corrections) per
``optimization/LBFGS.scala:129-133``.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.solvers.common import (
    model_buffer,
    record_model,
    ConvergenceReason,
    SolverConfig,
    SolverResult,
    check_convergence,
    project_to_hypercube,
    record_state,
    record_tape,
    tape_buffer,
    tracker_buffers,
)
from photon_ml_tpu.solvers.linesearch import strong_wolfe

ValueAndGrad = Callable[[jax.Array], Tuple[jax.Array, jax.Array]]


class _History(NamedTuple):
    """Ring buffer of (s, y) correction pairs. head = next write slot."""

    s: jax.Array  # (m, d)
    y: jax.Array  # (m, d)
    rho: jax.Array  # (m,) 1 / (s . y)
    count: jax.Array  # int32, number of valid pairs (<= m)
    head: jax.Array  # int32


def _empty_history(m: int, d: int, dtype) -> _History:
    return _History(
        s=jnp.zeros((m, d), dtype),
        y=jnp.zeros((m, d), dtype),
        rho=jnp.zeros((m,), dtype),
        count=jnp.int32(0),
        head=jnp.int32(0),
    )


def _push_history(h: _History, s: jax.Array, y: jax.Array) -> _History:
    """Append a correction pair; skip (no-op) when curvature s.y is not
    positive — the standard safeguard replacing breeze's internal handling."""
    sy = jnp.vdot(s, y)
    ok = sy > 1e-10 * jnp.maximum(jnp.vdot(y, y), 1e-30)

    def push(h):
        i = h.head
        return _History(
            s=h.s.at[i].set(s),
            y=h.y.at[i].set(y),
            rho=h.rho.at[i].set(1.0 / sy),
            count=jnp.minimum(h.count + 1, h.s.shape[0]),
            head=(h.head + 1) % h.s.shape[0],
        )

    return lax.cond(ok, push, lambda h: h, h)


def _two_loop_sequential(h: _History, grad: jax.Array) -> jax.Array:
    """Classic two-loop recursion, one (d,)-vector dot/axpy per history
    slot. Kept as the readable reference implementation; production uses
    the Gram form below (identical recurrence — drilled to 1e-12 in
    tests/test_solvers.py)."""
    m = h.s.shape[0]

    def backward(i, carry):
        q, alphas = carry
        j = (h.head - 1 - i) % m
        valid = i < h.count
        alpha = jnp.where(valid, h.rho[j] * jnp.vdot(h.s[j], q), 0.0)
        q = q - alpha * h.y[j]
        return q, alphas.at[j].set(alpha)

    q, alphas = lax.fori_loop(
        0, m, backward, (grad, jnp.zeros((m,), grad.dtype))
    )

    newest = (h.head - 1) % m
    y_newest = h.y[newest]
    gamma = jnp.where(
        h.count > 0,
        jnp.vdot(h.s[newest], y_newest)
        / jnp.maximum(jnp.vdot(y_newest, y_newest), 1e-30),
        1.0,
    )
    r = gamma * q

    def forward(i, r):
        j = (h.head - h.count + i) % m  # oldest -> newest among valid
        valid = i < h.count
        beta = jnp.where(valid, h.rho[j] * jnp.vdot(h.y[j], r), 0.0)
        return r + jnp.where(valid, alphas[j] - beta, 0.0) * h.s[j]

    return lax.fori_loop(0, m, forward, r)


def _two_loop(h: _History, grad: jax.Array) -> jax.Array:
    """Two-loop recursion in GRAM form: the same alpha/beta recurrence,
    but every (d,)-vector contraction batched into five (m, d) matmuls.

    The sequential form issues ~4m small sharded-vector ops per
    direction, and under a 'feature' mesh every ``vdot`` over the
    sharded coefficient axis is its OWN scalar all-reduce — ~2m
    collective latencies per L-BFGS iteration (docs/PARALLEL.md). Here the cross-terms come from one (m, m) Gram
    ``G = S Y^T`` plus two stacked history-vector products, so a
    direction costs O(1) collectives regardless of m; the recurrences
    themselves run on (m,)-replicated scalars. Expanding the recursion:

        alpha_i = rho_i (s_i.g - sum_{l newer} alpha_l s_i.y_l)
        q       = g - Y^T alpha
        beta_i  = rho_i (gamma y_i.q + sum_{l older} (alpha_l - beta_l)
                                         y_i.s_l)
        r       = gamma q + S^T (alpha - beta)

    — algebraically identical to the sequential loop (the float
    summation order inside each dot differs; equality is drilled to
    1e-12 in tests/test_solvers.py). Invalid ring slots keep rho=0 and
    mask to zero exactly as before."""
    m = h.s.shape[0]
    dtype = grad.dtype
    pos = jnp.arange(m, dtype=jnp.int32)
    # backward order: newest -> oldest; slot j processed at step i
    order_b = (h.head - 1 - pos) % m
    step_of = jnp.zeros((m,), jnp.int32).at[order_b].set(pos)
    valid = pos < h.count  # by backward step
    valid_slot = valid[step_of]  # by ring slot

    G = h.s @ h.y.T  # (m, m): G[a, b] = s_a . y_b — ONE contraction
    sg = h.s @ grad  # (m,)
    rho = h.rho

    def backward(i, alphas):
        j = order_b[i]
        cross = jnp.sum(
            jnp.where(step_of < i, alphas * G[j, :], 0.0)
        )
        alpha = jnp.where(
            valid[i], rho[j] * (sg[j] - cross), 0.0
        )
        return alphas.at[j].set(alpha)

    alphas = lax.fori_loop(
        0, m, backward, jnp.zeros((m,), dtype)
    )
    q = grad - h.y.T @ alphas

    newest = (h.head - 1) % m
    gamma = jnp.where(
        h.count > 0,
        G[newest, newest]
        / jnp.maximum(jnp.vdot(h.y[newest], h.y[newest]), 1e-30),
        1.0,
    )
    yq = h.y @ q  # (m,)
    # forward order: oldest -> newest among valid; reuse G transposed
    # (y_j . s_l = G[l, j])
    order_f = (h.head - h.count + pos) % m
    fstep_of = jnp.zeros((m,), jnp.int32).at[order_f].set(pos)

    def forward(i, betas):
        j = order_f[i]
        coeff = jnp.where(
            (fstep_of < i) & valid_slot, alphas - betas, 0.0
        )
        cross = jnp.sum(coeff * G[:, j])
        beta = jnp.where(
            valid[i], rho[j] * (gamma * yq[j] + cross), 0.0
        )
        return betas.at[j].set(beta)

    betas = lax.fori_loop(0, m, forward, jnp.zeros((m,), dtype))
    coeff = jnp.where(valid_slot, alphas - betas, 0.0)
    return gamma * q + h.s.T @ coeff


class _LbfgsState(NamedTuple):
    w: jax.Array
    value: jax.Array
    grad: jax.Array
    hist: _History
    iteration: jax.Array
    reason: jax.Array
    value_initial: jax.Array
    grad_norm_initial: jax.Array
    values: jax.Array
    grad_norms: jax.Array
    w_history: jax.Array
    evals: jax.Array  # total value_and_grad calls (full design passes)
    # per-iteration convergence tapes (track_states; one slot off):
    # accepted step size, line-search evaluations
    step_tape: jax.Array
    eval_tape: jax.Array


def minimize_lbfgs(
    value_and_grad_fn: ValueAndGrad,
    w0: jax.Array,
    config: SolverConfig = SolverConfig(),
) -> SolverResult:
    """Minimize a smooth objective. One strong-Wolfe line search per
    iteration; each line-search eval is a full (distributed) value+grad pass,
    matching the reference's cost model (``LBFGS.scala:68-97``)."""
    d = w0.shape[-1]
    dtype = w0.dtype
    m = config.num_corrections

    w0 = project_to_hypercube(w0, config.lower_bounds, config.upper_bounds)
    v0, g0 = value_and_grad_fn(w0)
    values, grad_norms = tracker_buffers(config.max_iters, dtype, config.track_states)
    gnorm0 = jnp.linalg.norm(g0)
    values, grad_norms = record_state(values, grad_norms, 0, v0, gnorm0)
    w_hist0 = model_buffer(config.max_iters, w0, config.track_models)
    # slot 0: no step yet, one eval (the initial value/grad pass)
    step_tape0 = record_tape(
        tape_buffer(config.max_iters, dtype, config.track_states), 0, 0.0
    )
    eval_tape0 = record_tape(
        tape_buffer(config.max_iters, dtype, config.track_states), 0, 1.0
    )

    init = _LbfgsState(
        w=w0,
        value=v0,
        grad=g0,
        hist=_empty_history(m, d, dtype),
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

    def body(s: _LbfgsState) -> _LbfgsState:
        direction = -_two_loop(s.hist, s.grad)
        dphi0 = jnp.vdot(s.grad, direction)
        # Safeguard: if the two-loop direction is not a descent direction
        # (numerically possible with stale curvature), restart on -grad.
        bad = dphi0 >= 0.0
        direction = jnp.where(bad, -s.grad, direction)
        dphi0 = jnp.where(bad, -jnp.vdot(s.grad, s.grad), dphi0)

        def phi(alpha):
            val, grad = value_and_grad_fn(s.w + alpha * direction)
            return val, jnp.vdot(grad, direction), grad

        # First step: scale to unit-ish length like breeze's init heuristic.
        alpha_init = jnp.where(
            s.hist.count == 0,
            jnp.minimum(1.0, 1.0 / jnp.maximum(jnp.linalg.norm(direction), 1e-30)),
            jnp.asarray(1.0, dtype),
        )
        alpha, v_ls, g_ls, ls_ok, ls_evals = strong_wolfe(
            phi,
            s.value,
            dphi0,
            alpha_init,
            g0=s.grad,
            c1=config.ls_c1,
            c2=config.ls_c2,
            max_evals=config.ls_max_evals,
        )

        w_new = s.w + alpha * direction
        has_bounds = (
            config.lower_bounds is not None
            or config.upper_bounds is not None
        )
        if has_bounds:
            # projection moves the point off the search ray, so the
            # line-search gradient no longer applies — re-evaluate
            w_new = project_to_hypercube(
                w_new, config.lower_bounds, config.upper_bounds
            )
            v_new, g_new = value_and_grad_fn(w_new)
            iter_evals = ls_evals + 1
        else:
            # the accepted point IS the last line-search point: reuse its
            # value and gradient instead of paying one more design pass
            v_new, g_new = v_ls, g_ls
            iter_evals = ls_evals
        hist = _push_history(s.hist, w_new - s.w, g_new - s.grad)

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
        # A dead line search means no further progress is possible. It also
        # leaves w unchanged (alpha=0), so the |df|=0 function-value test
        # would fire spuriously — the override replaces that spurious
        # FUNCTION_VALUES_CONVERGED (and NOT_CONVERGED), but never a
        # genuinely converged gradient nor MAX_ITERATIONS, which the
        # reference checks first (``AbstractOptimizer.scala:49-63``).
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
        return _LbfgsState(
            w=w_new,
            value=v_new,
            grad=g_new,
            hist=hist,
            iteration=it,
            reason=reason,
            value_initial=s.value_initial,
            grad_norm_initial=s.grad_norm_initial,
            values=values,
            grad_norms=grad_norms,
            w_history=record_model(s.w_history, it, w_new),
            evals=s.evals + iter_evals,
            step_tape=record_tape(s.step_tape, it, alpha),
            eval_tape=record_tape(
                s.eval_tape, it, iter_evals.astype(s.eval_tape.dtype)
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


# ---------------------------------------------------------------------------
# OWL-QN (Orthant-Wise Limited-memory Quasi-Newton), for L1 objectives.
# ---------------------------------------------------------------------------


def _pseudo_gradient(w: jax.Array, g: jax.Array, l1: jax.Array) -> jax.Array:
    """Pseudo-gradient of f(w) + l1*||w||_1 (Andrew & Gao 2007, eq. 4)."""
    right = g + l1  # derivative approaching from the right (w -> 0+)
    left = g - l1  # from the left
    pg_zero = jnp.where(left > 0.0, left, jnp.where(right < 0.0, right, 0.0))
    return jnp.where(w > 0.0, g + l1, jnp.where(w < 0.0, g - l1, pg_zero))


class _OwlqnState(NamedTuple):
    w: jax.Array
    value: jax.Array  # smooth part f(w)
    full_value: jax.Array  # f(w) + l1 ||w||_1  (convergence + tracking)
    grad: jax.Array  # smooth gradient
    hist: _History
    iteration: jax.Array
    reason: jax.Array
    value_initial: jax.Array
    grad_norm_initial: jax.Array
    values: jax.Array
    grad_norms: jax.Array
    w_history: jax.Array
    evals: jax.Array  # total value_and_grad calls (full design passes)
    # per-iteration convergence tapes (see _LbfgsState)
    step_tape: jax.Array
    eval_tape: jax.Array


def minimize_owlqn(
    value_and_grad_fn: ValueAndGrad,
    w0: jax.Array,
    l1_weight,
    config: SolverConfig = SolverConfig(),
) -> SolverResult:
    """Minimize f(w) + l1*||w||_1.

    value_and_grad_fn is the SMOOTH part only; the L1 term is handled via
    pseudo-gradient + orthant projection exactly as breeze's OWLQN (the
    reference selects it when the objective carries ``L1RegularizationTerm``,
    ``optimization/LBFGS.scala:56-66``). History pairs use smooth gradients;
    the line search is projected backtracking.
    """
    dtype = w0.dtype
    d = w0.shape[-1]
    m = config.num_corrections
    l1 = jnp.asarray(l1_weight, dtype)

    v0, g0 = value_and_grad_fn(w0)
    f0 = v0 + l1 * jnp.sum(jnp.abs(w0))
    pg0 = _pseudo_gradient(w0, g0, l1)
    pgnorm0 = jnp.linalg.norm(pg0)
    values, grad_norms = tracker_buffers(config.max_iters, dtype, config.track_states)
    values, grad_norms = record_state(values, grad_norms, 0, f0, pgnorm0)
    w_hist0 = model_buffer(config.max_iters, w0, config.track_models)
    step_tape0 = record_tape(
        tape_buffer(config.max_iters, dtype, config.track_states), 0, 0.0
    )
    eval_tape0 = record_tape(
        tape_buffer(config.max_iters, dtype, config.track_states), 0, 1.0
    )

    init = _OwlqnState(
        w=w0,
        value=v0,
        full_value=f0,
        grad=g0,
        hist=_empty_history(m, d, dtype),
        iteration=jnp.int32(0),
        reason=jnp.where(
            pgnorm0 == 0.0,
            jnp.int32(ConvergenceReason.GRADIENT_CONVERGED),
            jnp.int32(ConvergenceReason.NOT_CONVERGED),
        ),
        value_initial=f0,
        grad_norm_initial=pgnorm0,
        values=values,
        grad_norms=grad_norms,
        w_history=w_hist0,
        evals=jnp.int32(1),
        step_tape=step_tape0,
        eval_tape=eval_tape0,
    )

    def body(s: _OwlqnState) -> _OwlqnState:
        pg = _pseudo_gradient(s.w, s.grad, l1)
        direction = -_two_loop(s.hist, pg)
        # Sign alignment: discard components that disagree with -pg.
        direction = jnp.where(direction * pg < 0.0, direction, 0.0)
        # Fall back to steepest (pseudo) descent if alignment zeroed it out.
        degenerate = jnp.vdot(direction, direction) == 0.0
        direction = jnp.where(degenerate, -pg, direction)

        # Orthant for the projected step: sign(w), or sign(-pg) at w == 0.
        xi = jnp.where(s.w != 0.0, jnp.sign(s.w), jnp.sign(-pg))

        def trial(alpha):
            wt = s.w + alpha * direction
            wt = jnp.where(wt * xi > 0.0, wt, 0.0)  # orthant projection
            vt, gt = value_and_grad_fn(wt)
            ft = vt + l1 * jnp.sum(jnp.abs(wt))
            return wt, vt, ft, gt

        alpha0 = jnp.where(
            s.hist.count == 0,
            1.0 / jnp.maximum(jnp.linalg.norm(direction), 1e-30),
            jnp.asarray(1.0, dtype),
        )

        # Backtracking with the Armijo-like acceptance of Andrew & Gao:
        #   F(w') <= F(w) + c1 * pg . (w' - w)
        def ls_cond(c):
            alpha, _, _, _, _, k, accepted = c
            return (~accepted) & (k < config.ls_max_evals)

        def ls_body(c):
            alpha, wt, vt, ft, gt, k, _ = c
            wt, vt, ft, gt = trial(alpha)
            accepted = ft <= s.full_value + config.ls_c1 * jnp.vdot(pg, wt - s.w)
            alpha_next = jnp.where(accepted, alpha, alpha * 0.5)
            return alpha_next, wt, vt, ft, gt, k + 1, accepted

        wt0, vt0, ft0, gt0 = trial(alpha0)
        acc0 = ft0 <= s.full_value + config.ls_c1 * jnp.vdot(pg, wt0 - s.w)
        alpha, w_new, v_new, f_new, g_new, ls_evals, ls_ok = lax.while_loop(
            ls_cond,
            ls_body,
            (jnp.where(acc0, alpha0, alpha0 * 0.5), wt0, vt0, ft0, gt0,
             jnp.int32(1), acc0),
        )
        # On an exhausted line search keep the previous iterate — never
        # commit a rejected trial point (matches minimize_lbfgs's alpha=0).
        w_new = jnp.where(ls_ok, w_new, s.w)
        v_new = jnp.where(ls_ok, v_new, s.value)
        f_new = jnp.where(ls_ok, f_new, s.full_value)
        g_new = jnp.where(ls_ok, g_new, s.grad)

        hist = _push_history(s.hist, w_new - s.w, g_new - s.grad)
        pg_new = _pseudo_gradient(w_new, g_new, l1)
        pgnorm = jnp.linalg.norm(pg_new)
        it = s.iteration + 1
        reason = check_convergence(
            s.full_value,
            f_new,
            pgnorm,
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
            s.values, s.grad_norms, it, f_new, pgnorm
        )
        return _OwlqnState(
            w=w_new,
            value=v_new,
            full_value=f_new,
            grad=g_new,
            hist=hist,
            iteration=it,
            reason=reason,
            value_initial=s.value_initial,
            grad_norm_initial=s.grad_norm_initial,
            values=values,
            grad_norms=grad_norms,
            w_history=record_model(s.w_history, it, w_new),
            evals=s.evals + ls_evals,
            # a dead line search commits no step: tape the honest 0.0
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
        value=final.full_value,
        grad=_pseudo_gradient(final.w, final.grad, l1),
        iterations=final.iteration,
        reason=final.reason,
        values=final.values,
        grad_norms=final.grad_norms,
        w_history=final.w_history if config.track_models else None,
        evals=final.evals,
        step_tape=final.step_tape,
        eval_tape=final.eval_tape,
    )


def record_solve_metrics(
    result: SolverResult, registry=None, owlqn: bool = False
) -> None:
    """L-BFGS / OWL-QN counters into the obs registry:
    ``solver.<lbfgs|owlqn>.iterations`` plus ``.evals`` (value+grad
    passes == full design reads, the pass-cost ceiling basis). Host-side
    and synchronizing; callers gate on observability being enabled."""
    from photon_ml_tpu.solvers.common import record_solver_metrics

    record_solver_metrics("owlqn" if owlqn else "lbfgs", result, registry)
