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


# Dimension bound for the unrolled Cholesky path. What the v5e reads
# (ledger, PRs 29 to 31; PERF.md section 6): the first unrolled form kept
# the factor in a (d, d) array and wrote it with ``L.at[j:, j].set`` /
# ``y.at[i].set``, about 170 slice updates at d 16. Under ``vmap`` each
# became an update of a whole (E, 16, 16) array, and what XLA did not do
# in place it copied: a bucket of 641,842 two-row entities took 295 ms a
# solve of two Newton iterations, 50 ms of it in the updates and 80 ms in
# the copies and slices around them, and the vmapped solve was 60% of
# ``game_fe_re.cd``'s device time and 47% of ``game_music_2re.cd``'s (PR
# 29 lines: operations numbered ``fusion.1524`` / ``copy.34446``, 220 MB
# of code). In the form below every entry of the factor is a value of its
# own and nothing is updated in place: the same bucket takes 27 ms, 14 of
# them the two Cholesky solves (PR 30's lines hold the driver's
# measurement of the mechanism: 2.839 -> 1.253 s and 4.521 -> 2.559 s a
# job; PR 31's are in PERF.md). What it costs is the count of operations
# to trace and compile, d^3 / 6 multiplies and as many subtractions: 1.1 k
# at d 16, 7 k at d 32. The bound is where the chip was read (PR 31, a
# bucket of 65,536 entities 8 rows deep, two Newton iterations, this form
# against lax's Cholesky, ms a solve and seconds to compile): d 16 1.2 ms /
# 7 s against 454 ms / 2 s; d 24 4.4 ms / 29 s against 695 ms / 2 s; d 32
# 22 ms / 80 s against 958 ms / 4 s, in 0.9 GB of scratch against 3.2 GB.
# Batched, d 32 has paid for its compile after 81 solves of such a bucket
# (a GAME job makes 16 to 64). A solve that is not batched gains nothing
# at run time and pays the same compile once a shape (22 s on a CPU at d
# 32, under 1 s at d 16); it takes this form too, so that an entity solved
# alone and the same entity in a bucket compute the same numbers. Above
# 32 nothing is measured. tests/test_newton_entity_minor.py holds the
# count of operations a lowered solve has at the bound.
_UNROLLED_CHO_MAX_DIM = 32

# Columns of the factor, and entries of a substitution, between two
# ``optimization_barrier``s. Left to itself XLA fuses an entry's whole
# history into each of its readers, and a late entry recomputes every
# earlier one (d 16 under vmap: 17 GB read where the matrix is 0.7 GB);
# a barrier after every value makes each a pass over memory of its own.
# Measured on the v5e (PERF.md section 6, PR 31): groups of four.
_CHO_GROUP = 4


def solves_elementwise(dim: int) -> bool:
    """Whether a Newton step at this dimension is solved by the unrolled
    :func:`_small_cho_solve`: a coordinate's solve asks, to build its
    Hessian in the same form, entity-minor under ``vmap``
    (``GLMObjective.hessian_row_sum``)."""
    return dim <= _UNROLLED_CHO_MAX_DIM


def _settled(values: list, total: int) -> list:
    """``values`` with its last group behind an ``optimization_barrier``
    once the group is whole (``_CHO_GROUP`` entries, or what is left of
    ``total``): computed once, whoever reads them."""
    n = len(values)
    if n % _CHO_GROUP and n != total:
        return values
    start = (n - 1) // _CHO_GROUP * _CHO_GROUP
    return values[:start] + list(lax.optimization_barrier(values[start:]))


@jax.jit
def _small_cho_solve(h: jax.Array, b: jax.Array, shift=0.0) -> jax.Array:
    """(H + shift I)^{-1} b for SPD H (d, d) of STATIC small d: Cholesky
    (left-looking), then forward and back substitution, every entry of
    the factor and of both solutions a scalar of its own.

    Only multiplies, subtracts and an ``rsqrt`` a column combine them: no
    matrix is built or updated in place, so under ``vmap`` each value is
    an (E,) vector with the entities on the lanes. A non-PD matrix yields
    NaNs exactly like the lax factorization (``rsqrt`` of a pivot that is
    not positive), so the retry detection of :func:`_newton_direction` is
    unchanged. Jitted so that its few thousand operations are traced once
    a process and dimension, not once a bucket and caller (the plain and
    the jittered solve are one program: ``shift`` is an argument)."""
    d = b.shape[-1]
    low = []  # low[j][i - j] = L[i, j], scaled by inv[j] = 1 / L[j, j]
    inv = []
    for j in range(d):
        col = []
        for i in range(j, d):
            acc = h[i, j] + shift if i == j else h[i, j]
            for k in range(j):
                acc = lax.sub(acc, lax.mul(low[k][i - k], low[k][j - k]))
            if i == j:
                inv.append(lax.rsqrt(acc))
            col.append(lax.mul(acc, inv[j]))
        low.append(col)
        low, inv = _settled(low, d), _settled(inv, d)
    y = []
    for i in range(d):
        acc = b[i]
        for k in range(i):
            acc = lax.sub(acc, lax.mul(low[k][i - k], y[k]))
        y.append(lax.mul(acc, inv[i]))
        y = _settled(y, d)
    x = []  # from the last entry up: x[n] is entry d - 1 - n
    for i in reversed(range(d)):
        acc = y[i]
        for k in range(i + 1, d):
            acc = lax.sub(acc, lax.mul(low[i][k - i], x[d - 1 - k]))
        x.append(lax.mul(acc, inv[i]))
        x = _settled(x, d)
    return jnp.stack(x[::-1])


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


def _levenberg_shift(h: jax.Array) -> jax.Array:
    """What the retry adds to the diagonal of a Hessian that is not
    positive definite: 1e-6 of one plus the diagonal's mean."""
    d = h.shape[-1]
    return 1e-6 * (1.0 + jnp.trace(h, axis1=-2, axis2=-1) / d)


@jax.custom_batching.custom_vmap
def _small_direction(h: jax.Array, grad: jax.Array) -> jax.Array:
    """-H^{-1} grad by :func:`_small_cho_solve`, from the jittered matrix
    where the plain factorization met one that is not positive definite.
    The jittered solve runs only then."""
    p = _small_cho_solve(h, -grad, jnp.zeros((), h.dtype))
    bad = ~jnp.all(jnp.isfinite(p))
    return lax.cond(
        bad,
        lambda: _small_cho_solve(h, -grad, _levenberg_shift(h)),
        lambda: p,
    )


@_small_direction.def_vmap
def _small_direction_over_entities(axis_size, in_batched, h, grad):
    """The same over a batch of entities, which sees what one entity
    cannot: whether ANY lane needs the retry. ``vmap`` alone turns the
    ``cond`` into a select and every pass pays both solves; here the
    second, as many operations again, runs only if some lane's first
    failed (with l2 > 0 none does), and is selected lane by lane."""
    lead = lambda x, batched: (
        x if batched else jnp.broadcast_to(x, (axis_size,) + x.shape)
    )
    h, grad = lead(h, in_batched[0]), lead(grad, in_batched[1])
    solve = jax.vmap(_small_cho_solve)
    # a shift for every lane in both solves: one batched program
    p = solve(h, -grad, jnp.zeros((axis_size,), h.dtype))
    bad = ~jnp.all(jnp.isfinite(p), axis=-1)

    def retry():
        jittered = solve(h, -grad, _levenberg_shift(h))
        return jnp.where(bad[:, None], jittered, p)

    return lax.cond(jnp.any(bad), retry, lambda: p), True


@jax.named_scope("newton_direction")
def _newton_direction(h: jax.Array, grad: jax.Array) -> jax.Array:
    """Solve H p = -grad by Cholesky, retrying with a Levenberg jitter
    when H is not positive definite: the jittered solve is selected where
    the plain factorization produced NaNs."""
    d = grad.shape[-1]
    if solves_elementwise(d):
        return _small_direction(h, grad)
    solve = lambda mat: jax.scipy.linalg.cho_solve(
        jax.scipy.linalg.cho_factor(mat), -grad
    )
    p = solve(h)
    bad = ~jnp.all(jnp.isfinite(p))
    p_jittered = solve(h + _levenberg_shift(h) * jnp.eye(d, dtype=h.dtype))
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
