"""Shared solver machinery: convergence semantics, configs, result pytrees.

Convergence criteria reproduce ``optimization/AbstractOptimizer.scala:49-63``
exactly, *relative to the initial state*:

  - FUNCTION_VALUES_CONVERGED:  |f_prev - f_cur| <= tol * f_initial
  - GRADIENT_CONVERGED:         ||g_cur|| <= tol * ||g_initial||
  - MAX_ITERATIONS
  - OBJECTIVE_NOT_IMPROVING (TRON's improvement-failure budget,
    ``optimization/TRON.scala:136-224``)

Reasons are int32 codes (not Python enums) so they live on device and survive
jit/vmap — per-entity convergence histograms
(``optimization/game/RandomEffectOptimizationTracker.scala:33-110``) are then
one ``jnp.bincount`` away.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.core.types import _pytree_dataclass


class ConvergenceReason(enum.IntEnum):
    """Device-friendly codes; mirrors ``optimization/ConvergenceReason.scala``."""

    NOT_CONVERGED = 0
    MAX_ITERATIONS = 1
    FUNCTION_VALUES_CONVERGED = 2
    GRADIENT_CONVERGED = 3
    OBJECTIVE_NOT_IMPROVING = 4


@dataclasses.dataclass(frozen=True)
class SolverConfig:
    """Static (trace-time) solver knobs.

    Defaults follow the reference: L-BFGS maxIter 80 / tol 1e-7 / 10
    corrections (``optimization/LBFGS.scala:129-133``); TRON overrides via
    ``tron_*`` fields (``optimization/TRON.scala:230-237``).
    """

    max_iters: int = 80
    tolerance: float = 1e-7
    num_corrections: int = 10
    # line search
    ls_max_evals: int = 20
    ls_c1: float = 1e-4
    ls_c2: float = 0.9
    # TRON inner CG (``TRON.scala:252-319``)
    tron_max_cg: int = 20
    tron_cg_tol: float = 0.1
    tron_max_failures: int = 5
    # Box constraints (``optimization/OptimizationUtils.scala``): arrays of
    # shape (d,) or None. Applied by coefficient clipping after each step.
    lower_bounds: Optional[jax.Array] = None
    upper_bounds: Optional[jax.Array] = None
    # Record (value, |grad|) per iteration into fixed-size device buffers
    # (``optimization/OptimizationStatesTracker.scala:33-115``).
    track_states: bool = True
    # Additionally record the COEFFICIENTS per iteration — the reference's
    # ModelTracker (``supervised/model/ModelTracker.scala``), feeding
    # validate-per-iteration (``Driver.scala:293-347``). Costs a
    # (max_iters+1, d) buffer; off by default.
    track_models: bool = False


@_pytree_dataclass
class SolverResult:
    """What a solve returns — all device arrays, so it vmaps cleanly.

    ``values``/``grad_norms`` are (max_iters+1,) tracker buffers; entries at
    index > iterations are garbage and must be masked by callers — use
    :meth:`masked_history` / :func:`mask_tape` instead of re-deriving the
    contract by hand. Mirrors OptimizerState + OptimizationStatesTracker.
    """

    w: jax.Array
    value: jax.Array
    grad: jax.Array
    iterations: jax.Array  # int32
    reason: jax.Array  # int32 ConvergenceReason code
    values: jax.Array  # (max_iters+1,) objective per iteration
    grad_norms: jax.Array  # (max_iters+1,) ||grad|| per iteration
    # total inner CG iterations == Hessian-vector products (TRON only;
    # None for first-order solvers). Feeds FLOP/MFU accounting.
    cg_iterations: Optional[jax.Array] = None
    # total value_and_grad evaluations == full design passes (LBFGS /
    # OWL-QN / NEWTON; None for TRON, whose pass count is
    # iterations + 1 + cg_iterations under the vgc carry). The
    # counted-work basis for pass-cost ceiling decompositions.
    evals: Optional[jax.Array] = None
    # (max_iters+1, d) per-iteration coefficients when track_models
    # (ModelTracker); entries at index > iterations are unwritten zeros
    # and must be masked by callers like the values buffer
    w_history: Optional[jax.Array] = None
    # in-program convergence tapes (track_states; one slot otherwise),
    # decoded by obs/convergence.py — the telemetry that rides the
    # while_loop carry and therefore survives fully device-resident
    # solver loops (no host-side tracer needed):
    # TRON only: trust-region radius after each outer step (slot 0 =
    # the initial radius) and inner CG iterations per outer step
    radius_tape: Optional[jax.Array] = None
    cg_tape: Optional[jax.Array] = None
    # first-order + Newton: accepted step size per iteration (slot 0 =
    # 0) and objective evaluations per iteration (slot 0 = the initial
    # value/grad pass)
    step_tape: Optional[jax.Array] = None
    eval_tape: Optional[jax.Array] = None

    def masked_history(self):
        """Host-side tracker buffers with the entries-past-``iterations``
        garbage removed — THE reader every consumer of ``values`` /
        ``grad_norms`` / ``w_history`` should use instead of slicing by
        hand. Returns ``(values, grad_norms)`` — plus ``w_history`` as a
        third element when it was tracked. Scalar results come back
        TRUNCATED to ``iterations + 1`` entries (``iterations ==
        max_iters`` keeps the full buffer); vmapped results keep the
        full tape length with invalid entries masked to NaN (ragged
        truncation cannot batch). Materializes device arrays."""
        out = [
            mask_tape(self.values, self.iterations),
            mask_tape(self.grad_norms, self.iterations),
        ]
        if self.w_history is not None:
            out.append(mask_tape(self.w_history, self.iterations, axis=-2))
        return tuple(out)


def mask_tape(tape, iterations, axis: int = -1) -> np.ndarray:
    """Apply the tracker-buffer contract (entries past ``iterations``
    are garbage) on the host: truncate along ``axis`` for a scalar
    ``iterations``, NaN-mask for batched ones (a vmapped result's lanes
    stop at different iterations, so truncation cannot batch). Also
    correct for untracked one-slot buffers (index clamps)."""
    arr = np.asarray(tape)
    iters = np.asarray(iterations)
    axis = axis % arr.ndim
    size = arr.shape[axis]
    if iters.ndim == 0:
        n = min(int(iters), size - 1) + 1
        sl = [slice(None)] * arr.ndim
        sl[axis] = slice(0, n)
        return arr[tuple(sl)]
    idx_shape = [1] * arr.ndim
    idx_shape[axis] = size
    idx = np.arange(size).reshape(idx_shape)
    lim = np.minimum(iters, size - 1).reshape(
        list(iters.shape) + [1] * (arr.ndim - iters.ndim)
    )
    return np.where(idx <= lim, arr, np.nan)


def reason_histogram(reasons) -> dict:
    """ConvergenceReason name -> count over an array of reason codes: the
    form every history record's ``convergence_histogram`` takes."""
    return {
        ConvergenceReason(int(r)).name: int(c)
        for r, c in zip(*np.unique(np.asarray(reasons), return_counts=True))
    }


def index_result(result: "SolverResult", i) -> "SolverResult":
    """Element ``i`` of a STACKED SolverResult — the decode reader for
    results whose leaves carry a leading batch/path axis (a vmapped
    per-entity solve, or one lambda of ``train_glm``'s scanned
    regularization path, where every leaf — tapes included — is stacked
    along the scan axis). A lazy tree of device slices: no host sync, so
    decoding a pipelined path stays async until something materializes."""
    return jax.tree_util.tree_map(lambda a: a[i], result)


def final_grad_norm(result: "SolverResult") -> jax.Array:
    """||grad|| at the solve's LAST written tracker slot — valid with
    tracking on (gather at ``iterations``) or off (the one slot holds
    the latest state). Trace-safe and batched-safe; the GAME tracker
    tuples carry this per entity so fleet convergence summaries get a
    final-gradient signal without full tapes."""
    gn = result.grad_norms
    idx = jnp.minimum(result.iterations, gn.shape[-1] - 1)
    return jnp.take_along_axis(gn, idx[..., None], axis=-1)[..., 0]


def design_passes(result: "SolverResult") -> float:
    """Counted full design passes of one completed solve, in the
    2-matmul (one value/grad-equivalent) unit every FLOP accounting in
    the repo uses — bench.py's pipelined-MFU numerator and the cost
    book's per-span attribution share THIS function so they cannot
    drift. TRON: iterations + 1 initial vgc + CG Hessian-vector
    products (the curvature weights ride the acceptance evaluation, so
    no extra setup pass). First-order solvers: tracked value/grad
    evaluations. Fallback (exotic results): iterations + 1.
    A vmapped (batched) result sums the counted passes over its batch
    lanes — each lane is one solve. Materializes device scalars —
    callers gate on observability."""
    iters = np.asarray(result.iterations)
    if result.cg_iterations is not None:
        return (
            float(iters.sum())
            + float(iters.size)
            + float(np.asarray(result.cg_iterations).sum())
        )
    if result.evals is not None:
        return float(np.asarray(result.evals).sum())
    return float(iters.sum()) + float(iters.size)


def record_solver_metrics(prefix: str, result: "SolverResult", registry=None) -> None:
    """Feed one completed solve's counters into the metrics registry
    under ``solver.<prefix>.*`` plus the cross-optimizer aggregate
    ``solver.iterations`` (docs/OBSERVABILITY.md).

    Materializes the result's iteration counters — a device->host fetch —
    so call sites must gate on observability being enabled: the disabled
    path cannot afford a sync inserted between pipelined solves
    (bench.py's pipelined-solve measurement depends on that)."""
    from photon_ml_tpu import obs

    reg = registry if registry is not None else obs.registry()
    iters = float(np.asarray(result.iterations))
    reg.inc(f"solver.{prefix}.solves")
    reg.inc(f"solver.{prefix}.iterations", iters)
    reg.inc("solver.iterations", iters)
    if result.cg_iterations is not None:
        reg.inc(
            f"solver.{prefix}.cg_iterations",
            float(np.asarray(result.cg_iterations)),
        )
    if result.evals is not None:
        reg.inc(
            f"solver.{prefix}.evals", float(np.asarray(result.evals))
        )


def project_to_hypercube(
    w: jax.Array,
    lower: Optional[jax.Array],
    upper: Optional[jax.Array],
) -> jax.Array:
    """``OptimizationUtils.projectCoefficientsToHypercube`` as jnp.clip."""
    if lower is None and upper is None:
        return w
    return jnp.clip(
        w,
        -jnp.inf if lower is None else lower,
        jnp.inf if upper is None else upper,
    )


def check_convergence(
    value_prev: jax.Array,
    value_cur: jax.Array,
    grad_norm_cur: jax.Array,
    value_initial: jax.Array,
    grad_norm_initial: jax.Array,
    iteration: jax.Array,
    max_iters: int,
    tolerance: float,
) -> jax.Array:
    """Return the ConvergenceReason code (0 = keep going).

    Order matters and follows ``AbstractOptimizer.convergenceReason:49-63``:
    max-iterations, then function values, then gradient.

    A ``tolerance`` of zero asks for the whole iteration budget: the two
    relative tests would then fire only on a value that did not change in
    its last bit (or a gradient of exactly zero), which in float32 is
    rounding luck, so the same solve would do more or fewer passes by the
    order of its rows (PERF.md section 6, PR 29).
    """
    reason = jnp.int32(ConvergenceReason.NOT_CONVERGED)
    if tolerance > 0.0:
        grad_conv = grad_norm_cur <= tolerance * grad_norm_initial
        reason = jnp.where(
            grad_conv, jnp.int32(ConvergenceReason.GRADIENT_CONVERGED), reason
        )
        func_conv = jnp.abs(value_prev - value_cur) <= tolerance * jnp.abs(
            value_initial
        )
        reason = jnp.where(
            func_conv,
            jnp.int32(ConvergenceReason.FUNCTION_VALUES_CONVERGED),
            reason,
        )
    reason = jnp.where(
        iteration >= max_iters, jnp.int32(ConvergenceReason.MAX_ITERATIONS), reason
    )
    return reason


def tracker_buffers(
    max_iters: int, dtype, track: bool = True
) -> Tuple[jax.Array, jax.Array]:
    """Per-iteration (value, ||grad||) buffers. With track=False the buffers
    collapse to one slot (holding the latest state) so vmapped per-entity
    solves don't carry (entities, max_iters) tracker state."""
    size = max_iters + 1 if track else 1
    # +inf sentinel for unwritten slots: obviously not a real (value, |g|)
    # yet compatible with jax_debug_nans (a NaN fill would trip it on the
    # very first buffer conversion)
    return jnp.full((size,), jnp.inf, dtype), jnp.full((size,), jnp.inf, dtype)


def record_state(values, grad_norms, i, value, grad_norm):
    i = jnp.minimum(i, values.shape[0] - 1)
    return values.at[i].set(value), grad_norms.at[i].set(grad_norm)


def tape_buffer(max_iters: int, dtype, track: bool = True) -> jax.Array:
    """One per-iteration tape (radius, step size, CG/eval counts…):
    same sizing/sentinel contract as :func:`tracker_buffers` — one slot
    when tracking is off so vmapped per-entity solves don't carry
    (entities, max_iters) state, +inf fill so unwritten slots are
    obviously not measurements yet jax_debug_nans-safe."""
    size = max_iters + 1 if track else 1
    return jnp.full((size,), jnp.inf, dtype)


def record_tape(tape: jax.Array, i, value) -> jax.Array:
    i = jnp.minimum(i, tape.shape[0] - 1)
    return tape.at[i].set(value)


def model_buffer(max_iters: int, w0: jax.Array, track: bool) -> jax.Array:
    """(max_iters+1, d) per-iteration coefficient buffer (ModelTracker);
    one slot when tracking is off."""
    size = max_iters + 1 if track else 1
    return jnp.zeros((size,) + w0.shape, w0.dtype).at[0].set(w0)


def record_model(buf: jax.Array, i, w: jax.Array) -> jax.Array:
    i = jnp.minimum(i, buf.shape[0] - 1)
    return buf.at[i].set(w)
