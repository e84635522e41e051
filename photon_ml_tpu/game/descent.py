"""Block coordinate descent over GAME coordinates.

Rebuild of ``algorithm/CoordinateDescent.scala:39-198``: for each outer
iteration, update every coordinate in the configured sequence against the
residual of all the others (partial score = total - own), rescore, and
log the full training objective (loss + all regularization terms). The
reference's per-coordinate score RDDs with fullOuterJoin accumulation
(``CoordinateDescent.scala:115-123``) are dense (n,) device arrays here;
"sum of other coordinates' scores" is a subtraction.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import time
from typing import Dict, List, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from photon_ml_tpu import obs
from photon_ml_tpu.core.tasks import TaskType
from photon_ml_tpu.resilience import faults as _faults
from photon_ml_tpu.ops import metrics as metrics_mod
from photon_ml_tpu.solvers.common import ConvergenceReason, reason_histogram


@dataclasses.dataclass
class GameModel:
    """name -> parameters (fixed effect: (d,); random effect: (E, d)).
    The reference's ``model/Model.scala`` hierarchy collapses to this plus
    the coordinates' score() methods."""

    params: Dict[str, jax.Array]

    def copy(self) -> "GameModel":
        return GameModel(params=dict(self.params))


@dataclasses.dataclass
class CoordinateUpdateRecord:
    """One coordinate update's observability snapshot — the analog of the
    reference's per-coordinate logging + optimization trackers
    (``CoordinateDescent.scala:160-189``, ``optimization/game/*Tracker``)."""

    iteration: int
    coordinate: str
    objective: float
    # host wall time for THIS coordinate's update. In fused mode the whole
    # pass is one dispatch, so per-coordinate splits are unknowable: the
    # pass wall time is recorded on the FIRST coordinate's record and the
    # rest carry None (an even split would mislead anyone comparing
    # coordinate costs across fused/unfused runs).
    seconds: Optional[float]
    solver_iterations: float  # mean over entities for random effects
    convergence_histogram: Dict[str, int]
    # validation metric after this update, when a validation_fn is supplied
    # (``CoordinateDescent.scala:173-189``)
    validation_metric: Optional[float] = None
    # divergence-guard annotation: None for a normal update, "recovered"
    # when a non-finite update was rolled back and the damped retry
    # succeeded, "frozen" when the retry also failed and the coordinate
    # was excluded from further training (docs/ROBUSTNESS.md)
    event: Optional[str] = None
    # factored coordinates: the reference's array of (random effect,
    # latent matrix) trackers, one dict an inner iteration
    # (``game.factored.FactoredUpdateSummary.history_decode``): "lanes"
    # (count, solver_iterations, convergence_histogram over every lane of
    # every bucket) and "projection" (the shared-B solve's iterations,
    # cg_iterations, passes over the design, reason, grad_norm).
    # ``solver_iterations`` / ``convergence_histogram`` above are the LAST
    # inner iteration's lanes. INDEX_MAP random effects over a sparse
    # shard: one dict, "lanes" and "sparse_re" (each bucket's passes over
    # its compact rows: ``game.projected.IndexMapUpdateSummary``).
    inner_iterations: Optional[List[dict]] = None


def _coordinate_reg_term(coord, params) -> jax.Array:
    """Penalty dispatch shared by the fused and unfused paths: the
    coordinate's own reg_term when it defines one (factored coordinates
    penalize gamma and B under different configs), else the config
    applied to the params."""
    if hasattr(coord, "reg_term"):
        return coord.reg_term(params)
    return _config_reg_term(coord.config, params)


def _config_reg_term(cfg, params) -> jax.Array:
    """loss-side penalty of one coordinate's params under its config —
    matches exactly what the coordinate's solver minimizes."""
    l2 = cfg.reg_weight * (1.0 - cfg.l1_ratio)
    l1 = cfg.reg_weight * cfg.l1_ratio
    leaves = jax.tree_util.tree_leaves(params)
    sq = sum(jnp.vdot(p, p) for p in leaves)
    ab = sum(jnp.sum(jnp.abs(p)) for p in leaves)
    return 0.5 * l2 * sq + l1 * ab


def _history_record(
    iteration,
    coordinate,
    objective,
    reasons,
    iterations,
    seconds,
    validation_metric=None,
    event=None,
    inner_iterations=None,
) -> CoordinateUpdateRecord:
    """THE record builder both the sequential drain and the grid sweep
    use — one place for the reason histogram / solver-iteration
    aggregation semantics."""
    reasons = np.atleast_1d(np.asarray(reasons))
    iters_arr = np.asarray(iterations)
    return CoordinateUpdateRecord(
        iteration=iteration,
        coordinate=coordinate,
        objective=float(objective),
        seconds=seconds,
        validation_metric=validation_metric,
        event=event,
        inner_iterations=inner_iterations,
        solver_iterations=(
            float(np.mean(iters_arr)) if iters_arr.size else 0.0
        ),
        convergence_histogram=reason_histogram(reasons),
    )


def _record_update_metrics(rec: CoordinateUpdateRecord) -> None:
    """Feed one materialized update record into the process metrics
    registry (docs/OBSERVABILITY.md taxonomy). Called at materialize()
    time — after the batched device->host drain — so the hot loop's
    deferred-stats pipelining is untouched."""
    reg = obs.registry()
    reg.inc("game.updates")
    reg.inc("game.solver_iterations", rec.solver_iterations)
    reg.set_gauge("game.objective", rec.objective)
    if rec.validation_metric is not None:
        reg.set_gauge("game.validation_metric", rec.validation_metric)
    if rec.seconds is not None:
        reg.observe("game.update_ms", rec.seconds * 1e3)
    if rec.event == "recovered":
        reg.inc("resilience.rollbacks")
    elif rec.event == "frozen":
        reg.inc("resilience.frozen_coordinates")
    inner = rec.inner_iterations or []
    passes = [sum(i["sparse_re"]["passes"]) for i in inner
              if "sparse_re" in i]
    if passes:
        reg.inc("game.sparse_re.updates")
        reg.inc("game.sparse_re.passes", sum(passes))
    solves = [i["projection"] for i in inner if "projection" in i]
    if solves:
        reg.inc("game.factored.updates")
        reg.inc("game.factored.inner_iterations", len(solves))
        reg.inc(
            "game.factored.projection_passes",
            sum(s["passes"] for s in solves),
        )
        reg.inc(
            "game.factored.projection_rows",
            sum(s["passes"] * s["rows"] for s in solves),
        )
        reg.inc(
            "game.factored.projection_cg_iterations",
            sum(s["cg_iterations"] for s in solves),
        )


def _normalize_fuse_passes(fp):
    """True | False | 'coordinate', strictly. Bool-likes (np.bool_, 0/1)
    normalize to bool; anything else raises — an unrecognized value
    would otherwise silently select the slow plain loop. Applied at
    construction AND at run() (the attribute is assignable)."""
    if isinstance(fp, str):
        if fp != "coordinate":
            raise ValueError(
                f"fuse_passes must be True, False, or 'coordinate'; got "
                f"{fp!r}"
            )
        return fp
    if fp is True or fp is False:
        return fp
    if isinstance(fp, (int, np.bool_)) and fp in (0, 1):
        return bool(fp)
    raise ValueError(
        f"fuse_passes must be True, False, or 'coordinate'; got {fp!r}"
    )


def _pass_body(live, names, loss_fn, labels, base_offsets, weights,
               params, scores, key):
    """ONE full coordinate-descent pass, trace-safe: every coordinate's
    update_step + rescore + post-update training objective, in sequence.
    THE pass definition shared by the single-pass fused program
    (:meth:`CoordinateDescent._fused_pass_fn`) and the multi-pass
    superpass program (:meth:`CoordinateDescent._superpass_fn`), so the
    two dispatch granularities cannot drift numerically — including the
    PRNG stream (one split per coordinate, in update order)."""

    def reg_term(name, p):
        return _coordinate_reg_term(live[name], p)

    objs = []
    trackers = []
    for name in names:
        total = sum(scores.values())
        partial = total - scores[name]
        key, sub = jax.random.split(key)
        # the coordinate's name scopes its device time in a trace
        with jax.named_scope(name):
            p, tr, s = live[name].update_step(params[name], partial, sub)
        params = {**params, name: p}
        scores = {**scores, name: s}
        reg = sum(reg_term(n, params[n]) for n in names)
        tot = sum(scores[n] for n in names)
        objs.append(loss_fn(labels, base_offsets + tot, weights) + reg)
        trackers.append(tr)
    return params, scores, key, tuple(objs), tuple(trackers)


def _tree_finite(tree) -> jax.Array:
    """Scalar bool: every inexact leaf of ``tree`` is finite — the
    divergence-guard *detection* predicate evaluated in-program (the
    non-finite-UPDATE half; the non-finite-OBJECTIVE half is a direct
    isfinite on the pass objectives)."""
    fin = jnp.bool_(True)
    for leaf in jax.tree_util.tree_leaves(tree):
        if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.inexact):
            fin = fin & jnp.all(jnp.isfinite(leaf))
    return fin


def _loss_fn_for_task(task: TaskType):
    if task == TaskType.LOGISTIC_REGRESSION:
        return metrics_mod.total_logistic_loss
    if task == TaskType.LINEAR_REGRESSION:
        return metrics_mod.total_squared_loss
    if task == TaskType.POISSON_REGRESSION:
        return metrics_mod.total_poisson_loss
    raise ValueError(f"no GAME training evaluator for {task}")


def _tree_bytes(tree) -> int:
    """Bytes of a tree's array leaves (the ``bytes`` of a ``game.fetch``)."""
    return sum(
        int(getattr(leaf, "nbytes", 0))
        for leaf in jax.tree_util.tree_leaves(tree)
    )


# identifies one CoordinateDescent.run in its spans (the `job` attribute)
_RUN_IDS = itertools.count(1)


def _spanned_run(run):
    """``run`` under its root span ``game.cd.run``. Inside it every call
    into a compiled pass is a ``game.dispatch`` span (until the call
    returns: enqueue, not completion), every blocking device-to-host read
    a ``game.fetch`` (until the value is on the host) and the host-side
    tape decode a ``game.decode``; what none of them covers is the root's
    self time. No span synchronises."""

    @functools.wraps(run)
    def spanned(self, num_iterations, *args, **kwargs):
        with obs.span(
            "game.cd.run", cat="game", job=next(_RUN_IDS),
            iterations=int(num_iterations),
        ):
            return run(self, num_iterations, *args, **kwargs)

    return spanned


class _AsyncCheckpointWriter:
    """One-deep background checkpoint writer: the training loop hands a
    fully host-snapshotted write closure to :meth:`submit` and keeps
    dispatching device work while serialization + the atomic swap hit
    disk (epoch time bounded by device math, not checkpoint I/O —
    docs/INGEST.md's overlap principle applied to the output side).
    ``submit`` joins any previous write first, so writes serialize in
    step order and at most one is in flight.

    Failure contract: a background-write error SURFACES at the next
    ``submit``/``join`` — at the latest before ``run()`` returns — and
    ``join`` then falls back to re-running the retained write closure
    SYNCHRONOUSLY (``resilience.ckpt_async_fallback`` event + counter),
    so an async-path-only failure (the ``checkpoint.async_write`` chaos
    site: a dying writer thread, an fd lost to the background context)
    costs overlap, never durability. Only a fallback that ALSO fails
    raises — at that point the step genuinely cannot be written. An
    exception that unwinds ``run()`` between a submit and its join can
    at worst lose that one overlapped write, which resume tolerates by
    falling back to the previous VALID step
    (``io.checkpoint.latest_checkpoint``)."""

    def __init__(self):
        import threading

        self._threading = threading
        self._thread = None
        self._fn = None
        self._exc: Optional[BaseException] = None

    def submit(self, write_fn) -> None:
        self.join()
        self._fn = write_fn

        def run():
            try:
                # chaos seam: the background serialize/swap. Distinct
                # from checkpoint.save (probed inside save_checkpoint,
                # where the retry policy owns it): this site dies on the
                # WRITER THREAD, exercising the surface-at-join +
                # synchronous-fallback path.
                _faults.fire("checkpoint.async_write")
                write_fn()
            except BaseException as e:  # noqa: BLE001 — re-raised at join
                self._exc = e

        t = self._threading.Thread(
            target=run, name="game-ckpt-writer", daemon=True
        )
        self._thread = t
        t.start()

    def join(self) -> None:
        t = self._thread
        if t is not None:
            t.join()
            self._thread = None
        if self._exc is not None:
            exc = self._exc
            fn = self._fn
            self._exc = None
            obs.registry().inc("resilience.ckpt_async_fallbacks")
            obs.emit_event(
                "resilience.ckpt_async_fallback",
                cat="resilience",
                error=repr(exc),
            )
            # durability boundary: whoever called join() is standing on a
            # point that PROMISED a checkpoint (next submit, preemption
            # marker, run return). Re-run the failed write synchronously;
            # only a double failure breaks the promise.
            fn()


class CoordinateDescent:
    """Owns the coordinates and the outer loop.

    coordinates: ordered mapping name -> coordinate (the reference's
    updating sequence, ``cli/game/training/Params.scala``). All coordinates
    must see the same rows in the same order (shared labels/offsets/weights).
    """

    def __init__(
        self,
        coordinates: Mapping[str, object],
        labels: jax.Array,
        base_offsets: jax.Array,
        weights: jax.Array,
        task: TaskType,
        fuse_passes=True,  # True | False | "coordinate"
    ):
        """``fuse_passes`` — dispatch granularity, identical math in all
        modes:

        - ``True`` (default): each full CD pass is ONE dispatch
          (:meth:`_fused_pass_fn`).
        - ``"coordinate"``: one dispatch PER COORDINATE UPDATE, with the
          rescore and training objective fused into it (K dispatches per
          pass for K coordinates). The chunked middle ground for shapes
          where the whole-pass program is too large to compile in
          reasonable time (the 1.2M-row flagship shape in r4) but
          per-coordinate programs compile fine.
        - ``False``: plain loop (~3 dispatches per update: update+rescore,
          objective, eager score arithmetic)."""
        self.coordinates = dict(coordinates)
        self.labels = labels
        self.base_offsets = base_offsets
        self.weights = weights
        self.task = task
        self.fuse_passes = _normalize_fuse_passes(fuse_passes)
        loss_fn = _loss_fn_for_task(task)
        names = list(self.coordinates)

        # training objective from per-coordinate scores + params in ONE
        # dispatch: the reg-term composition would otherwise issue several
        # eager ops per coordinate per update — pure launch latency.
        # labels/offsets/weights ride as jit ARGUMENTS: closed-over
        # concrete arrays lower to HLO literals and bloat the compiled
        # module (see _fused_pass_fn)
        coords_ref = self.coordinates

        @jax.jit
        def full_objective(labels_, base_offsets_, weights_, scores_dict,
                           params_dict):
            reg = sum(
                _coordinate_reg_term(coords_ref[n], params_dict[n])
                for n in names
            )
            total = sum(scores_dict[n] for n in names)
            return loss_fn(labels_, base_offsets_ + total, weights_) + reg

        self._full_objective = lambda scores_dict, params_dict: (
            full_objective(
                self.labels, self.base_offsets, self.weights,
                scores_dict, params_dict,
            )
        )

    def _fused_pass_fn(self):
        """ONE jitted dispatch for a FULL coordinate-descent pass: every
        coordinate's update_step + rescore + per-update training objective,
        unrolled in sequence inside a single XLA program. Each dispatch
        costs a launch (and a fetch when its value is read), so the
        unfused loop (2 updates + 2 objectives + score arithmetic) pays
        ~6 latencies per pass; this pays ONE. Used by run() whenever no
        validation_fn is supplied and every coordinate exposes the
        trace-safe update_step (all in-tree coordinates do).

        The pass must NOT close over the coordinates' device-resident
        design/batch arrays: concrete closed-over arrays are not tracers,
        so tracing inlines them as HLO LITERALS and the compiled
        program carries the whole dataset (observed: multi-hundred-MB
        modules, and jax.closure_convert does NOT help — it only hoists
        captured tracers). Instead every coordinate exposes its arrays as an
        explicit ``fused_state()`` pytree, threaded through the jit as
        arguments; the per-update objective is likewise computed from
        argument-passed labels/offsets/weights."""
        names = list(self.coordinates)
        if getattr(self, "_fused_pass", None) is None:
            coords = self.coordinates
            loss_fn = _loss_fn_for_task(self.task)

            def one_pass(states, labels, base_offsets, weights, params,
                         scores, key):
                live = {
                    n: coords[n].with_fused_state(states[n]) for n in names
                }
                return _pass_body(
                    live, names, loss_fn, labels, base_offsets, weights,
                    params, scores, key,
                )

            self._fused_pass = jax.jit(one_pass)
        f = self._fused_pass
        # states are re-snapshotted on EVERY call: a caller that mutates a
        # coordinate between run() calls (reg_weights, design) must train
        # on the fresh state, same as the unfused loop would. fused_state()
        # returns already-resident device arrays, so the rebuild is a dict
        # construction; the jit cache still hits on identical shapes.
        states = {
            n: self.coordinates[n].fused_state() for n in names
        }

        def call(p, s, k):
            return f(
                states, self.labels, self.base_offsets, self.weights, p, s,
                k,
            )

        return call

    def _superpass_fn(self, k: int):
        """ONE jitted dispatch running up to ``k`` FULL coordinate-descent
        passes — the multi-pass extension of :meth:`_fused_pass_fn` (same
        ``_pass_body``, same PRNG stream, same state-threading contract).
        A bounded ``lax.while_loop`` carries (params, scores, key) across
        passes entirely in HBM and early-exits ON DEVICE when either

        - the objective-tolerance convergence check fires: the last
          coordinate's post-update objective moved <= tol * |objective at
          dispatch entry| relative to the previous pass (tol is a traced
          scalar; 0 disables), or
        - the divergence-guard DETECTION predicate fires: a non-finite
          pass objective or non-finite updated parameters. The failing
          pass is NOT committed — the returned (params, scores, key) are
          the last good state, so the host can replay exactly that pass
          through the per-update guarded loop (rollback / damped retry /
          freeze stay host-side policy, at dispatch granularity).

        Per-pass objectives and coordinate trackers write into fixed-size
        (k, ...) tape buffers (the PR-7 carry-tape idiom) whose entries
        past ``passes_done`` are garbage the host masks. Returns
        ``(params, scores, key, passes_done, guard, converged, objs_tape,
        tracker_tapes)``."""
        cache = getattr(self, "_superpass_progs", None)
        if cache is None:
            cache = self._superpass_progs = {}
        if k not in cache:
            names = list(self.coordinates)
            coords = self.coordinates
            loss_fn = _loss_fn_for_task(self.task)

            def superpass(states, labels, base_offsets, weights, params,
                          scores, key, tol, guard_on):
                live = {
                    n: coords[n].with_fused_state(states[n]) for n in names
                }

                def one_pass(params, scores, key):
                    return _pass_body(
                        live, names, loss_fn, labels, base_offsets,
                        weights, params, scores, key,
                    )

                # objective at dispatch entry: the convergence reference
                # scale, and the "previous objective" for the chunk's
                # first pass (cross-dispatch continuity: this equals the
                # previous chunk's final objective by construction)
                reg0 = sum(
                    _coordinate_reg_term(live[n], params[n]) for n in names
                )
                tot0 = sum(scores[n] for n in names)
                obj_in = loss_fn(labels, base_offsets + tot0, weights) + reg0

                out_sh = jax.eval_shape(one_pass, params, scores, key)
                obj_dtype = out_sh[3][0].dtype
                objs_tape0 = jnp.full((k, len(names)), jnp.nan, obj_dtype)
                tr_tapes0 = jax.tree_util.tree_map(
                    lambda s: jnp.zeros((k,) + s.shape, s.dtype), out_sh[4]
                )
                obj_in = obj_in.astype(obj_dtype)
                tol_c = jnp.asarray(tol, obj_dtype)

                def cond(carry):
                    i, stop = carry[0], carry[1]
                    return (i < k) & ~stop

                def body(carry):
                    (i, _stop, guard, conv, params, scores, key,
                     objs_tape, tr_tapes) = carry
                    p2, s2, k2, objs, trs = one_pass(params, scores, key)
                    objs_vec = jnp.stack(
                        [o.astype(obj_dtype) for o in objs]
                    )
                    finite = jnp.all(jnp.isfinite(objs_vec))
                    for n in names:
                        finite = finite & _tree_finite(p2[n])
                    # without the guard, non-finite passes COMMIT — the
                    # unguarded fused loop's semantics (one NaN poisons
                    # the run), and the host's passes_done == chunk
                    # assumption (it never reads the flags) stays true
                    commit = finite | ~guard_on
                    tripped = guard_on & ~finite
                    prev = jnp.where(
                        i == 0,
                        obj_in,
                        objs_tape[jnp.maximum(i - 1, 0), -1],
                    )
                    cur = objs_vec[-1]
                    converged = (
                        commit
                        & (tol_c > 0)
                        & (jnp.abs(prev - cur) <= tol_c * jnp.abs(obj_in))
                    )
                    objs_tape = objs_tape.at[i].set(
                        jnp.where(commit, objs_vec, objs_tape[i])
                    )
                    tr_tapes = jax.tree_util.tree_map(
                        lambda buf, v: buf.at[i].set(
                            jnp.where(commit, v, buf[i])
                        ),
                        tr_tapes,
                        trs,
                    )
                    sel = lambda a, b: jnp.where(commit, a, b)
                    params = jax.tree_util.tree_map(sel, p2, params)
                    scores = jax.tree_util.tree_map(sel, s2, scores)
                    key = jnp.where(commit, k2, key)
                    return (
                        i + commit.astype(i.dtype),
                        tripped | converged,
                        guard | tripped,
                        conv | converged,
                        params,
                        scores,
                        key,
                        objs_tape,
                        tr_tapes,
                    )

                init = (
                    jnp.int32(0), jnp.bool_(False), jnp.bool_(False),
                    jnp.bool_(False), params, scores, key, objs_tape0,
                    tr_tapes0,
                )
                (i, _stop, guard, conv, params, scores, key, objs_tape,
                 tr_tapes) = lax.while_loop(cond, body, init)
                return (
                    params, scores, key, i, guard, conv, objs_tape,
                    tr_tapes,
                )

            cache[k] = jax.jit(superpass)
        states = {
            n: self.coordinates[n].fused_state()
            for n in self.coordinates
        }

        def call(p, s, key, tol, guard_on):
            return cache[k](
                states, self.labels, self.base_offsets, self.weights,
                p, s, key, tol, guard_on,
            )

        return call, states

    def _coordinate_step_fns(self):
        """One jitted dispatch PER COORDINATE: update_step + rescore +
        the post-update training objective fused together — the chunked
        fallback for shapes where the whole-pass program is too large
        to compile in reasonable time. Shares the fused path's
        state-threading contract (coordinates' device arrays ride as jit
        ARGUMENTS, never as closed-over literals; see
        :meth:`_fused_pass_fn`); states are re-snapshotted per call like
        the fused path so coordinate mutations between runs are seen."""
        names = list(self.coordinates)
        if getattr(self, "_chunk_fns", None) is None:
            coords = self.coordinates
            loss_fn = _loss_fn_for_task(self.task)

            def make(name):
                def one_step(states, labels, base_offsets, weights,
                             params, scores, key):
                    live = {
                        n: coords[n].with_fused_state(states[n])
                        for n in names
                    }
                    total = sum(scores.values())
                    partial = total - scores[name]
                    with jax.named_scope(name):
                        p, tr, s = live[name].update_step(
                            params[name], partial, key
                        )
                    params = {**params, name: p}
                    scores = {**scores, name: s}
                    reg = sum(
                        _coordinate_reg_term(live[n], params[n])
                        for n in names
                    )
                    tot = sum(scores[n] for n in names)
                    obj = (
                        loss_fn(labels, base_offsets + tot, weights) + reg
                    )
                    return p, tr, s, obj

                return jax.jit(one_step)

            self._chunk_fns = {name: make(name) for name in names}
        states = {n: self.coordinates[n].fused_state() for n in names}
        return self._chunk_fns, states


    @_spanned_run
    def run(
        self,
        num_iterations: int,
        initial_model: Optional[GameModel] = None,
        seed: int = 0,
        validation_fn=None,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 1,
        resume: bool = True,
        divergence_guard: bool = False,
        stop_check=None,
        passes_per_dispatch: int = 1,
        convergence_tolerance: float = 0.0,
        sharded_checkpoints=False,
        entity_keys=None,
        heartbeat=None,
        freeze=None,
    ):
        """Returns (model, history). Objective is logged after every
        coordinate update like ``CoordinateDescent.scala:160-170``;
        `validation_fn(model) -> float`, when given, is evaluated after
        every coordinate update too (``CoordinateDescent.scala:173-189``).

        ``passes_per_dispatch`` (K): with ``fuse_passes=True`` and K > 1,
        up to K coordinate-descent passes run per XLA dispatch through
        the device-resident superpass program (:meth:`_superpass_fn`) —
        a run of P passes costs ceil(P/K) dispatches instead of P. K is
        capped by the checkpoint cadence (``checkpoint_every`` still
        fires on schedule; the dispatch chunk shrinks to hit each
        boundary), and checkpoint / preemption / validation semantics
        hold at DISPATCH boundaries — K is the checkpoint granularity.
        Identical math to K = 1 (asserted in tests/test_device_loops.py).

        ``convergence_tolerance``: with K > 1, an objective-tolerance
        convergence check runs IN-PROGRAM after every pass — when the
        full training objective moves less than tol * |objective at
        dispatch entry| between consecutive passes, the superpass
        early-exits on device and the run returns with the passes
        actually executed. 0 (default) disables the check.

        With ``divergence_guard`` AND K > 1, the guard's *detection*
        predicate (non-finite pass objective or updated params) also
        runs in-program: a failing pass is not committed, the superpass
        returns the last good state plus a guard flag, and the host
        replays exactly that pass through the per-update guarded loop —
        so rollback / damped-retry / freeze semantics are bit-identical
        to a fully host-guarded run while healthy passes stay fused.

        With ``checkpoint_dir``, the full training state (parameter tables,
        PRNG key, iteration counter, history) is written atomically every
        ``checkpoint_every`` outer iterations, and — when ``resume`` — a
        run restarted over the same directory continues from the latest
        completed pass with an identical PRNG stream, reproducing the
        uninterrupted run exactly (SURVEY §5.4; the reference has no
        analog, it leans on Spark lineage).

        ``divergence_guard`` (docs/ROBUSTNESS.md): after each coordinate
        update, check the training objective for non-finites; on failure
        roll the coordinate back to its pre-update state and retry once
        against a DAMPED residual (half the partial score), and if the
        retry also fails, FREEZE the coordinate — its params stay at the
        last finite state and it is skipped for the rest of the run (and
        of any resumed run: the frozen set rides in the checkpoint) while
        the remaining coordinates keep training. Guarded runs use the
        per-update dispatch loop (the check needs the objective on the
        host after every update), so the fused whole-pass dispatch is
        bypassed — enable it for resilience, not throughput.

        ``stop_check`` (preemption): a zero-arg callable polled at PASS
        boundaries (e.g. :class:`photon_ml_tpu.resilience.GracefulShutdown`
        wired to SIGTERM). When it turns true the loop writes a final
        checkpoint plus a ``preempted.json`` marker (with checkpoint_dir)
        and returns early; restarting with ``resume=True`` continues
        bit-for-bit, reproducing the uninterrupted run.

        ``sharded_checkpoints`` (docs/MULTIHOST.md): True writes
        per-process checkpoint shards (``io.checkpoint.
        save_checkpoint_sharded`` — each process writes only its shard,
        process 0 publishes the quorum manifest); an int N writes N
        shards from a single process (the emulation / shrunk-restart
        mode). ``entity_keys`` (coordinate -> global ordered entity-id
        list) labels entity-table rows so those tables shard by row and
        a restore onto a DIFFERENT process count or entity order
        re-shards BY KEY (``reindex_entity_params``) instead of by
        position. Resume accepts both formats interchangeably.

        ``heartbeat`` (:class:`photon_ml_tpu.parallel.heartbeat.
        HeartbeatMonitor`): polled at pass boundaries. On a detected
        peer loss, the loop writes a FINAL checkpoint at the current
        boundary plus a ``host-loss.json`` marker and re-raises
        :class:`~photon_ml_tpu.resilience.hostloss.HostLossDetected` —
        the drivers map it to the distinct host-loss exit code so a
        restart (same or smaller world size) resumes from the shard
        set.

        ``freeze``: coordinate names to EXCLUDE from updates for the
        whole run — they keep their (warm-started) params and still
        contribute their score. This seeds the same frozen set the
        divergence guard grows, so it rides checkpoints identically (a
        resumed run unions the checkpoint's casualties with the seed)
        and forces the per-update loop the same way a guard-frozen
        coordinate does. The lifecycle orchestrator uses it to retrain
        only convergence-unhealthy coordinates while healthy ones carry
        over bit-identical from the previous export."""
        names = list(self.coordinates)
        seed_frozen = set(freeze or ())
        unknown_frozen = seed_frozen - set(names)
        if unknown_frozen:
            raise ValueError(
                f"freeze names unknown coordinates: "
                f"{sorted(unknown_frozen)}"
            )
        if seed_frozen >= set(names):
            raise ValueError("freeze covers every coordinate — nothing "
                             "would train")
        model = (
            initial_model.copy()
            if initial_model is not None
            else GameModel(
                {n: self.coordinates[n].initial_params() for n in names}
            )
        )
        history: List[CoordinateUpdateRecord] = []
        key = jax.random.PRNGKey(seed)
        # Data over a mesh (an entity-sharded or batch-sharded descent, or
        # multi-controller SPMD): the locally created state, the PRNG key
        # and zero-initialized parameters, is a single-device array, while
        # every pass gives both back REPLICATED over the data's mesh. Left
        # so, the second pass sees other input types than the first and
        # the whole fused pass is traced, lowered and compiled (or loaded)
        # TWICE; multi-process, jit rejects a process-local input
        # outright. Re-place it replicated over the data mesh.
        mesh = getattr(getattr(self.labels, "sharding", None), "mesh", None)
        if mesh is not None and mesh.size > 1:
            from jax.sharding import NamedSharding, PartitionSpec

            rep = NamedSharding(mesh, PartitionSpec())
            multi_process = jax.process_count() > 1

            def _on_the_mesh(x):
                if isinstance(x, jax.Array) and (
                    not x.is_fully_addressable
                    if multi_process
                    else len(x.sharding.device_set) > 1
                ):
                    return x  # already placed over the mesh
                return jax.device_put(
                    np.asarray(x) if multi_process else x, rep
                )

            model = GameModel(
                {
                    n: jax.tree_util.tree_map(_on_the_mesh, p)
                    for n, p in model.params.items()
                }
            )
            key = _on_the_mesh(key)
        start_it = 0
        # divergence-guard casualties + caller-frozen coordinates (both
        # skip updates; both ride checkpoints)
        frozen: set = set(seed_frozen)
        if checkpoint_dir is not None and resume:
            from photon_ml_tpu.io.checkpoint import latest_checkpoint

            ckpt = latest_checkpoint(checkpoint_dir)
            if ckpt is not None:
                missing = set(names) - set(ckpt.params)
                if missing:
                    raise ValueError(
                        f"checkpoint lacks coordinates {sorted(missing)}"
                    )
                if ckpt.step > num_iterations:
                    raise ValueError(
                        f"checkpoint at step {ckpt.step} exceeds "
                        f"num_iterations={num_iterations}; refusing to "
                        "return a longer run's state as if it were shorter"
                    )
                restored = ckpt.params
                if ckpt.entity_keys and entity_keys:
                    # restore-with-resharding: entity tables re-key onto
                    # THIS run's entity order (identical orders pass
                    # through untouched — bit-for-bit resume)
                    from photon_ml_tpu.io.checkpoint import (
                        reindex_entity_params,
                    )

                    restored = reindex_entity_params(
                        ckpt,
                        {n: list(k) for n, k in entity_keys.items()},
                    )
                model = GameModel(
                    {
                        n: jax.tree_util.tree_map(
                            jnp.asarray, restored[n]
                        )
                        for n in names
                    }
                )
                key = jnp.asarray(ckpt.rng_key, jnp.uint32)
                start_it = ckpt.step
                history = [
                    CoordinateUpdateRecord(**h) for h in ckpt.history
                ]
                frozen = (set(ckpt.frozen) & set(names)) | seed_frozen

        with obs.span("game.dispatch", cat="game", kind="score",
                      coordinates=len(names)):
            scores = {
                n: self.coordinates[n].score(model.params[n])
                for n in names
            }

        # Per-update device stats stay ON DEVICE during the loop (objective
        # scalar, per-entity solver trackers) so consecutive updates
        # pipeline without a host sync each pass — the deferred analog of
        # the reference's post-hoc tracker collects. They materialize into
        # `history` lazily (before checkpoints and at return). With a
        # validation_fn the defer is moot: it returns a host float.
        pending: List[dict] = []

        def materialize():
            if not pending:
                return
            from photon_ml_tpu.obs import convergence as _conv

            # convergence decode is OPT-IN via the installed
            # --convergence-report tracker, not via plain tracing: the
            # per-update fleet decode (numpy aggregation + one
            # structured event per coordinate per pass) measurably eats
            # into the <5% tracing budget on smoke shapes, so it rides
            # the gate's dedicated tapes-on leg instead
            # (benchmarks/obs_overhead.py). Events still land in
            # events.jsonl when a tracer is ALSO active.
            conv_enabled = _conv.tracking_enabled()
            # ONE batched device->host transfer for the whole backlog:
            # individually materialized values cost a device->host fetch
            # EACH, and every pass logs an objective scalar plus
            # per-entity tracker arrays per coordinate — fetched one by
            # one, the stats drain was the dominant wall of the
            # cluster-scale GAME benches (r5).
            fetch = []
            for p in pending:
                r = p["result"]
                raw = getattr(r, "pending", None)
                if hasattr(r, "history_fetch"):
                    # a summary that brings its own tracker tree (the
                    # factored coordinate's) and decodes it below
                    fetch.append((p["objective"], r.history_fetch()))
                elif raw is not None:
                    # lazy RandomEffectUpdateSummary: per-bucket device
                    # (reason, iterations, final grad norm); valid-lane
                    # masks and entity indices are host-side
                    fetch.append(
                        (
                            p["objective"],
                            tuple(
                                (re_, it_, gn_)
                                for re_, it_, gn_, _, _ in raw
                            ),
                        )
                    )
                else:
                    # grad_norms tape rides the drain whole (tiny); the
                    # final-norm gather happens host-side below so the
                    # track_states=True case stays correct
                    fetch.append(
                        (
                            p["objective"],
                            (r.reason, r.iterations, r.grad_norms),
                        )
                    )
            if jax.process_count() > 1:
                # global arrays with non-addressable shards (entity-lane
                # sharded trackers) reshard to replicated ON DEVICE so
                # the single batched device_get below still carries
                # everything in one transfer
                from photon_ml_tpu.parallel.multihost import (
                    reshard_replicated,
                )

                fetch = jax.tree_util.tree_map(reshard_replicated, fetch)
            with obs.span("game.fetch", cat="game", what="history",
                          bytes=_tree_bytes(fetch)):
                host = jax.device_get(fetch)
            with obs.span("game.decode", cat="game", updates=len(pending)):
                _decode(host, conv_enabled, _conv)
            pending.clear()

        def _decode(host, conv_enabled, _conv):
            """Tape decode and history records on the host, for the
            backlog ``materialize`` has just fetched."""
            for p, (obj, tr) in zip(pending, host):
                result = p.pop("result")
                raw = getattr(result, "pending", None)
                inner_iterations = None
                if hasattr(result, "history_decode"):
                    (
                        reason, iterations, grad_norms, entity_ids,
                        inner_iterations,
                    ) = result.history_decode(tr)
                elif raw is not None:
                    valid = [v for _, _, _, v, _ in raw]
                    reason = np.concatenate(
                        [
                            np.asarray(re_)[v]
                            for (re_, _, _), v in zip(tr, valid)
                        ]
                    )
                    iterations = np.concatenate(
                        [
                            np.asarray(it_)[v]
                            for (_, it_, _), v in zip(tr, valid)
                        ]
                    )
                    grad_norms = np.concatenate(
                        [
                            np.asarray(gn_)[v]
                            for (_, _, gn_), v in zip(tr, valid)
                        ]
                    )
                    entity_ids = np.concatenate(
                        [
                            np.asarray(ei)[v]
                            for (_, _, _, v, ei) in raw
                        ]
                    )
                else:
                    reason, iterations, gn_tape = tr
                    gn_arr = np.asarray(gn_tape)
                    it_arr = np.asarray(iterations)
                    idx = np.minimum(it_arr, gn_arr.shape[-1] - 1)
                    grad_norms = np.take_along_axis(
                        gn_arr, idx[..., None], axis=-1
                    )[..., 0]
                    entity_ids = None
                rec = _history_record(
                    p["iteration"],
                    p["coordinate"],
                    obj,
                    reason,
                    iterations,
                    p["seconds"],
                    p["validation_metric"],
                    p.get("event"),
                    inner_iterations,
                )
                history.append(rec)
                _record_update_metrics(rec)
                if conv_enabled:
                    # fleet summary per coordinate per pass: iterations
                    # histogram, non-converged count/fraction, worst-k
                    # entities by final grad norm -> convergence.*
                    # metrics + convergence.fleet events (which also
                    # ride the tracer hook into the flight recorder)
                    _conv.note_update(
                        coordinate=p["coordinate"],
                        iteration=p["iteration"],
                        reasons=reason,
                        iterations=iterations,
                        grad_norms=grad_norms,
                        entity_ids=entity_ids,
                    )

        # the fused path needs the FULL trace-safe surface, not just
        # update_step — a custom coordinate providing only update/score
        # must keep working through the plain loop
        _fused_surface = (
            "update_step", "fused_state", "with_fused_state", "wrap_tracker"
        )
        has_surface = all(
            all(hasattr(c, m) for m in _fused_surface)
            for c in self.coordinates.values()
        )
        mode = _normalize_fuse_passes(self.fuse_passes)
        # the guard needs every update's objective ON THE HOST before the
        # next update commits — incompatible with the fused whole-pass
        # dispatch (and with deferring the check), so guarded runs take
        # the plain per-update loop
        use_fused = (
            mode is True and validation_fn is None and has_surface
            and not divergence_guard
            # a resumed frozen set can't be excluded inside the one-dispatch
            # pass program; take a per-update loop that can skip
            and not frozen
        )
        use_chunked = (
            mode == "coordinate" and has_surface and not divergence_guard
        )
        # multi-pass superpass (K passes per dispatch): the fused-mode
        # surface requirements, but the guard is ALLOWED — its detection
        # predicate runs in-program and failing passes replay through
        # the per-update loop (force_plain below). A grown/resumed
        # frozen set still can't be excluded inside the program, so the
        # branch re-checks `frozen` every iteration.
        k_dispatch = max(1, int(passes_per_dispatch))
        use_super = (
            k_dispatch > 1
            and mode is True
            and validation_fn is None
            and has_surface
        )
        # Checkpoint writes OVERLAP the next dispatch chunk's device
        # math: the training state is snapshotted to host synchronously
        # (the write must capture THIS boundary, not whatever the next
        # pass mutates), then serialization + atomic swap run on a
        # background writer. At most one write is in flight; the
        # preemption paths and the run's return join() first, so every
        # durability guarantee those boundaries had under synchronous
        # writes still holds — a mid-pass hard crash can at worst lose
        # the overlapped write, which resume already tolerates (it
        # falls back to the previous VALID checkpoint and the
        # deterministic PRNG stream reproduces the run).
        ckpt_writer = _AsyncCheckpointWriter()

        def _ckpt_snapshot():
            # host snapshot: params / key / history copied now (the
            # write must capture THIS boundary, not whatever the next
            # pass mutates)
            with obs.span("game.fetch", cat="game", what="checkpoint",
                          bytes=_tree_bytes(model.params)):
                params_host = {
                    n: jax.tree_util.tree_map(
                        lambda a: np.asarray(a), model.params[n]
                    )
                    for n in names
                }
                key_host = np.asarray(key)
            return (
                params_host,
                key_host,
                [dataclasses.asdict(h) for h in history],
                sorted(frozen),
            )

        def _sharded_num_shards():
            return (
                None
                if sharded_checkpoints is True
                else int(sharded_checkpoints)
            )

        def _save_ckpt_local(step, wait: bool = False):
            """The legacy single-file writer on the overlapped
            background thread. NO collectives — the only cadence writer
            the host-loss handler may reach (photon-lint PL001: the
            sharded writer's digest exchange and swap barrier are
            full-world collectives)."""
            from photon_ml_tpu.io.checkpoint import save_checkpoint

            materialize()
            t0 = time.perf_counter()
            params_host, key_host, hist_host, frozen_host = (
                _ckpt_snapshot()
            )
            ckpt_writer.submit(
                lambda: save_checkpoint(
                    checkpoint_dir,
                    step,
                    # save_checkpoint handles plain tables AND
                    # FactoredParams
                    params_host,
                    key_host,
                    hist_host,
                    frozen=frozen_host,
                )
            )
            if wait:
                ckpt_writer.join()
            obs.registry().observe(
                "game.checkpoint.submit_ms",
                (time.perf_counter() - t0) * 1e3,
            )

        def _save_ckpt_sharded(step):
            """Per-process shard set + quorum manifest. On a pod the
            digest exchange + swap barrier are collective, so the
            write runs SYNCHRONOUSLY on the training thread (every
            process must reach the exchange together; a background
            thread would race the next pass's collectives)."""
            from photon_ml_tpu.io.checkpoint import (
                save_checkpoint_sharded,
            )

            materialize()
            t0 = time.perf_counter()
            params_host, key_host, hist_host, frozen_host = (
                _ckpt_snapshot()
            )
            ekeys_host = (
                {
                    n: [str(k) for k in v]
                    for n, v in entity_keys.items()
                }
                if entity_keys
                else None
            )
            ckpt_writer.join()  # any legacy overlapped write first
            save_checkpoint_sharded(
                checkpoint_dir,
                step,
                params_host,
                key_host,
                history=hist_host,
                frozen=frozen_host,
                entity_keys=ekeys_host,
                num_shards=_sharded_num_shards(),
            )
            obs.registry().observe(
                "game.checkpoint.submit_ms",
                (time.perf_counter() - t0) * 1e3,
            )

        def _save_ckpt(step, wait: bool = False):
            if sharded_checkpoints:
                _save_ckpt_sharded(step)
            else:
                _save_ckpt_local(step, wait)

        def _save_final_shards(step: int) -> None:
            """The pod survivors' final save — collective-free by
            contract: the normal sharded writer exchanges digests and
            barriers over the FULL world, which includes the peer just
            declared dead (it would hang forever without a watchdog, or
            exhaust its retries with one). One elected survivor writes
            the complete quorum step instead
            (``save_checkpoint_sharded_final``). The pending device
            stats are NOT materialized here — their drain may need a
            device collective (reshard of non-addressable trackers) the
            dead peer can no longer complete, and history is replay
            metadata, not math state."""
            from photon_ml_tpu.io.checkpoint import (
                save_checkpoint_sharded_final,
            )

            with obs.span("game.fetch", cat="game", what="checkpoint",
                          bytes=_tree_bytes(model.params)):
                params_host = {
                    n: jax.tree_util.tree_map(
                        lambda a: np.asarray(a), model.params[n]
                    )
                    for n in names
                }
                key_host = np.asarray(key)
            ckpt_writer.join()
            save_checkpoint_sharded_final(
                checkpoint_dir,
                step,
                params_host,
                key_host,
                history=[dataclasses.asdict(h) for h in history],
                frozen=sorted(frozen),
                entity_keys=(
                    {
                        n: [str(k) for k in v]
                        for n, v in entity_keys.items()
                    }
                    if entity_keys
                    else None
                ),
                num_shards=_sharded_num_shards(),
            )

        def _host_loss_boundary(step: int, saved: bool) -> None:
            """Pass-boundary heartbeat poll: on a detected peer loss the
            SURVIVORS' contract runs here — final durable checkpoint at
            this boundary (collective-free on a pod: the dead peer can
            no longer complete an exchange), host-loss marker, then
            surface the exception for the driver's distinct-exit-code
            mapping. The marker is written even when the final save
            FAILS — the restart then resumes from the newest complete
            quorum step instead."""
            if heartbeat is None:
                return
            try:
                heartbeat.check()
            except Exception as e:
                from photon_ml_tpu.resilience.hostloss import (
                    HostLossDetected,
                    write_host_loss_marker,
                )

                if not isinstance(e, HostLossDetected):
                    raise
                if checkpoint_dir is not None:
                    final_ok = True
                    try:
                        if saved:
                            # this boundary's cadence checkpoint already
                            # landed (all peers alive at that point)
                            ckpt_writer.join()
                        elif sharded_checkpoints:
                            # ANY world size: the single-publisher
                            # final writer — collective-free by
                            # construction. The normal sharded writer's
                            # digest exchange + completion barrier
                            # include the peer just declared dead (the
                            # PR-11 hang, photon-lint PL001); routing
                            # single-process emulation through the same
                            # path keeps the recovery writer in tier-1.
                            _save_final_shards(step)
                        else:
                            # legacy format: the overlapped local
                            # writer, no collectives
                            _save_ckpt_local(step, wait=True)
                    except Exception as save_err:  # noqa: BLE001
                        final_ok = False
                        obs.emit_event(
                            "resilience.host_loss_save_failed",
                            cat="resilience",
                            iteration=step,
                            error=repr(save_err),
                        )
                    write_host_loss_marker(
                        checkpoint_dir, step, e.peers, reason=e.reason,
                        final_checkpoint=final_ok,
                    )
                obs.emit_event(
                    "resilience.host_loss",
                    cat="resilience",
                    iteration=step,
                    peers=e.peers,
                )
                raise

        # count XLA backend compiles for the duration of the run: the
        # steady-state zero-recompile invariant of the cached pass/step
        # programs is only provable if something counts actual compiles
        # (obs.compile_events; idempotent global listener)
        obs.install_compile_listener()
        stopped = False
        it = start_it
        # guard replay: when a superpass reports a non-committed pass,
        # run exactly ONE pass through the per-update loop (which owns
        # the rollback/damp/freeze policy), then resume superpassing
        force_plain = False
        while it < num_iterations:
            pass_t0 = time.perf_counter()
            if use_super and not frozen and not force_plain:
                # dispatch chunk: K passes, shrunk to land exactly on
                # the checkpoint cadence and the run end — checkpoint /
                # preemption semantics live at dispatch boundaries
                chunk = min(k_dispatch, num_iterations - it)
                if checkpoint_dir is not None:
                    chunk = min(
                        chunk,
                        checkpoint_every
                        - ((it - start_it) % checkpoint_every),
                    )
                sp_call, sp_states = self._superpass_fn(chunk)
                params_in = {n: model.params[n] for n in names}
                tol_arr = jnp.asarray(float(convergence_tolerance))
                guard_arr = jnp.asarray(bool(divergence_guard))
                t0 = time.perf_counter()
                with obs.span(
                    "game.dispatch", cat="game", kind="superpass",
                    iteration=it, passes=chunk, coordinates=len(names),
                ):
                    (params_out, scores, key, passes_dev, guard_dev,
                     conv_dev, objs_tape, tr_tapes) = sp_call(
                        params_in, scores, key, tol_arr, guard_arr
                    )
                model.params.update(params_out)
                seconds = time.perf_counter() - t0
                if divergence_guard or convergence_tolerance > 0:
                    # the flags decide host control flow, so reading
                    # them synchronizes — ONE sync per K passes. With
                    # neither feature on they are statically known and
                    # the dispatch chain stays fully pipelined.
                    with obs.span("game.fetch", cat="game",
                                  what="superpass_flags"):
                        passes_done = int(passes_dev)
                        guard = bool(guard_dev)
                        converged = bool(conv_dev)
                else:
                    passes_done, guard, converged = chunk, False, False
                for p in range(passes_done):
                    for ci, name in enumerate(names):
                        pending.append(
                            {
                                "iteration": it + p,
                                "coordinate": name,
                                # dispatch wall on the chunk's FIRST
                                # record only — the dispatch is
                                # indivisible (fused-mode contract)
                                "seconds": (
                                    seconds if p == 0 and ci == 0
                                    else None
                                ),
                                "objective": objs_tape[p, ci],
                                "validation_metric": None,
                                "result": self.coordinates[
                                    name
                                ].wrap_tracker(
                                    jax.tree_util.tree_map(
                                        lambda a, _p=p: a[_p],
                                        tr_tapes[ci],
                                    )
                                ),
                            }
                        )
                if obs.get_tracer() is not None:
                    obs.sample_hbm()
                it += passes_done
                _reg = obs.registry()
                _reg.inc("game.dispatches")
                _reg.inc("game.superpasses")
                _reg.inc("game.passes", passes_done)
                if passes_done:
                    _reg.observe(
                        "game.pass_ms", seconds * 1e3 / passes_done
                    )
                if guard:
                    # in-program detection, host-side policy: replay the
                    # non-committed pass through the per-update loop
                    # (same PRNG key — the superpass never advanced it
                    # past the last committed pass, so the replay
                    # reproduces the exact failing updates)
                    force_plain = True
                    obs.emit_event(
                        "resilience.superpass_guard",
                        cat="resilience",
                        iteration=it,
                        passes_done=passes_done,
                    )
                saved = False
                if (
                    passes_done
                    and checkpoint_dir is not None
                    and (it - start_it) % checkpoint_every == 0
                ):
                    _save_ckpt(it)
                    saved = True
                _host_loss_boundary(it, saved)
                if stop_check is not None and stop_check():
                    stopped = True
                    if checkpoint_dir is not None:
                        # the marker promises a durable checkpoint at
                        # this step: drain the overlapped write first
                        if not saved:
                            _save_ckpt(it, wait=True)
                        else:
                            ckpt_writer.join()
                        from photon_ml_tpu.resilience.shutdown import (
                            write_preempted_marker,
                        )

                        write_preempted_marker(
                            checkpoint_dir,
                            it,
                            getattr(stop_check, "signum", None),
                        )
                    break
                if converged:
                    obs.emit_event(
                        "game.converged",
                        cat="game",
                        iteration=it,
                        tolerance=float(convergence_tolerance),
                    )
                    break
                continue
            if use_fused:
                params_in = {n: model.params[n] for n in names}
                fused = self._fused_pass_fn()
                t0 = time.perf_counter()
                # the fused pass is ONE indivisible dispatch: one span
                # says so, and no per-coordinate window is invented
                with obs.span(
                    "game.dispatch", cat="game", kind="fused",
                    iteration=it, passes=1, coordinates=len(names),
                ):
                    params_out, scores, key, objs, trackers = fused(
                        params_in, scores, key
                    )
                model.params.update(params_out)
                seconds = time.perf_counter() - t0
                for i, (name, obj, tr) in enumerate(
                    zip(names, objs, trackers)
                ):
                    pending.append(
                        {
                            "iteration": it,
                            "coordinate": name,
                            "objective": obj,
                            # full fused-pass wall time on the first record
                            # only; the dispatch is indivisible
                            "seconds": seconds if i == 0 else None,
                            "validation_metric": None,
                            "result": self.coordinates[name].wrap_tracker(
                                tr
                            ),
                        }
                    )
            elif use_chunked:
                fns, states = self._coordinate_step_fns()
                for name in names:
                    if name in frozen:
                        continue
                    with obs.span(
                        "game.update", cat="game",
                        coordinate=name, iteration=it,
                    ):
                        key, sub = jax.random.split(key)
                        params_in = {n: model.params[n] for n in names}
                        t0 = time.perf_counter()
                        with obs.span(
                            "game.dispatch", cat="game",
                            kind="coordinate", iteration=it, passes=1,
                            coordinates=1,
                        ):
                            p, tr, s, obj = fns[name](
                                states,
                                self.labels,
                                self.base_offsets,
                                self.weights,
                                params_in,
                                scores,
                                sub,
                            )
                        model.params[name] = p
                        scores = {**scores, name: s}
                        # wall of the (async) dispatch window (the
                        # deferred-stats pipelining must not gain a
                        # block_until_ready here)
                        seconds = time.perf_counter() - t0
                        vmetric = (
                            float(validation_fn(model))
                            if validation_fn is not None
                            else None
                        )
                        pending.append(
                            {
                                "iteration": it,
                                "coordinate": name,
                                "objective": obj,
                                "seconds": seconds,
                                "validation_metric": vmetric,
                                "result": self.coordinates[
                                    name
                                ].wrap_tracker(tr),
                            }
                        )
            else:
                def _dispatch(kind):
                    return obs.span(
                        "game.dispatch", cat="game", kind=kind,
                        iteration=it, passes=1, coordinates=1,
                    )

                def _objective_on_host(cand_scores, cand_params):
                    with _dispatch("objective"):
                        obj_dev = self._full_objective(
                            cand_scores, cand_params
                        )
                    with obs.span("game.fetch", cat="game",
                                  what="objective"):
                        return float(obj_dev)

                for name in names:
                    if name in frozen:
                        continue
                    update_span = obs.span(
                        "game.update", cat="game",
                        coordinate=name, iteration=it,
                    )
                    with update_span:
                        t0 = time.perf_counter()
                        coord = self.coordinates[name]
                        total = sum(scores.values())
                        partial = total - scores[name]

                        def _attempt(prev_p, residual, sub):
                            if hasattr(coord, "update_and_score"):
                                p, r, s = coord.update_and_score(
                                    prev_p, residual, sub
                                )
                            else:
                                p, r = coord.update(prev_p, residual, sub)
                                s = coord.score(p)
                            # fault site: corrupt-mode poisons the accepted
                            # update with non-finites — the drill for the
                            # divergence guard (and, unguarded, for the
                            # one-NaN-poisons-the-run failure mode)
                            if _faults.fire(
                                "descent.update", key=name
                            ).corrupt:
                                p = jax.tree_util.tree_map(
                                    lambda a: jnp.full_like(a, jnp.nan), p
                                )
                                s = jnp.full_like(s, jnp.nan)
                            return p, r, s

                        key, sub = jax.random.split(key)
                        with _dispatch("update"):
                            params, result, new_scores = _attempt(
                                model.params[name], partial, sub
                            )
                        event = None
                        if divergence_guard:
                            cand_scores = {**scores, name: new_scores}
                            cand_params = {**model.params, name: params}
                            obj_host = _objective_on_host(
                                cand_scores, cand_params
                            )
                            if not np.isfinite(obj_host):
                                # rollback to the pre-update state and retry
                                # once against a DAMPED residual (half the
                                # partial score): overshoot-driven overflow
                                # gets a gentler target, injected faults get
                                # a second probe
                                obs.emit_event(
                                    "resilience.rollback",
                                    cat="resilience",
                                    coordinate=name,
                                    iteration=it,
                                )
                                # flight recorder: the spans/metrics
                                # leading INTO the divergence are the
                                # post-mortem; dump them now, before the
                                # damped retry perturbs the state
                                obs.flight_dump("divergence")
                                key, sub = jax.random.split(key)
                                with _dispatch("update"):
                                    params, result, new_scores = _attempt(
                                        model.params[name], partial * 0.5,
                                        sub,
                                    )
                                cand_scores = {**scores, name: new_scores}
                                cand_params = {
                                    **model.params, name: params
                                }
                                obj_host = _objective_on_host(
                                    cand_scores, cand_params
                                )
                                if np.isfinite(obj_host):
                                    event = "recovered"
                                else:
                                    # graceful degradation: keep the last
                                    # finite state, exclude the coordinate
                                    # from further passes, keep training the
                                    # rest (the record's objective is the
                                    # retained finite state; event="frozen"
                                    # marks the failure)
                                    frozen.add(name)
                                    event = "frozen"
                                    params = model.params[name]
                                    new_scores = scores[name]
                                    obs.emit_event(
                                        "resilience.freeze",
                                        cat="resilience",
                                        coordinate=name,
                                        iteration=it,
                                    )
                                update_span.set(event=event)
                        model.params[name] = params
                        scores[name] = new_scores

                        with _dispatch("objective"):
                            obj = self._full_objective(
                                scores, model.params
                            )
                        # seconds measures host dispatch+update wall time;
                        # with deferred stats the device may still be
                        # draining
                        seconds = time.perf_counter() - t0
                        vmetric = (
                            float(validation_fn(model))
                            if validation_fn is not None
                            else None
                        )
                        pending.append(
                            {
                                "iteration": it,
                                "coordinate": name,
                                "objective": obj,
                                "seconds": seconds,
                                "validation_metric": vmetric,
                                "event": event,
                                # the result object is kept whole: reading
                                # .reason/.iterations on a
                                # RandomEffectUpdateSummary materializes
                                # device buffers, which must not happen
                                # until materialize()
                                "result": result,
                            }
                        )
            force_plain = False
            pass_seconds = time.perf_counter() - pass_t0
            if obs.get_tracer() is not None:
                # live HBM counter-track sample at the pass boundary
                # (a no-op where memory_stats is unsupported)
                obs.sample_hbm()
            _reg = obs.registry()
            _reg.inc("game.passes")
            _reg.observe("game.pass_ms", pass_seconds * 1e3)
            saved = False
            if (
                checkpoint_dir is not None
                and (it + 1 - start_it) % checkpoint_every == 0
            ):
                _save_ckpt(it + 1)
                saved = True
            # host-loss poll at the pass boundary — the only point where
            # the survivors hold a complete, checkpointable snapshot
            _host_loss_boundary(it + 1, saved)
            # preemption poll at the pass boundary — the only point where
            # the training state is a complete, checkpointable snapshot
            if stop_check is not None and stop_check():
                stopped = True
                if checkpoint_dir is not None:
                    # the marker promises a durable checkpoint at this
                    # step: drain the overlapped write first
                    if not saved:
                        _save_ckpt(it + 1, wait=True)
                    else:
                        ckpt_writer.join()
                    from photon_ml_tpu.resilience.shutdown import (
                        write_preempted_marker,
                    )

                    write_preempted_marker(
                        checkpoint_dir,
                        it + 1,
                        getattr(stop_check, "signum", None),
                    )
                break
            it += 1
        # the run's durability contract: every checkpoint submitted is
        # on disk (or has raised) before run() returns
        ckpt_writer.join()
        materialize()
        if checkpoint_dir is not None and not stopped:
            # the run reached its target: a stale marker from an earlier
            # preempted attempt no longer applies
            from photon_ml_tpu.resilience.shutdown import (
                clear_preempted_marker,
            )

            clear_preempted_marker(checkpoint_dir)
        return model, history

    def total_scores(self, model: GameModel) -> jax.Array:
        return sum(
            self.coordinates[n].score(model.params[n])
            for n in self.coordinates
        )


# stacked-leaf audit threshold: below this a broadcast miss costs noise;
# above it the grid multiplies a real buffer (designs, row features)
_GRID_STACK_WARN_BYTES = 1 << 20


def _warm_start_params(coords, names, initial_model):
    """Per-coordinate starting params: the warm start's table where one
    is given (a GameModel or a plain name->params mapping), the
    coordinate's cold ``initial_params()`` otherwise. Warm leaves must
    match the cold-start structure and shapes EXACTLY — callers hand us
    entity-keyed, already-remapped tables (load_game_model /
    reindex_entity_params); a shape mismatch here means a positional or
    stale warm start and is refused, never silently cold-started."""
    init = (
        getattr(initial_model, "params", initial_model)
        if initial_model is not None
        else None
    )
    out = {}
    for n in names:
        want = coords[n].initial_params()
        if init is None or n not in init:
            out[n] = want
            continue
        try:
            got = jax.tree_util.tree_map(
                lambda g, w: jnp.asarray(g, jnp.asarray(w).dtype),
                init[n],
                want,
            )
            bad = any(
                jnp.shape(g) != jnp.shape(w)
                for g, w in zip(
                    jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want),
                )
            )
        except (ValueError, TypeError) as e:
            raise ValueError(
                f"warm start for coordinate {n!r} does not match its "
                f"parameter structure ({e})"
            ) from e
        if bad:
            raise ValueError(
                f"warm start for coordinate {n!r} has mismatched "
                "shapes — warm starts re-key by entity id "
                "(reindex_entity_params / load_game_model), never by "
                "position"
            )
        out[n] = got
    return out


def run_grid(
    cd: CoordinateDescent,
    combos: Sequence[Mapping[str, float]],
    num_iterations: int,
    seed: int = 0,
    initial_model=None,
):
    """Train EVERY reg-weight combo simultaneously by vmapping the
    per-coordinate chunked dispatch over a combo axis (SURVEY §2.5.6,
    hyperparameter parallelism).

    Grid entries share every shape — only reg weights differ — so the
    combo axis vmaps over (params, scores, reg-weight leaves) while the
    design/data arrays broadcast. This is valid exactly where the
    reference trains grid entries independently
    (``cli/game/training/Driver.scala:317-384``); it does NOT apply to
    the lambda-PATH-with-warm-starts semantics (sequential by
    definition) nor to per-update validation.

    Each combo's result is IDENTICAL to a sequential
    ``cd.run(num_iterations, seed=seed)`` with that combo's reg weights
    (same PRNG stream: every lane shares the split sequence, like the
    sequential runs each starting from the same seed).

    ``initial_model`` (a :class:`GameModel` or name->params mapping)
    warm-starts EVERY lane from the same tables — the lifecycle
    retrain's cheap in-cycle model selection: the previous export seeds
    all combos at once and each lane's result still matches
    ``cd.run(..., initial_model=...)`` with that combo. Warm tables
    must already be entity-keyed into THIS run's vocabulary; shape
    mismatches are refused (the positional warm-start bug class).

    Returns ``(models, history)``: ``models[c]`` is combo c's
    :class:`GameModel`; ``history[c]`` the combo's
    :class:`CoordinateUpdateRecord` list (fused-timing semantics —
    wall seconds on each pass's first record only).
    """
    names = list(cd.coordinates)
    coords = cd.coordinates
    combos = list(combos)
    n_combo = len(combos)
    if n_combo < 2:
        raise ValueError(
            f"run_grid needs >= 2 combos (got {n_combo}); run cd.run() "
            "for a single configuration"
        )
    for c in coords.values():
        if not hasattr(c, "fused_state_for_reg"):
            raise ValueError(
                f"{type(c).__name__} does not support grid vmapping "
                "(no fused_state_for_reg); run combos sequentially"
            )
    fns, _ = cd._coordinate_step_fns()

    # stack ONLY the leaves that vary with the reg weight; shared data
    # leaves broadcast (identified by object identity across two probe
    # states — the coordinate returns the SAME arrays for the invariant
    # parts)
    per_combo = [
        {n: coords[n].fused_state_for_reg(cb[n]) for n in names}
        for cb in combos
    ]
    probe_a = {n: coords[n].fused_state_for_reg(0.5) for n in names}
    probe_b = {n: coords[n].fused_state_for_reg(0.25) for n in names}
    axes = jax.tree_util.tree_map(
        lambda a, b: None if a is b else 0, probe_a, probe_b
    )

    # Same-OBJECT contract (``FixedEffectCoordinate.fused_state_for_reg``
    # documents it): combo-invariant leaves must come back as the
    # identical array object on every call so the identity test above
    # broadcasts them. A coordinate that rebuilds an invariant leaf per
    # call still trains CORRECTLY — but the leaf gets stacked n_combo
    # times, multiplying its footprint by the grid size. Detect the
    # miss for leaves where that costs real memory (value-equal across
    # the first two combos yet not the same object) and warn loudly
    # instead of silently burning HBM.
    def _stack_with_audit(path, *leaves):
        if all(l is leaves[0] for l in leaves):
            return leaves[0]
        stacked = jnp.stack(leaves)
        if (
            getattr(stacked, "nbytes", 0) >= _GRID_STACK_WARN_BYTES
            and np.array_equal(
                np.asarray(leaves[0]), np.asarray(leaves[1])
            )
        ):
            import warnings

            leaf_name = jax.tree_util.keystr(path)
            warnings.warn(
                f"run_grid: leaf {leaf_name} ({stacked.nbytes / 1e6:.1f}"
                f" MB stacked) is value-identical across combos but was "
                "returned as a fresh object by fused_state_for_reg, so "
                "it is stacked x{} instead of broadcast — return the "
                "SAME array object for combo-invariant leaves".format(
                    n_combo
                ),
                RuntimeWarning,
                stacklevel=3,
            )
        return stacked

    states = jax.tree_util.tree_map_with_path(
        _stack_with_audit, *per_combo
    )
    vfns = {
        n: jax.vmap(fns[n], in_axes=(axes, None, None, None, 0, 0, None))
        for n in names
    }

    def broadcast(p):
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(
                a, (n_combo,) + jnp.shape(a)
            ),
            p,
        )

    starts = _warm_start_params(coords, names, initial_model)
    params = broadcast(starts)
    scores = broadcast(
        {n: coords[n].score(starts[n]) for n in names}
    )
    key = jax.random.PRNGKey(seed)
    records = []  # (iteration, name, objective (C,), trackers, seconds)
    for it in range(num_iterations):
        t0 = time.perf_counter()
        for i, name in enumerate(names):
            key, sub = jax.random.split(key)
            p, tr, s, obj = vfns[name](
                states, cd.labels, cd.base_offsets, cd.weights,
                params, scores, sub,
            )
            params = {**params, name: p}
            scores = {**scores, name: s}
            records.append([it, name, obj, tr, None])
        records[-len(names)][4] = time.perf_counter() - t0

    # ONE batched host drain for every combo's stats
    fetch = [(r[2], r[3]) for r in records]
    with obs.span("game.fetch", cat="game", what="grid_history",
                  bytes=_tree_bytes(fetch)):
        host = jax.device_get(fetch)
    models = [
        GameModel(
            {
                n: jax.tree_util.tree_map(lambda a: a[c], params[n])
                for n in names
            }
        )
        for c in range(n_combo)
    ]
    history: List[List[CoordinateUpdateRecord]] = [
        [] for _ in range(n_combo)
    ]
    for (it, name, _, _, seconds), (objs, tr) in zip(records, host):
        for c in range(n_combo):
            tr_c = jax.tree_util.tree_map(lambda a: a[c], tr)
            summary = coords[name].wrap_tracker(tr_c)
            history[c].append(
                _history_record(
                    it,
                    name,
                    np.asarray(objs)[c],
                    summary.reason,
                    summary.iterations,
                    seconds,
                )
            )
    return models, history


def _lambda_segment_fn(cd: CoordinateDescent, length: int):
    """ONE device dispatch for a whole lambda-path segment: ``length``
    coordinate-descent passes ride a ``lax.scan`` through the same
    fused update surface as :func:`run_grid` (reg weights enter as jit
    ARGUMENTS via ``fused_state_for_reg``, so every combo on the path
    reuses this one executable — zero recompiles per lambda). Cached on
    the descent object per pass count."""
    cache = getattr(cd, "_lambda_segment_fns", None)
    if cache is None:
        cache = cd._lambda_segment_fns = {}
    fn = cache.get(length)
    if fn is not None:
        return fn
    names = list(cd.coordinates)
    coords = cd.coordinates
    loss_fn = _loss_fn_for_task(cd.task)

    def segment(states, labels, base_offsets, weights, params, scores,
                key):
        live = {
            n: coords[n].with_fused_state(states[n]) for n in names
        }

        def body(carry, _):
            params, scores, key = carry
            objs = []
            trackers = {}
            for name in names:
                key, sub = jax.random.split(key)
                total = sum(scores.values())
                partial = total - scores[name]
                p, tr, s = live[name].update_step(
                    params[name], partial, sub
                )
                params = {**params, name: p}
                scores = {**scores, name: s}
                reg = sum(
                    _coordinate_reg_term(live[n], params[n])
                    for n in names
                )
                tot = sum(scores[n] for n in names)
                objs.append(
                    loss_fn(labels, base_offsets + tot, weights) + reg
                )
                trackers[name] = tr
            return (params, scores, key), (jnp.stack(objs), trackers)

        (params, scores, key), ys = jax.lax.scan(
            body, (params, scores, key), None, length=length
        )
        return params, scores, ys

    fn = cache[length] = jax.jit(segment)
    return fn


def run_lambda_path(
    cd: CoordinateDescent,
    combos: Sequence[Mapping[str, float]],
    num_iterations: int,
    seed: int = 0,
    initial_model=None,
    scan: bool = True,
):
    """Warm-started lambda PATH over reg-weight combos — the sequential
    semantics :func:`run_grid` explicitly does not cover: combo c+1
    warm-starts from combo c's solution (order combos strongest-lambda
    first, the GLM driver's descending-path convention), so late combos
    converge from an already-good start. With ``initial_model`` the
    FIRST combo warm-starts too (the previous export, entity-keyed) —
    model selection cheap enough to run inside a lifecycle retrain
    cycle.

    Each segment rides the PR-8 scan path: ``scan=True`` runs all
    ``num_iterations`` passes of a combo as ONE device dispatch
    (``lax.scan`` over passes; :func:`_lambda_segment_fn`), compiled
    once for the whole path because reg weights are jit arguments.
    ``scan=False`` runs the identical math through the per-update
    chunked loop (one dispatch per coordinate update) — the lifecycle
    drill asserts scan==loop equivalence.

    Every combo restarts the PRNG stream from ``seed`` (matching a
    sequential ``cd.run(seed=seed)`` per combo and :func:`run_grid`'s
    lanes); only the warm start carries forward. Returns ``(models,
    history)`` shaped like :func:`run_grid` — one entry per combo, in
    path order."""
    names = list(cd.coordinates)
    coords = cd.coordinates
    combos = list(combos)
    if not combos:
        raise ValueError("run_lambda_path needs >= 1 combo")
    for c in coords.values():
        if not hasattr(c, "fused_state_for_reg"):
            raise ValueError(
                f"{type(c).__name__} does not support the lambda path "
                "(no fused_state_for_reg); run combos sequentially"
            )
    params = _warm_start_params(coords, names, initial_model)
    scores = {n: coords[n].score(params[n]) for n in names}
    fns, _ = cd._coordinate_step_fns()
    models: List[GameModel] = []
    raw: List[tuple] = []  # (combo idx, objs, trackers) device refs
    for cb in combos:
        states = {
            n: coords[n].fused_state_for_reg(cb[n]) for n in names
        }
        key = jax.random.PRNGKey(seed)
        if scan:
            t0 = time.perf_counter()
            params, scores, (objs, trackers) = _lambda_segment_fn(
                cd, num_iterations
            )(
                states, cd.labels, cd.base_offsets, cd.weights,
                params, scores, key,
            )
            raw.append((objs, trackers, time.perf_counter() - t0))
        else:
            objs_acc = []
            trackers_acc = {n: [] for n in names}
            t0 = time.perf_counter()
            for _ in range(num_iterations):
                it_objs = []
                for name in names:
                    key, sub = jax.random.split(key)
                    p, tr, s, obj = fns[name](
                        states, cd.labels, cd.base_offsets, cd.weights,
                        params, scores, sub,
                    )
                    params = {**params, name: p}
                    scores = {**scores, name: s}
                    it_objs.append(obj)
                    trackers_acc[name].append(tr)
                objs_acc.append(jnp.stack(it_objs))
            objs = jnp.stack(objs_acc)  # (T, N) like the scan output
            trackers = {
                n: jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *trackers_acc[n]
                )
                for n in names
            }
            raw.append((objs, trackers, time.perf_counter() - t0))
        models.append(GameModel(dict(params)))
    # ONE batched host drain for the whole path
    fetch = [(o, t) for o, t, _ in raw]
    with obs.span("game.fetch", cat="game", what="path_history",
                  bytes=_tree_bytes(fetch)):
        host = jax.device_get(fetch)
    history: List[List[CoordinateUpdateRecord]] = []
    for (objs, trackers), (_, _, seconds) in zip(host, raw):
        records: List[CoordinateUpdateRecord] = []
        for it in range(num_iterations):
            for i, name in enumerate(names):
                tr_it = jax.tree_util.tree_map(
                    lambda a: a[it], trackers[name]
                )
                summary = coords[name].wrap_tracker(tr_it)
                records.append(
                    _history_record(
                        it,
                        name,
                        np.asarray(objs)[it, i],
                        summary.reason,
                        summary.iterations,
                        seconds if it == 0 and i == 0 else None,
                    )
                )
        history.append(records)
    return models, history
