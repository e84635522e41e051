"""GLM training: the regularization path with warm starts.

Rebuild of ``supervised/model/GeneralizedLinearAlgorithm.scala:37,181-251``
+ ``ModelTraining.scala:32-141`` as a host loop over jitted solves:

  - the regularization weights are trained in DESCENDING order
    (``ModelTraining.scala:124``), each solve warm-started from the previous
    solution (``GeneralizedLinearAlgorithm.scala:226-235``);
  - the model is optimized in normalized space via whitening algebra folded
    into the objective (no feature materialization), then mapped back to raw
    feature space (``GeneralizedLinearAlgorithm.scala:111-113``);
  - L2 goes into the objective, L1 selects OWL-QN, TRON is L2-only — the
    validation matrix of ``Params.scala:156-173``.

The per-lambda solve is ONE jitted XLA computation (solver loop included);
regularization weights are traced scalars so the whole path reuses a single
compilation. Under pjit with a sharded batch this is the reference's
fixed-effect distributed regime; under vmap it is the per-entity regime.
"""

from __future__ import annotations

import dataclasses
import enum
import itertools
import time
from functools import lru_cache
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu import obs
from photon_ml_tpu.core.normalization import (
    NormalizationContext,
    NormalizationType,
    build_normalization_context,
    no_normalization,
)
from photon_ml_tpu.core.types import Coefficients, LabeledBatch
from photon_ml_tpu.models.glm import GeneralizedLinearModel, TaskType
from photon_ml_tpu.ops import sparse as sparse_ops
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.ops.objective import GLMObjective, RegularizationContext
from photon_ml_tpu.ops.stats import summarize_features
from photon_ml_tpu.solvers import (
    SolverConfig,
    SolverResult,
    minimize_lbfgs,
    minimize_newton,
    minimize_owlqn,
    minimize_tron,
)

# Variance guard for 1 / Hessian-diagonal, mirroring the epsilon in
# ``optimization/game/OptimizationProblem.scala:89-116`` (MathConst.EPSILON).
_VARIANCE_EPSILON = 1e-12


class HashableBounds:
    """Immutable per-coefficient bound vector with O(1) hashing AND O(1)
    equality.

    Configs key the lru_cache'd solver builders, so bounds must be
    hashable; a plain float tuple would make every cache lookup
    hash/compare d boxed floats — O(d) Python work per solve, which is
    pathological at the feature-sharded huge-d regime where
    ``parallel/distributed.py`` blocks the bounds out to d_block slots.
    The content is digested ONCE at construction into a 16-byte
    ``bytes`` key (shape + blake2b of the raw buffer); hashing hashes
    the digest and HashableBounds-vs-HashableBounds equality compares
    digests only, so every ``_build_solver`` lookup on a config carrying
    bounds costs O(1) regardless of d (a blake2b collision is
    cryptographically negligible next to lru_cache's false-hit cost)."""

    __slots__ = ("values", "digest", "_hash")

    def __init__(self, values):
        import hashlib

        import numpy as np

        arr = np.ascontiguousarray(np.asarray(values, dtype=float))
        arr.setflags(write=False)
        self.values = arr
        self.digest = (
            repr(arr.shape).encode()
            + hashlib.blake2b(arr.tobytes(), digest_size=16).digest()
        )
        self._hash = hash(self.digest)

    def __hash__(self):
        return self._hash

    def __eq__(self, other):
        import numpy as np

        if isinstance(other, HashableBounds):
            return self.digest == other.digest
        if other is None:
            return False
        try:
            return np.array_equal(
                self.values, np.asarray(other, dtype=float)
            )
        except (TypeError, ValueError):
            return NotImplemented

    def __array__(self, dtype=None, copy=None):
        import numpy as np

        return np.asarray(self.values, dtype)

    def __len__(self):
        return len(self.values)

    def __iter__(self):
        return iter(self.values.tolist())

    def __repr__(self):
        return f"HashableBounds(d={self.values.size})"


class OptimizerType(enum.Enum):
    """``optimization/OptimizerType.scala`` + NEWTON, a TPU-native
    addition: exact Newton/IRLS with an explicit (d, d) Hessian and
    Cholesky solves — one MXU pass per iteration. The reference cannot
    afford the d^2 treeAggregate; small-d TPU solves can (dense features,
    scale-only normalization, L2 only)."""

    LBFGS = "LBFGS"
    TRON = "TRON"
    NEWTON = "NEWTON"


@dataclasses.dataclass(frozen=True)
class GLMTrainingConfig:
    """Typed analog of the core driver's ``Params.scala:36-183`` knobs that
    concern a single training run (I/O and staging knobs live in cli/)."""

    task: TaskType = TaskType.LOGISTIC_REGRESSION
    optimizer: OptimizerType = OptimizerType.LBFGS
    reg_weights: Tuple[float, ...] = (0.0,)
    regularization: RegularizationContext = RegularizationContext()
    normalization: NormalizationType = NormalizationType.NONE
    max_iters: int = 80
    tolerance: float = 1e-7
    num_corrections: int = 10
    intercept_index: Optional[int] = None
    # box constraints as content-hashed HashableBounds so configs key the
    # solver cache in O(1); tuples/arrays are accepted and wrapped
    lower_bounds: Optional[HashableBounds] = None
    upper_bounds: Optional[HashableBounds] = None
    compute_variances: bool = False
    track_states: bool = True
    # per-iteration coefficient snapshots (ModelTracker,
    # ``supervised/model/ModelTracker.scala``) — feeds validate-per-iteration
    track_models: bool = False
    # regularization-path execution mode: "scan" runs the WHOLE
    # descending-lambda path as ONE jitted ``lax.scan`` program (one
    # dispatch + one decode for N lambdas — the device-resident rebuild
    # of ``ModelTraining.scala:32-141``); "loop" keeps the host loop of
    # one dispatch per lambda (the reference shape, kept as the
    # equivalence oracle and an escape hatch for toolchains that cannot
    # compile the scanned program)
    path_mode: str = "scan"

    def __post_init__(self):
        import numpy as np

        v = self.reg_weights
        if v is not None:
            # normalize ANY sequence (incl. device arrays: one transfer,
            # not one sync per element) to a hashable float tuple
            object.__setattr__(
                self,
                "reg_weights",
                tuple(np.asarray(v, dtype=float).tolist()),
            )
        for name in ("lower_bounds", "upper_bounds"):
            v = getattr(self, name)
            if v is not None and not isinstance(v, HashableBounds):
                object.__setattr__(self, name, HashableBounds(v))

    def validate(self) -> None:
        """The reference's cross-flag validation matrix
        (``Params.scala:156-173``, ``OptimizationProblem.scala:155-161``)."""
        if self.path_mode not in ("scan", "loop"):
            raise ValueError(
                f"path_mode must be 'scan' or 'loop', got {self.path_mode!r}"
            )
        has_l1 = self.regularization.reg_type in ("L1", "ELASTIC_NET")
        if self.optimizer == OptimizerType.TRON and has_l1:
            raise ValueError(
                "TRON does not support L1 regularization "
                "(reference Params.scala:158-161)"
            )
        has_constraints = (
            self.lower_bounds is not None or self.upper_bounds is not None
        )
        if has_constraints and self.normalization != NormalizationType.NONE:
            raise ValueError(
                "box constraints cannot be combined with normalization "
                "(reference Params.scala:162-165)"
            )
        if (
            self.optimizer == OptimizerType.TRON
            and not loss_for_task(self.task).twice_differentiable
        ):
            raise ValueError(
                f"{self.task} is first-order only; use LBFGS "
                "(reference SmoothedHingeLossFunction.scala:24-60)"
            )
        if (
            self.normalization == NormalizationType.STANDARDIZATION
            and self.intercept_index is None
        ):
            raise ValueError(
                "standardization requires an intercept term "
                "(reference Params.scala:166-169)"
            )
        if self.optimizer == OptimizerType.NEWTON:
            if has_l1:
                raise ValueError("NEWTON supports L2 only (use OWL-QN for L1)")
            if not loss_for_task(self.task).twice_differentiable:
                raise ValueError(f"{self.task} is first-order only; use LBFGS")
            if has_constraints:
                raise ValueError(
                    "NEWTON does not support box constraints; use LBFGS"
                )
            if self.normalization == NormalizationType.STANDARDIZATION:
                raise ValueError(
                    "NEWTON supports scale-only normalization (no whiten "
                    "shifts); use SCALE_WITH_* or NONE"
                )

    def solver_config(self) -> SolverConfig:
        lb = self.lower_bounds
        ub = self.upper_bounds
        return SolverConfig(
            max_iters=self.max_iters,
            tolerance=self.tolerance,
            num_corrections=self.num_corrections,
            lower_bounds=None if lb is None else jnp.asarray(lb.values),
            upper_bounds=None if ub is None else jnp.asarray(ub.values),
            track_states=self.track_states,
            track_models=self.track_models,
        )


@dataclasses.dataclass(frozen=True)
class TrainedModel:
    """(lambda, model, solver trace) — the reference returns
    List[(Double, GeneralizedLinearModel)] plus ModelTracker."""

    reg_weight: float
    model: GeneralizedLinearModel
    result: SolverResult


def _build_solver(config: GLMTrainingConfig):
    """jitted solve(w0, reg_weight, batch, norm) with traced reg weight and
    normalization arrays. Cached on the (hashable) config so repeated
    train_glm calls — the lambda path, GAME coordinate-descent rounds,
    bootstrap replicas — reuse ONE compilation instead of re-tracing.
    The cache key zeroes reg_weights (they are traced call arguments, not
    trace-time constants), so configs differing only in lambdas share the
    compilation too."""
    return _build_solver_cached(
        dataclasses.replace(config, reg_weights=(0.0,))
    )


def _solver_step_fn(config: GLMTrainingConfig):
    """Trace-safe ``solve(w0, reg_weight, batch, norm) -> SolverResult``
    closure — the ONE per-lambda solve body shared by the per-lambda jit
    (``path_mode="loop"``) and the scanned whole-path program
    (``path_mode="scan"``), so the two modes cannot drift."""
    loss = loss_for_task(config.task)
    reg = config.regularization
    scfg = config.solver_config()
    use_owlqn = reg.reg_type in ("L1", "ELASTIC_NET")
    use_tron = config.optimizer == OptimizerType.TRON
    use_newton = config.optimizer == OptimizerType.NEWTON

    def solve(w0, reg_weight, batch: LabeledBatch, norm: NormalizationContext):
        l1 = reg_weight * reg.l1_weight(1.0)
        l2 = reg_weight * reg.l2_weight(1.0)
        obj = GLMObjective(loss=loss, normalization=norm, l2_weight=l2)
        vg = lambda w: obj.value_and_grad(w, batch)
        if use_owlqn:
            return minimize_owlqn(vg, w0, l1, scfg)
        if use_tron:
            hvp = lambda w, v: obj.hessian_vector(w, v, batch)
            return minimize_tron(
                vg, hvp, w0, scfg,
                hvp_at_fn=lambda c, v: obj.hessian_vector_at(c, v, batch),
                vgc_fn=lambda w: obj.value_grad_curvature(w, batch),
            )
        if use_newton:
            hess = lambda w: obj.hessian_full(w, batch)
            return minimize_newton(vg, hess, w0, scfg)
        return minimize_lbfgs(vg, w0, scfg)

    return solve


def _variances_fn(config: GLMTrainingConfig):
    """Trace-safe per-coefficient variance estimate (1 / Hessian diag)."""
    loss = loss_for_task(config.task)
    reg = config.regularization

    def variances(
        w, reg_weight, batch: LabeledBatch, norm: NormalizationContext
    ):
        l2 = reg_weight * reg.l2_weight(1.0)
        obj = GLMObjective(loss=loss, normalization=norm, l2_weight=l2)
        diag = obj.hessian_diagonal(w, batch)
        return 1.0 / jnp.maximum(diag, _VARIANCE_EPSILON)

    return variances


@lru_cache(maxsize=64)
def _build_solver_cached(config: GLMTrainingConfig):
    return (
        jax.jit(_solver_step_fn(config)),
        jax.jit(_variances_fn(config)),
    )


def _build_path_solver(config: GLMTrainingConfig):
    """jitted ``solve_path(w0, reg_weights, batch, norm)`` running the
    WHOLE descending-lambda regularization path as ONE XLA program: a
    ``lax.scan`` over the lambda vector whose carry is the warm-start
    coefficients (exactly the host loop's warm-start chaining,
    ``GeneralizedLinearAlgorithm.scala:226-235``) and whose stacked ys
    carry, per lambda: the full SolverResult (PR-7 convergence tapes
    included — they ride the scan axis), the de-normalized raw-space
    coefficient means, variances when ``compute_variances``, and
    de-normalized ModelTracker snapshots when ``track_models``. The host
    dispatches ONCE per path and decodes afterwards; the carry is
    donated (off-CPU) so the warm start runs copy-free in HBM. Same
    cache-key convention as ``_build_solver``: reg weights are traced
    call arguments, so configs differing only in lambdas share one
    compilation (a new PATH LENGTH is a new input shape — one XLA
    compile per length, no Python re-trace)."""
    return _build_path_solver_cached(
        dataclasses.replace(config, reg_weights=(0.0,))
    )


@lru_cache(maxsize=64)
def _build_path_solver_cached(config: GLMTrainingConfig):
    solve_one = _solver_step_fn(config)
    variances = _variances_fn(config)
    compute_variances = config.compute_variances
    track_models = config.track_models
    intercept_index = config.intercept_index

    def solve_path(
        w0, reg_weights, batch: LabeledBatch, norm: NormalizationContext
    ):
        def step(w, lam):
            result = solve_one(w, lam, batch, norm)
            coef = Coefficients(
                means=result.w,
                variances=(
                    variances(result.w, lam, batch, norm)
                    if compute_variances
                    else None
                ),
            )
            raw = norm.transform_model_coefficients(coef, intercept_index)
            ys = {"result": result, "means": raw.means}
            if raw.variances is not None:
                ys["variances"] = raw.variances
            if track_models and result.w_history is not None:
                # de-normalize the per-iteration snapshots in-program
                # (the host loop vmaps the same transform per lambda)
                ys["w_history_raw"] = jax.vmap(
                    lambda m: norm.transform_model_coefficients(
                        Coefficients(means=m), intercept_index
                    ).means
                )(result.w_history)
            return result.w, ys

        _, ys = lax.scan(step, w0, reg_weights)
        return ys

    # donating the warm-start carry keeps the path copy-free in HBM;
    # CPU backends ignore donation with a warning, so skip it there
    donate = (0,) if jax.default_backend() != "cpu" else ()
    return jax.jit(solve_path, donate_argnums=donate)


def _record_solve_metrics(config: GLMTrainingConfig, result) -> None:
    """Route a completed solve to its solver module's metric recorder —
    the dispatch mirrors ``_build_solver_cached``'s solver selection
    (L1/elastic-net means the LBFGS enum actually ran OWL-QN)."""
    if config.regularization.reg_type in ("L1", "ELASTIC_NET"):
        from photon_ml_tpu.solvers.lbfgs import record_solve_metrics

        record_solve_metrics(result, owlqn=True)
    elif config.optimizer == OptimizerType.TRON:
        from photon_ml_tpu.solvers.tron import record_solve_metrics

        record_solve_metrics(result)
    elif config.optimizer == OptimizerType.LBFGS:
        from photon_ml_tpu.solvers.lbfgs import record_solve_metrics

        record_solve_metrics(result)
    else:
        from photon_ml_tpu.solvers.common import record_solver_metrics

        record_solver_metrics(config.optimizer.name.lower(), result)


# identifies one path solve in its spans (the `job` attribute)
_JOB_IDS = itertools.count(1)


_summarize_jit = jax.jit(summarize_features)


def solve_dtype(batch: LabeledBatch):
    """Solver-state dtype for a batch: at least float32. Features may be
    stored bfloat16 (halved HBM + host->device bytes; the MXU upconverts
    inside the matmul), but optimizer state, gradients, and line-search
    scalars need f32 accumulation to converge to reference tolerances."""
    return jnp.promote_types(batch.features.dtype, jnp.float32)


def prepare_normalization(
    config: GLMTrainingConfig, batch: LabeledBatch
) -> NormalizationContext:
    """Feature summary pass -> whitening context (``Driver.scala:229-253``)."""
    if config.normalization == NormalizationType.NONE:
        return no_normalization()
    summary = _summarize_jit(batch)
    return build_normalization_context(
        config.normalization, summary, config.intercept_index
    )


@jax.jit
def _take_rows(perm, *columns):
    return tuple(c[perm] for c in columns)


def _hot_cold_layout(
    batch: LabeledBatch, config: GLMTrainingConfig
) -> LabeledBatch:
    """The batch this solve should run on: a plain padded-ELL design split
    hot/cold on its device where ``ops.sparse.split_hot_cold`` finds that
    its own column counts pay for it over this solve, the row-aligned
    columns permuted to the hybrid's stored order; any other batch as it
    came. The models live in coefficient space, so callers see no
    difference. Paid inside every call, under the ``glm.layout`` span,
    and nothing of it outlives the call: a caller that solves many times
    on one design splits once (``split_hot_cold``) and passes the
    ``HybridFeatures``.

    The one place where ``train_glm`` waits for the device before its
    solve is enqueued: on a plain padded-ELL design on one device whose
    kind has measured rates, the rule sorts the slots' ids and fetches the
    largest counts before it can answer, engaged or declined, so such
    calls no longer pipeline behind one another (every other batch is
    answered on the host, in microseconds, as before)."""
    x = batch.features
    with obs.span("glm.layout", cat="solver") as sp:
        if not sparse_ops.is_sparse(x):
            info = {
                "reason": "hybrid" if sparse_ops.is_hybrid(x)
                else "feature_sharded" if sparse_ops.is_feature_sharded(x)
                else "dense"
            }
        elif config.optimizer == OptimizerType.NEWTON:
            # the explicit Hessian is built from dense features
            info = {"reason": "dense_hessian"}
        else:
            itemsize = jnp.dtype(solve_dtype(batch)).itemsize
            hybrid, info = sparse_ops.split_hot_cold(
                x,
                # every iteration evaluates the objective at least once
                evaluations=len(config.reg_weights) * max(1, config.max_iters),
                exact_squares=config.compute_variances,
                # the quasi-Newton pairs and the solver's working vectors
                solver_bytes=(2 * config.num_corrections + 10)
                * x.d * itemsize,
            )
        if "reason" in info:
            obs.registry().inc("sparse.split.skipped")
            obs.registry().inc("sparse.split.skipped." + info["reason"])
            sp.set(hot_columns=0, **info)
            return batch
        obs.registry().inc("sparse.split.engaged")
        sp.set(**info)
        labels, offsets, weights, mask = _take_rows(
            hybrid.row_perm,
            batch.labels, batch.offsets, batch.weights, batch.mask,
        )
        return dataclasses.replace(
            batch, features=hybrid, labels=labels, offsets=offsets,
            weights=weights, mask=mask,
        )


def train_glm(
    batch: LabeledBatch,
    config: GLMTrainingConfig,
    initial_coefficients: Optional[Coefficients] = None,
    normalization: Optional[NormalizationContext] = None,
) -> Sequence[TrainedModel]:
    """Train one model per regularization weight, descending, warm-started.

    Returns models in the ORIGINAL config order of reg_weights (like
    ``ModelTraining.scala:130-140``, which sorts for training but reports
    per input order). Coefficients are de-normalized to raw feature space;
    `initial_coefficients` are likewise expected in RAW space (e.g. a
    previously returned model) and are mapped into normalized space before
    solving.

    With ``path_mode="scan"`` (default) the whole path — every solve,
    warm-start chaining, de-normalization, variances — executes as ONE
    XLA dispatch (``_build_path_solver``); ``path_mode="loop"`` keeps
    the reference-shaped host loop of one dispatch per lambda. Both
    modes are numerically equivalent to <= 1e-10 (asserted in
    tests/test_device_loops.py) and share the per-lambda solve body.
    """
    config.validate()
    norm = (
        normalization
        if normalization is not None
        else prepare_normalization(config, batch)
    )
    batch = _hot_cold_layout(batch, config)
    d = batch.num_features
    dtype = solve_dtype(batch)
    if initial_coefficients is not None:
        w = norm.inverse_transform_model_coefficients(
            initial_coefficients, config.intercept_index
        ).means
        w = jnp.asarray(w, dtype)
        if config.path_mode == "scan":
            # the path program donates its carry argument; hand it a
            # fresh buffer so the caller's warm-start model (which, with
            # identity normalization, w aliases) is never invalidated
            w = w + jnp.zeros((), dtype)
    else:
        w = jnp.zeros((d,), dtype)

    if config.path_mode == "scan":
        return _train_glm_scan(batch, config, norm, w)
    return _train_glm_loop(batch, config, norm, w)


def _train_glm_scan(
    batch: LabeledBatch,
    config: GLMTrainingConfig,
    norm: NormalizationContext,
    w: jax.Array,
) -> Sequence[TrainedModel]:
    """Single-dispatch regularization path: one ``lax.scan`` program over
    the descending lambda vector, decoded on the host afterwards. The
    untraced path inserts NO host syncs — results are lazy slices of the
    stacked ys, so consecutive train_glm calls still pipeline (bench.py's
    pipelined reading, on a dense design, depends on that; the one wait
    of ``train_glm`` is before this function, in ``_hot_cold_layout``, on
    plain sparse designs only); the traced/convergence-enabled path synchronizes
    once and retro-emits per-lambda ``glm.solve`` spans + tape counters
    inside the one ``glm.solve_path`` span window. Spans: the root
    ``glm.solve_path`` holds ``glm.dispatch`` (the call into the compiled
    program, until it returns) and ``glm.decode`` (the host-side indexing
    of the stacked result: a handful of tiny eager programs)."""
    dtype = solve_dtype(batch)
    lams = sorted(config.reg_weights, reverse=True)
    solve_path = _build_path_solver(config)
    with obs.span(
        "glm.solve_path",
        cat="solver",
        job=next(_JOB_IDS),
        optimizer=config.optimizer.name,
        path_len=len(lams),
        dispatches=1,
    ) as sp:
        tracer = obs.get_tracer()
        t0 = time.perf_counter()
        with obs.span("glm.dispatch", cat="solver", program="solve_path"):
            ys = solve_path(w, jnp.asarray(lams, dtype), batch, norm)
        conv_enabled = (
            tracer is not None or obs.convergence.tracking_enabled()
        )
        from photon_ml_tpu.solvers.common import index_result

        if conv_enabled:
            # one sync for the whole path, then the per-element decode:
            # solver metrics, convergence reports/events, and — under a
            # tracer — retro-stamped per-lambda glm.solve spans whose
            # windows split the path wall proportionally to each solve's
            # counted design passes (the honest attribution available
            # for an indivisible dispatch), each carrying its (value,
            # |grad|) counter replay
            sp.sync(ys["means"])
            seconds = time.perf_counter() - t0
        with obs.span("glm.decode", cat="solver"):
            # lazy per-lambda slices of the stacked ys (each slice is an
            # async device op, not a sync — the pipelined-solve contract)
            results = [
                index_result(ys["result"], i) for i in range(len(lams))
            ]
            if conv_enabled:
                _note_path_convergence(
                    config, lams, results, tracer, t0, seconds
                )
            by_lambda = {}
            for i, lam in enumerate(lams):
                result = results[i]
                if config.track_models and "w_history_raw" in ys:
                    result = dataclasses.replace(
                        result, w_history=ys["w_history_raw"][i]
                    )
                coef = Coefficients(
                    means=ys["means"][i],
                    variances=(
                        ys["variances"][i] if "variances" in ys else None
                    ),
                )
                model = GeneralizedLinearModel(
                    coefficients=coef, task=config.task
                )
                by_lambda[lam] = TrainedModel(
                    reg_weight=lam, model=model, result=result
                )
    return [by_lambda[lam] for lam in config.reg_weights]


def _note_path_convergence(config, lams, results, tracer, t0, seconds):
    """The synchronized decode of one scanned path (``results`` are on
    the host's side of a sync): solver metrics and convergence reports
    for every lambda and, under a tracer, one retro-stamped ``glm.solve``
    span a lambda."""
    from photon_ml_tpu.solvers.common import design_passes

    passes = [design_passes(r) for r in results]
    total_passes = sum(passes) or 1.0
    start_s = t0
    for i, (lam, result) in enumerate(zip(lams, results)):
        _record_solve_metrics(config, result)
        report = obs.decode_result(
            result, optimizer=config.optimizer.name.lower()
        )
        obs.convergence.note_solve(report, label=f"lambda={float(lam):g}")
        if tracer is not None:
            share_s = seconds * passes[i] / total_passes
            obs.add_span(
                "glm.solve",
                start_s,
                start_s + share_s,
                cat="solver",
                optimizer=config.optimizer.name,
                reg_weight=float(lam),
                path=True,
                passes=passes[i],
                convergence_reason=report.reason,
                convergence_order=report.order,
            )
            obs.convergence.emit_tape_counters(
                report, tracer, tracer.us_of(start_s), share_s * 1e6
            )
            start_s += share_s


def train_glm_streamed(
    design,
    config: GLMTrainingConfig,
    initial_coefficients: Optional[Coefficients] = None,
) -> Sequence[TrainedModel]:
    """Out-of-core ``train_glm``: the design exceeds HBM, so every
    objective evaluation STREAMS the host-resident chunks of a
    :class:`photon_ml_tpu.io.pipeline.StreamedDesign` through the fused
    per-chunk passes, accumulating exact value/grad/curvature partials
    in a donated carry (``io.pipeline.StreamingObjective``). The
    UNMODIFIED device solver loops drive it — inside their
    ``lax.while_loop`` the sweep runs through ``jax.pure_callback`` —
    so TRON / L-BFGS / OWL-QN see the exact full-dataset objective and
    the trained models match the in-core path to <= 1e-10
    (tests/test_pipeline.py, drilled across solvers and prefetch
    depths).

    Same contract as :func:`train_glm` (descending warm-started lambda
    path, models reported in config order, variances from the streamed
    Hessian diagonal) with out-of-core restrictions: dense chunked
    designs only, ``normalization=NONE`` (a whitening summary would
    itself need a streaming pass — not reproduced), no NEWTON (explicit
    Hessians need the in-core design).
    """
    import numpy as np

    from photon_ml_tpu.io.pipeline import StreamingObjective

    config.validate()
    if config.normalization != NormalizationType.NONE:
        raise ValueError(
            "train_glm_streamed supports normalization=NONE only (the "
            "whitening summary needs its own streaming pass)"
        )
    if config.optimizer == OptimizerType.NEWTON:
        raise ValueError(
            "NEWTON materializes the explicit Hessian from the in-core "
            "design; use TRON or LBFGS for out-of-core training"
        )
    loss = loss_for_task(config.task)
    reg = config.regularization
    scfg = config.solver_config()
    use_owlqn = reg.reg_type in ("L1", "ELASTIC_NET")
    use_tron = config.optimizer == OptimizerType.TRON
    dtype = np.dtype(design.dtype)
    if initial_coefficients is not None:
        w = jnp.asarray(initial_coefficients.means, dtype)
    else:
        w = jnp.zeros((design.d,), dtype)

    by_lambda = {}
    for lam in sorted(config.reg_weights, reverse=True):
        l1 = lam * reg.l1_weight(1.0)
        l2 = lam * reg.l2_weight(1.0)
        sobj = StreamingObjective(design, loss, l2_weight=l2)
        with obs.span(
            "glm.solve",
            cat="solver",
            optimizer=config.optimizer.name,
            reg_weight=float(lam),
            streamed=True,
            chunks=design.num_chunks,
        ) as sp:
            tracer = obs.get_tracer()
            t0 = time.perf_counter()
            # disable_jit: the solver while_loops run as HOST loops, so
            # each objective evaluation's chunk sweep executes directly
            # on the calling thread. Wrapped in a compiled while_loop
            # the sweep would run via pure_callback on a runtime
            # callback thread, whose nested chunk dispatches can
            # deadlock a single-threaded CPU executor (observed) — and
            # out-of-core solves are sweep-bound anyway, so host-side
            # solver control flow costs nothing measurable.
            with jax.disable_jit():
                if use_owlqn:
                    result = minimize_owlqn(
                        sobj.value_and_grad, w, l1, scfg
                    )
                elif use_tron:
                    result = minimize_tron(
                        sobj.value_and_grad, sobj.hessian_vector, w, scfg
                    )
                else:
                    result = minimize_lbfgs(sobj.value_and_grad, w, scfg)
            conv_enabled = (
                tracer is not None or obs.convergence.tracking_enabled()
            )
            if conv_enabled:
                sp.sync(result.w)
                _record_solve_metrics(config, result)
                report = obs.decode_result(
                    result, optimizer=config.optimizer.name.lower()
                )
                obs.convergence.note_solve(
                    report, label=f"lambda={float(lam):g} (streamed)"
                )
                sp.set(
                    convergence_reason=report.reason,
                    convergence_order=report.order,
                    sweep_s=round(time.perf_counter() - t0, 4),
                )
        w = result.w  # warm start for the next (smaller) lambda
        var = None
        if config.compute_variances:
            var = jnp.asarray(
                1.0
                / np.maximum(
                    sobj.hessian_diagonal(np.asarray(result.w)),
                    _VARIANCE_EPSILON,
                ),
                dtype,
            )
        # normalization is NONE: solved space IS raw feature space
        coef = Coefficients(means=result.w, variances=var)
        model = GeneralizedLinearModel(coefficients=coef, task=config.task)
        by_lambda[lam] = TrainedModel(
            reg_weight=lam, model=model, result=result
        )
    return [by_lambda[lam] for lam in config.reg_weights]


def _train_glm_loop(
    batch: LabeledBatch,
    config: GLMTrainingConfig,
    norm: NormalizationContext,
    w: jax.Array,
) -> Sequence[TrainedModel]:
    """The reference-shaped host loop (one jit dispatch per lambda) —
    ``path_mode="loop"``, kept as the scan path's equivalence oracle."""
    solve, variances_fn = _build_solver(config)
    dtype = solve_dtype(batch)
    by_lambda = {}
    job = next(_JOB_IDS)
    for lam in sorted(config.reg_weights, reverse=True):
        with obs.span(
            "glm.solve",
            cat="solver",
            job=job,
            optimizer=config.optimizer.name,
            reg_weight=float(lam),
        ) as sp:
            tracer = obs.get_tracer()
            t0 = time.perf_counter()
            with obs.span("glm.dispatch", cat="solver", program="solve"):
                result = solve(w, jnp.asarray(lam, dtype), batch, norm)
            conv_enabled = (
                tracer is not None
                or obs.convergence.tracking_enabled()
            )
            if conv_enabled:
                # device-time attribution + per-solve iteration counters
                # + the convergence decode. All synchronize, so they run
                # ONLY under an active tracer (or an installed
                # --convergence-report tracker): the disabled path must
                # keep pipelined solves (bench.py) free of inserted host
                # syncs.
                sp.sync(result.w)
                seconds = time.perf_counter() - t0
                _record_solve_metrics(config, result)
                # convergence-health decode (obs/convergence.py): the
                # in-program tapes -> reason/rate/plateau report,
                # convergence.* metrics, a structured event carrying
                # the tapes, and a Chrome counter track replaying the
                # (value, |grad|) curve under this span's window
                report = obs.decode_result(
                    result, optimizer=config.optimizer.name.lower()
                )
                obs.convergence.note_solve(
                    report, label=f"lambda={float(lam):g}"
                )
                sp.set(
                    convergence_reason=report.reason,
                    convergence_order=report.order,
                )
                if tracer is not None:
                    obs.convergence.emit_tape_counters(
                        report, tracer, tracer.us_of(t0), seconds * 1e6
                    )
        w = result.w  # warm start for the next (smaller) lambda
        if config.track_models and result.w_history is not None:
            # snapshots leave the solver in normalized space; de-normalize
            # rows so ModelTracker consumers see raw-feature coefficients
            hist = jax.vmap(
                lambda m: norm.transform_model_coefficients(
                    Coefficients(means=m), config.intercept_index
                ).means
            )(result.w_history)
            result = dataclasses.replace(result, w_history=hist)
        var = (
            variances_fn(result.w, jnp.asarray(lam, dtype), batch, norm)
            if config.compute_variances
            else None
        )
        coef = Coefficients(means=result.w, variances=var)
        coef = norm.transform_model_coefficients(coef, config.intercept_index)
        model = GeneralizedLinearModel(coefficients=coef, task=config.task)
        by_lambda[lam] = TrainedModel(
            reg_weight=lam, model=model, result=result
        )

    return [by_lambda[lam] for lam in config.reg_weights]
