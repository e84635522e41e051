"""Factored random effects + matrix-factorization scoring.

Rebuild of ``algorithm/FactoredRandomEffectCoordinate.scala:37-267``: when
entities are too many / data too thin for full per-entity coefficient
vectors, factor the random effect as  w_e = B gamma_e  with a shared
projection B (d x k) and per-entity latent coefficients gamma_e (k,).
Training alternates (numInnerIterations x):

  (a) project the active design through the current B and solve the
      per-entity latent GLMs (a RandomEffect solve in k dims);
  (b) re-fit B as ONE GLM whose virtual features are the Kronecker
      products x (x) gamma_e (``kroneckerProductFeaturesAndCoefficients``
      :251-266) — here the Kronecker design is NEVER materialized: margins,
      gradients, and Hessian-vector products contract X, gamma, and B
      directly by einsum, so phase (b) costs O(E R d k) FLOPs and
      O(E R d) memory instead of the O(E R d k) memory a materialized
      (E*R, d*k) matrix would need.

Accepts a :class:`BucketedRandomEffectDesign` (or a single global-cap
design, wrapped as one bucket): phase (a) runs per bucket with
gather/scatter against the global gamma table; phase (b) sums every
bucket's contribution into one shared-B objective.

``MatrixFactorizationModel`` (``model/MatrixFactorizationModel.scala:30-134``)
is the inference-side pairing: two latent tables scored by gathered dot.
"""

from __future__ import annotations

import copy
import dataclasses
from functools import lru_cache
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from photon_ml_tpu.core.types import _pytree_dataclass
from photon_ml_tpu.game.coordinates import (
    CoordinateConfig,
    _design_offsets_maps,
    _make_solve,
)
from photon_ml_tpu.game.data import (
    BucketedRandomEffectDesign,
    RandomEffectDesign,
    gather_offsets_compact,
)
from photon_ml_tpu.models.training import OptimizerType
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.solvers import (
    minimize_lbfgs,
    minimize_owlqn,
    minimize_tron,
)
from photon_ml_tpu.solvers.common import (
    ConvergenceReason,
    final_grad_norm,
    reason_histogram,
)


@_pytree_dataclass
class FactoredParams:
    """(per-entity latent table, shared projection)."""

    gamma: jax.Array  # (E, k)
    projection: jax.Array  # (d, k)


def is_factored_params(x) -> bool:
    """THE predicate for factored parameter containers — persistence and
    checkpointing dispatch on it."""
    return isinstance(x, FactoredParams)


@dataclasses.dataclass(frozen=True)
class FactoredConfig:
    """``MFOptimizationConfiguration.scala:24-46`` ("numInnerIter,latentDim")
    plus the two sub-configs (random-effect & latent-matrix) the reference
    parses from its triple-config string."""

    latent_dim: int
    num_inner_iterations: int = 1
    random_effect_config: Optional[CoordinateConfig] = None
    latent_factor_config: Optional[CoordinateConfig] = None

    def __post_init__(self):
        if self.latent_dim < 1:
            raise ValueError(f"latent_dim must be >= 1, got {self.latent_dim}")
        if self.num_inner_iterations < 1:
            raise ValueError(
                f"num_inner_iterations must be >= 1, got "
                f"{self.num_inner_iterations}"
            )


@_pytree_dataclass
class FactoredUpdateTracker:
    """What one factored update says of itself, on the device: the
    reference's array of (random effect, latent matrix) trackers an inner
    iteration (``FactoredRandomEffectOptimizationTracker.scala:27``).

    ``lanes[b]`` is bucket ``b``'s ``(reason, iterations, final grad
    norm)``, each ``(num_inner_iterations, E_b)``: every lane of every
    bucket, every inner iteration. The ``projection_*`` leaves are
    ``(num_inner_iterations,)``, one entry a solve of the shared B:
    its outer iterations, its CG iterations (== Hessian-vector products;
    0 for a first-order solver), its passes over the design (TRON: outer
    + 1 + CG; otherwise the solver's counted evaluations), its reason and
    its final gradient norm."""

    lanes: tuple
    projection_iterations: jax.Array
    projection_cg_iterations: jax.Array
    projection_passes: jax.Array
    projection_reason: jax.Array
    projection_grad_norm: jax.Array


@dataclasses.dataclass
class FactoredUpdateSummary:
    """Lazy host view of one :class:`FactoredUpdateTracker` (the factored
    counterpart of ``RandomEffectUpdateSummary``): device arrays until
    read. ``reason`` / ``iterations`` / ``grad_norms`` / ``entity_ids``
    are over every real lane of every bucket in the LAST inner iteration;
    ``inner_iterations`` is the whole array, one dict an inner iteration.

    ``history_fetch`` / ``history_decode`` are what
    ``CoordinateDescent``'s history drain calls, so that the tracker
    rides the run's one batched device-to-host transfer."""

    tracker: FactoredUpdateTracker
    valid_lanes: list  # per bucket (E_b,) bool, host: not a sharding pad
    entity_index: list  # per bucket (E_b,) int, host: lane -> table row
    _host: Optional[tuple] = None  # history_decode's result, once read

    def history_fetch(self):
        return self.tracker

    def history_decode(self, host: FactoredUpdateTracker):
        """(reason, iterations, grad_norms, entity_ids, inner_iterations)
        from the fetched tracker."""
        valid = [np.asarray(v) for v in self.valid_lanes]

        def lanes_of(field: int, inner: int) -> np.ndarray:
            return np.concatenate(
                [
                    np.asarray(bucket[field])[inner][v]
                    for bucket, v in zip(host.lanes, valid)
                ]
            )

        num_inner = int(np.shape(host.projection_iterations)[0])
        inner_iterations = []
        for i in range(num_inner):
            reasons, iters = lanes_of(0, i), lanes_of(1, i)
            inner_iterations.append(
                {
                    "lanes": {
                        "count": int(iters.size),
                        "solver_iterations": (
                            float(np.mean(iters)) if iters.size else 0.0
                        ),
                        "convergence_histogram": reason_histogram(reasons),
                    },
                    "projection": {
                        "iterations": int(host.projection_iterations[i]),
                        "cg_iterations": int(
                            host.projection_cg_iterations[i]
                        ),
                        "passes": int(host.projection_passes[i]),
                        "reason": ConvergenceReason(
                            int(host.projection_reason[i])
                        ).name,
                        "grad_norm": float(host.projection_grad_norm[i]),
                    },
                }
            )
        last = num_inner - 1
        entity_ids = np.concatenate(
            [np.asarray(ei)[v] for ei, v in zip(self.entity_index, valid)]
        )
        return (
            lanes_of(0, last),
            lanes_of(1, last),
            lanes_of(2, last),
            entity_ids,
            inner_iterations,
        )

    def _decoded(self):
        if self._host is None:
            self._host = self.history_decode(jax.device_get(self.tracker))
        return self._host

    @property
    def reason(self) -> np.ndarray:
        return self._decoded()[0]

    @property
    def iterations(self) -> np.ndarray:
        return self._decoded()[1]

    @property
    def grad_norms(self) -> np.ndarray:
        return self._decoded()[2]

    @property
    def entity_ids(self) -> np.ndarray:
        return self._decoded()[3]

    @property
    def inner_iterations(self) -> List[dict]:
        return self._decoded()[4]


def _einsum(spec, a, b):
    """Every contraction of the design with B, V or gamma. At matmul
    precision HIGHEST: the k-wide products go to the MXU, whose default
    rounds float32 operands to bfloat16 (the objective the chip reported
    read 7e-6 to 1.7e-5 off the float32 reference's before this, 1e-7
    for the plain coordinates: PERF.md section 6, PR 36). A float32
    configuration stays float32."""
    return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST)


def _latent_objective(loss, lam, shape, gammas, buckets_offsets, buckets):
    """``(value_and_grad(vecB), hvp(vecB, vecV))`` of the shared
    projection's GLM over the buckets' weighted, masked slots, vec(B) the
    (d * k,) coefficient vector of the virtual Kronecker features
    x (x) gamma, which are never built: every term contracts the bucket's
    features, its lanes' gammas and B (or V) directly."""
    d, k = shape

    def margins(B, bucket, gamma_b, offsets):
        xb = _einsum("erd,dk->erk", bucket.features, B)
        return _einsum("erk,ek->er", xb, gamma_b) + offsets

    def value_and_grad(vecB):
        B = vecB.reshape(d, k)
        val = 0.5 * lam * jnp.vdot(B, B)
        grad = lam * B
        for bucket, gamma_b, offsets in zip(buckets, gammas, buckets_offsets):
            w = bucket.weights * bucket.mask
            z = margins(B, bucket, gamma_b, offsets)
            val = val + jnp.sum(w * loss.value(z, bucket.labels))
            c = w * loss.d1(z, bucket.labels)
            cg = _einsum("er,ek->erk", c, gamma_b)
            grad = grad + _einsum("erd,erk->dk", bucket.features, cg)
        return val, grad.reshape(-1)

    def hvp(vecB, vecV):
        B = vecB.reshape(d, k)
        V = vecV.reshape(d, k)
        out = lam * V
        for bucket, gamma_b, offsets in zip(buckets, gammas, buckets_offsets):
            w = bucket.weights * bucket.mask
            z = margins(B, bucket, gamma_b, offsets)
            dz = margins(V, bucket, gamma_b, jnp.zeros_like(offsets))
            c2 = w * loss.d2(z, bucket.labels) * dz
            cg = _einsum("er,ek->erk", c2, gamma_b)
            out = out + _einsum("erd,erk->dk", bucket.features, cg)
        return out.reshape(-1)

    return value_and_grad, hvp


_LATENT_OPTIMIZERS = (OptimizerType.LBFGS, OptimizerType.TRON)


@lru_cache(maxsize=64)
def _make_latent_solve(config: CoordinateConfig):
    """jitted solve for the shared projection B over any number of bucket
    designs. The objective treats vec(B) as the coefficient vector of a
    GLM on the VIRTUAL Kronecker features x (x) gamma — contracted lazily:

      margin_er = einsum('erd,dk,ek->er', X_b, B, gamma_b)
      grad_dk   = einsum('er,erd,ek->dk', c, X_b, gamma_b) + lambda B
      (Hv)_dk   = same contraction with c2 * dmargin(V)

    Bucket tensors arrive as positional args (pytrees of varying shapes),
    so one compilation serves a whole training run. TRON and L-BFGS (and
    OWL-QN under an L1 share) are what it implements; any other optimizer
    is refused here, at build time."""
    loss = loss_for_task(config.task)
    scfg = config.solver_config()
    use_owlqn = config.l1_ratio > 0.0
    if not use_owlqn and config.optimizer not in _LATENT_OPTIMIZERS:
        raise ValueError(
            f"the factored coordinate's projection solve implements "
            f"{[o.name for o in _LATENT_OPTIMIZERS]} (OWL-QN under an L1 "
            f"share), not {config.optimizer.name}"
        )
    use_tron = config.optimizer == OptimizerType.TRON
    l2 = config.reg_weight * (1.0 - config.l1_ratio)
    l1 = config.reg_weight * config.l1_ratio
    lam = l2

    def solve(b0, gammas, buckets_offsets, buckets):
        value_and_grad, hvp = _latent_objective(
            loss, lam, b0.shape, gammas, buckets_offsets, buckets
        )
        if use_owlqn:
            return minimize_owlqn(value_and_grad, b0.reshape(-1), l1, scfg)
        if use_tron:
            return minimize_tron(value_and_grad, hvp, b0.reshape(-1), scfg)
        return minimize_lbfgs(value_and_grad, b0.reshape(-1), scfg)

    return jax.jit(solve)


def _projection_tracker(result) -> tuple:
    """(iterations, CG iterations, design passes, reason, final grad norm)
    of one solve of the shared projection, on the device: the integer
    form of ``solvers.common.design_passes``."""
    iterations = result.iterations.astype(jnp.int32)
    if result.cg_iterations is not None:
        cg = result.cg_iterations.astype(jnp.int32)
        passes = iterations + 1 + cg
    else:
        cg = jnp.zeros((), jnp.int32)
        passes = (
            result.evals.astype(jnp.int32)
            if result.evals is not None
            else iterations + 1
        )
    return iterations, cg, passes, result.reason, final_grad_norm(result)


def _make_factored_update(
    re_config: CoordinateConfig,
    latent_config: CoordinateConfig,
    num_inner_iterations: int,
):
    """ONE jitted call for a whole factored update and its rescore: the
    eager ``update`` dispatches it, the fused coordinate-descent pass
    inlines it. Every bucket's ``entity_index`` is an argument (a leaf of
    ``fused_state``), so the program holds no lane map as a constant. Its
    device time splits by ``jax.named_scope``: ``factored/offsets``,
    ``/project``, ``/latent_solve``, ``/table_write``, ``/gamma_gather``,
    ``/projection_solve``, ``/score``. Both regularization weights are
    trace-time constants of the two inner solves."""
    return _make_factored_update_cached(
        dataclasses.replace(re_config, random_effect=None),
        dataclasses.replace(latent_config, random_effect=None),
        num_inner_iterations,
    )


@lru_cache(maxsize=64)
def _make_factored_update_cached(
    re_config, latent_config, num_inner_iterations
):
    re_solve = _make_solve(re_config, batched=True)
    latent_solve = _make_latent_solve(latent_config)
    reg_weight = re_config.reg_weight

    def scope(name):
        return jax.named_scope("factored/" + name)

    def update_all(
        params, full_offsets, entity_indices, offsets_maps, buckets,
        row_features, row_entities,
    ):
        gamma, b = params.gamma, params.projection
        # the residual offsets do not change inside an update: one compact
        # gather serves every inner iteration and both solves
        with scope("offsets"):
            bucket_offsets = gather_offsets_compact(
                full_offsets, offsets_maps, [bk.mask for bk in buckets]
            )
        lane_tapes = [[] for _ in buckets]
        projection_tape = []
        for _ in range(num_inner_iterations):
            # (a) latent-space per-entity solves, bucket by bucket
            for tape, eidx, bucket, offsets in zip(
                lane_tapes, entity_indices, buckets, bucket_offsets
            ):
                with scope("gamma_gather"):
                    g0 = jnp.take(gamma, eidx, axis=0, mode="clip")
                with scope("project"):
                    latent_feats = _einsum(
                        "erd,dk->erk", bucket.features, b
                    )
                with scope("latent_solve"):
                    result = re_solve(
                        g0,
                        jnp.full((eidx.shape[0],), reg_weight, gamma.dtype),
                        latent_feats,
                        bucket.labels,
                        offsets,
                        bucket.weights,
                        bucket.mask,
                    )
                tape.append(
                    (result.reason, result.iterations,
                     final_grad_norm(result))
                )
                with scope("table_write"):
                    gamma = gamma.at[eidx].set(result.w, mode="drop")
            # (b) shared projection over ALL buckets, einsum-contracted
            with scope("gamma_gather"):
                gammas = tuple(
                    jnp.take(gamma, eidx, axis=0, mode="clip")
                    for eidx in entity_indices
                )
            with scope("projection_solve"):
                latent_result = latent_solve(
                    b, gammas, tuple(bucket_offsets), tuple(buckets)
                )
                b = latent_result.w.reshape(b.shape)
                projection_tape.append(_projection_tracker(latent_result))
        new_params = FactoredParams(gamma=gamma, projection=b)
        with scope("score"):
            scores = _score_rows(new_params, row_features, row_entities)
        tracker = FactoredUpdateTracker(
            tuple(
                tuple(jnp.stack(field) for field in zip(*tape))
                for tape in lane_tapes
            ),
            *(jnp.stack(field) for field in zip(*projection_tape)),
        )
        return new_params, tracker, scores

    return jax.jit(update_all)


def _score_rows(params: FactoredParams, feats, ents):
    """Rows scored through the factors, x_i . (B gamma_e) as
    (x_i B) . gamma_e; -1 = unknown entity scores 0."""
    latent = _einsum("nd,dk->nk", feats, params.projection)
    safe = jnp.maximum(ents, 0)
    per_row = _einsum("nk,nk->n", latent, params.gamma[safe])
    return jnp.where(ents >= 0, per_row, 0.0)


class FactoredRandomEffectCoordinate:
    """Drop-in coordinate: update(params, partial_scores) / score(params)."""

    def __init__(
        self,
        design,  # RandomEffectDesign | BucketedRandomEffectDesign
        row_features: jax.Array,
        row_entities: jax.Array,
        full_offsets_base: jax.Array,
        re_config: CoordinateConfig,
        factored: FactoredConfig,
        seed: int = 0,
        initial_projection=None,  # (d, k): B0 in place of the seeded draw
    ):
        if isinstance(design, RandomEffectDesign):
            design = BucketedRandomEffectDesign(
                buckets=[design],
                entity_index=[
                    np.arange(design.num_entities, dtype=np.int32)
                ],
                num_entities=design.num_entities,
            )
        self.design = design
        self._offsets_maps = _design_offsets_maps(design)
        self._entity_indices = tuple(
            jnp.asarray(ei) for ei in design.entity_index
        )
        # static per-bucket masks of real (non-sharding-pad) lanes
        self._valid_lanes = [
            np.asarray(ei) < design.num_entities
            for ei in design.entity_index
        ]
        self.row_features = row_features
        self.row_entities = row_entities
        self.full_offsets_base = full_offsets_base
        self.config = re_config
        self.factored = factored
        self._seed = seed
        if initial_projection is not None:
            initial_projection = np.asarray(initial_projection)
            want = (design.dim, factored.latent_dim)
            if initial_projection.shape != want:
                raise ValueError(
                    f"initial_projection must be {want}, got "
                    f"{initial_projection.shape}"
                )
        self._initial_projection = initial_projection

        latent_cfg = factored.latent_factor_config or re_config
        self._latent_cfg = latent_cfg
        self._update_all = _make_factored_update(
            re_config,
            latent_cfg,
            factored.num_inner_iterations,
        )
        self._score = jax.jit(_score_rows)

    @property
    def num_entities(self) -> int:
        return self.design.num_entities

    @property
    def dim(self) -> int:
        """Original feature dimension of the underlying design."""
        return self.design.dim

    def initial_params(self) -> FactoredParams:
        """Gamma zeros; B the ``initial_projection`` the coordinate was
        given, else a Gaussian N(0, 1/d) like the reference's random
        projection init (``FactoredRandomEffectOptimizationProblem``)."""
        from photon_ml_tpu.models.training import solve_dtype

        d = self.design.dim
        k = self.factored.latent_dim
        b = self._initial_projection
        if b is None:
            rng = np.random.default_rng(self._seed)
            b = rng.normal(0.0, 1.0 / np.sqrt(d), size=(d, k))
        dtype = solve_dtype(self.design.buckets[0])
        return FactoredParams(
            gamma=jnp.zeros((self.num_entities, k), dtype),
            projection=jnp.asarray(b, dtype),
        )

    def update(
        self, params: FactoredParams, partial_scores: jax.Array, key=None
    ) -> Tuple[FactoredParams, "FactoredUpdateSummary"]:
        new_params, tracker, _ = self.update_step(
            params, partial_scores, key
        )
        return new_params, self.wrap_tracker(tracker)

    def score(self, params: FactoredParams) -> jax.Array:
        return self._score(params, self.row_features, self.row_entities)

    def update_step(
        self, params: FactoredParams, partial_scores: jax.Array, key=None
    ) -> Tuple[FactoredParams, FactoredUpdateTracker, jax.Array]:
        """Trace-safe update + rescore (the fused CD pass's unit): returns
        the RAW tracker (a pytree), which ``wrap_tracker`` turns into the
        lazy history summary."""
        return self._update_all(
            params,
            self.full_offsets_base + partial_scores,
            self._entity_indices,
            self._offsets_maps,
            tuple(self.design.buckets),
            self.row_features,
            self.row_entities,
        )

    def wrap_tracker(
        self, tracker: FactoredUpdateTracker
    ) -> "FactoredUpdateSummary":
        return FactoredUpdateSummary(
            tracker=tracker,
            valid_lanes=self._valid_lanes,
            entity_index=self.design.entity_index,
        )

    def fused_state(self):
        """See ``FixedEffectCoordinate.fused_state``."""
        return (
            tuple(self.design.buckets),
            self._entity_indices,
            self._offsets_maps,
            self.row_features,
            self.row_entities,
            self.full_offsets_base,
        )

    def with_fused_state(self, state):
        c = copy.copy(self)
        (
            buckets,
            c._entity_indices,
            c._offsets_maps,
            c.row_features,
            c.row_entities,
            c.full_offsets_base,
        ) = state
        c.design = dataclasses.replace(self.design, buckets=list(buckets))
        return c

    def reg_term(self, params: FactoredParams) -> jax.Array:
        """gamma is penalized under the RE config, B under the latent-factor
        config — the exact quantities the two inner solves minimize."""
        from photon_ml_tpu.game.descent import _config_reg_term

        return _config_reg_term(self.config, params.gamma) + _config_reg_term(
            self._latent_cfg, params.projection
        )

    def to_full_table(self, params: FactoredParams) -> jax.Array:
        """Materialize w_e = B gamma_e: (E, d) — the reference's
        ``RandomEffectModelInProjectedSpace.toRandomEffectModel``."""
        return params.gamma @ params.projection.T


class MatrixFactorizationModel:
    """Two latent tables; score(row, col) = rowFactors[row] . colFactors[col]
    with either side missing scoring 0 (``MatrixFactorizationModel.scala``)."""

    def __init__(self, row_factors: jax.Array, col_factors: jax.Array):
        if row_factors.shape[1] != col_factors.shape[1]:
            raise ValueError("row/col latent dims differ")
        self.row_factors = row_factors
        self.col_factors = col_factors

        @jax.jit
        def score(rows, cols, rf, cf):
            safe_r = jnp.maximum(rows, 0)
            safe_c = jnp.maximum(cols, 0)
            s = jnp.einsum("nk,nk->n", rf[safe_r], cf[safe_c])
            return jnp.where((rows >= 0) & (cols >= 0), s, 0.0)

        self._score = score

    @property
    def latent_dim(self) -> int:
        return self.row_factors.shape[1]

    def score(self, row_ids: jax.Array, col_ids: jax.Array) -> jax.Array:
        return self._score(
            row_ids, col_ids, self.row_factors, self.col_factors
        )

    @staticmethod
    def random(
        num_rows: int, num_cols: int, latent_dim: int, seed: int = 0,
        dtype=jnp.float32,
    ) -> "MatrixFactorizationModel":
        rng = np.random.default_rng(seed)
        return MatrixFactorizationModel(
            jnp.asarray(rng.normal(size=(num_rows, latent_dim)), dtype),
            jnp.asarray(rng.normal(size=(num_cols, latent_dim)), dtype),
        )
