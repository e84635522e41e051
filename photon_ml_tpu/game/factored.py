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
      directly by einsum, so phase (b) costs O(h d k) FLOPs and O(h d)
      memory, h the held rows, instead of the O(h d k) memory a
      materialized (h, d*k) matrix would need.

Accepts a :class:`BucketedRandomEffectDesign` (or a single global-cap
design, wrapped as one bucket): phase (a) runs per bucket, on lanes;
phase (b) has no lanes and reads a compact copy of the held rows
(:class:`HeldRowDesign`), built once at construction, not the padded
buckets, of which about half the slots hold no row, each held row's gamma
laid from its lane by runs. Inside an update gamma stays in the lanes:
the global table is read once, for the first warm starts, and written
once, at the end.

``MatrixFactorizationModel`` (``model/MatrixFactorizationModel.scala:30-134``)
is the inference-side pairing: two latent tables scored by gathered dot.
"""

from __future__ import annotations

import copy
import dataclasses
from functools import lru_cache, partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from photon_ml_tpu import obs
from photon_ml_tpu.core.types import _pytree_dataclass
from photon_ml_tpu.game.coordinates import (
    CoordinateConfig,
    _design_offsets_maps,
    _lane_of_entity,
    _make_solve,
)
from photon_ml_tpu.game.data import (
    BucketedRandomEffectDesign,
    RandomEffectDesign,
    fill_offsets,
    gather_held_offsets,
    held_slot_values,
    spread_lanes,
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
    held_rows: int  # held slots (mask > 0); a B pass reads H >= these
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
                        "rows": self.held_rows,
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


HELD_ROWS_ALIGN = 1024


@_pytree_dataclass
class HeldRowDesign:
    """The held rows of a bucketed design, one entry an entry of
    ``offsets_gather_maps``' ``perm`` (a held slot of an ordered design),
    in its order (so that the offsets gather's vector lines up with it as
    it is), zero-padded to a multiple of ``HELD_ROWS_ALIGN`` entries. What
    the shared projection's GLM reads.

    The features lie as (d, H), the entries minor: lane-dense on the chip,
    where (H, d) of d 64 lies padded to 128 lanes, and where the (n, R, k)
    intermediates of an (n, R, d) form were laid out with R or k minor, 16
    times their size (PERF.md section 6, PR 37).

    ``weights`` is the slot's weight times its mask (the cap's
    weight-preserving rescale kept; 0 on a wasted ``perm`` entry of an
    unordered design and on padding). An entry's gamma is its lane's,
    laid by ``game.data.spread_lanes``."""

    features: jax.Array  # (d, H)
    weights: jax.Array  # (H,)
    labels: jax.Array  # (H,)


def _held_vector(a, held):
    """A vector in ``perm``'s order, zero-padded to the held rows' H."""
    return jnp.pad(a, (0, held.weights.shape[0] - a.shape[0]))


@partial(jax.jit, static_argnums=2)
def _held_features(row_features, perm, size):
    """(d, H): the rows ``perm`` names, zero-padded to ``size``: a bucket's
    slot holds its row's features. Built ``HELD_ROWS_ALIGN`` entries at a
    time, so that the only temp is ``row_features`` made row-major for the
    gather (2.16 GB for the music cell's, compiled for v5e: one gather and
    transpose of them all took 4.10 GB, the (H, d) rows laid out padded to
    128 lanes; PERF.md section 6, PR 37)."""
    chunk = HELD_ROWS_ALIGN
    # padding names no row: the fill gives it zeros
    perm = jnp.pad(perm, (0, size - perm.shape[0]),
                   constant_values=row_features.shape[0])

    def put(i, out):
        at = lax.dynamic_slice_in_dim(perm, i * chunk, chunk)
        rows = jnp.take(row_features, at, axis=0, mode="fill", fill_value=0)
        return lax.dynamic_update_slice_in_dim(out, rows.T, i * chunk, axis=1)

    out = jnp.zeros((row_features.shape[1], size), row_features.dtype)
    return lax.fori_loop(0, size // chunk, put, out)


def _build_held_rows(design, perm, row_features):
    """The :class:`HeldRowDesign` of the design, once, at construction:
    the features gathered from ``row_features`` through ``perm`` (the
    offsets maps', on the device), the (H,) vectors read on the host at
    the same slots; and the count of held slots (mask > 0)."""
    from photon_ml_tpu.parallel.multihost import fetch_replicated

    def host(a):
        return np.asarray(fetch_replicated(a))

    buckets = design.buckets
    masks = [host(b.mask) for b in buckets]
    weights = held_slot_values(
        [host(b.weights) * m for b, m in zip(buckets, masks)], masks)
    labels = held_slot_values([host(b.labels) for b in buckets], masks)
    size = -(-weights.size // HELD_ROWS_ALIGN) * HELD_ROWS_ALIGN

    def pad(v, dtype):
        return jnp.asarray(np.pad(v, (0, size - v.size)).astype(dtype))

    return HeldRowDesign(
        features=_held_features(
            row_features.astype(buckets[0].features.dtype), perm, size),
        weights=pad(weights, buckets[0].weights.dtype),
        labels=pad(labels, buckets[0].labels.dtype),
    ), int(sum(np.count_nonzero(m > 0) for m in masks))


def _einsum(spec, a, b):
    """Every contraction of the design with B, V or gamma. At matmul
    precision HIGHEST: the k-wide products go to the MXU, whose default
    rounds float32 operands to bfloat16 (the objective the chip reported
    read 7e-6 to 1.7e-5 off the float32 reference's before this, 1e-7
    for the plain coordinates: PERF.md section 6, PR 36). A float32
    configuration stays float32."""
    return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST)


def _latent_objective(loss, lam, shape, held, gamma_rows, offsets):
    """``(value_and_grad(vecB), hvp(vecB, vecV))`` of the shared
    projection's GLM over the held rows (a :class:`HeldRowDesign`, each
    row's gamma ``gamma_rows`` (H, k) and residual offset ``offsets``
    (H,)), vec(B) the (d * k,) coefficient vector of the
    virtual Kronecker features x (x) gamma, which are never built: every
    term contracts the rows' features, their gammas and B (or V)
    directly."""
    d, k = shape

    def margins(B, offsets):
        xb = _einsum("dh,dk->hk", held.features, B)
        return _einsum("hk,hk->h", xb, gamma_rows) + offsets

    def value_and_grad(vecB):
        B = vecB.reshape(d, k)
        z = margins(B, offsets)
        val = 0.5 * lam * jnp.vdot(B, B) + jnp.sum(
            held.weights * loss.value(z, held.labels))
        c = held.weights * loss.d1(z, held.labels)
        cg = _einsum("h,hk->hk", c, gamma_rows)
        grad = lam * B + _einsum("dh,hk->dk", held.features, cg)
        return val, grad.reshape(-1)

    def hvp(vecB, vecV):
        B = vecB.reshape(d, k)
        V = vecV.reshape(d, k)
        z = margins(B, offsets)
        dz = margins(V, jnp.zeros_like(offsets))
        c2 = held.weights * loss.d2(z, held.labels) * dz
        cg = _einsum("h,hk->hk", c2, gamma_rows)
        out = lam * V + _einsum("dh,hk->dk", held.features, cg)
        return out.reshape(-1)

    return value_and_grad, hvp


_LATENT_OPTIMIZERS = (OptimizerType.LBFGS, OptimizerType.TRON)


@lru_cache(maxsize=64)
def _make_latent_solve(config: CoordinateConfig):
    """jitted solve for the shared projection B over the held rows. The
    objective treats vec(B) as the coefficient vector of a GLM on the
    VIRTUAL Kronecker features x (x) gamma — contracted lazily:

      margin_h = einsum('dh,dk,hk->h', X, B, gamma_rows)
      grad_dk  = einsum('h,dh,hk->dk', c, X, gamma_rows) + lambda B
      (Hv)_dk  = same contraction with c2 * dmargin(V)

    The held rows arrive as arguments, so one compilation serves a whole
    training run. TRON and L-BFGS (and OWL-QN under an L1 share) are what
    it implements; any other optimizer is refused here, at build time."""
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

    def solve(b0, gamma_rows, offsets, held):
        value_and_grad, hvp = _latent_objective(
            loss, lam, b0.shape, held, gamma_rows, offsets
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
    inlines it. Every bucket's ``entity_index``, the entity -> lane map and
    the held rows are arguments (leaves of ``fused_state``), so the program
    holds no lane map and no copy of the design as a constant. Its device
    time splits by ``jax.named_scope``: ``factored/offsets``,
    ``/gamma_gather`` (the first warm starts, from the table),
    ``/project``, ``/latent_solve``, ``/gamma_spread`` (the lanes'
    solutions laid over the held rows by runs, ``game.data.spread_lanes``),
    ``/projection_solve``, ``/table_write`` (once, after the last inner
    iteration: a gather through the entity -> lane map), ``/score``.
    Both regularization weights are trace-time constants of the two inner
    solves."""
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
        params, full_offsets, entity_indices, lane_of_entity, offsets_maps,
        buckets, held, row_features, row_entities,
    ):
        gamma, b = params.gamma, params.projection
        perm, starts = offsets_maps
        masks = [bk.mask for bk in buckets]
        # runs while an update is traced, never in a pass
        obs.registry().inc(
            "game.factored.gamma_spread_runs",
            num_inner_iterations * sum(s.shape[0] for s in starts),
        )
        obs.registry().inc("game.factored.table_write.inverse_gather")
        # the residual offsets do not change inside an update: one compact
        # gather, an index a held row, serves every inner iteration and both
        # solves, the B solve's as it is and the lanes' through the fills
        with scope("offsets"):
            gathered = gather_held_offsets(full_offsets, perm)
            bucket_offsets = fill_offsets(gathered, starts, masks)
            held_offsets = _held_vector(gathered, held)
        # inside an update gamma lives in the lanes: the table is read once,
        # for the first warm starts, and written once, at the end; an entity
        # sits in at most one lane, so a lane's last solution is its row
        with scope("gamma_gather"):
            solved = [
                jnp.take(gamma, eidx, axis=0, mode="clip")
                for eidx in entity_indices
            ]
        lane_tapes = [[] for _ in buckets]
        projection_tape = []
        for _ in range(num_inner_iterations):
            # (a) latent-space per-entity solves, bucket by bucket
            for i, (tape, bucket, offsets) in enumerate(
                zip(lane_tapes, buckets, bucket_offsets)
            ):
                with scope("project"):
                    latent_feats = _einsum(
                        "erd,dk->erk", bucket.features, b
                    )
                with scope("latent_solve"):
                    result = re_solve(
                        solved[i],
                        jnp.full(
                            (solved[i].shape[0],), reg_weight, gamma.dtype
                        ),
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
                solved[i] = result.w
            # (b) shared projection over the held rows, einsum-contracted;
            # a held row's gamma is its lane's, laid by runs
            with scope("gamma_spread"):
                gamma_rows = spread_lanes(
                    solved, starts, masks, held.weights.shape[0]
                )
            with scope("projection_solve"):
                latent_result = latent_solve(
                    b, gamma_rows, held_offsets, held
                )
                b = latent_result.w.reshape(b.shape)
                projection_tape.append(_projection_tracker(latent_result))
        # the lanes are a fixed permutation of the table rows they hold: one
        # gather through the entity -> lane map, where a scatter a bucket
        # cost about ten times as much a row (PERF.md section 6)
        with scope("table_write"):
            written = jnp.take(
                jnp.concatenate(solved), jnp.maximum(lane_of_entity, 0),
                axis=0, mode="clip",
            )
            gamma = jnp.where(lane_of_entity[:, None] >= 0, written, gamma)
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
        self._lane_of_entity = jnp.asarray(
            _lane_of_entity(design.entity_index, design.num_entities)
        )
        # the B solve's rows, and how many slots the mask holds among them
        # (game.factored.projection_rows counts those)
        self._held, self._held_rows = _build_held_rows(
            design, self._offsets_maps[0], row_features
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
            self._lane_of_entity,
            self._offsets_maps,
            tuple(self.design.buckets),
            self._held,
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
            held_rows=self._held_rows,
        )

    def fused_state(self):
        """See ``FixedEffectCoordinate.fused_state``."""
        return (
            tuple(self.design.buckets),
            self._entity_indices,
            self._lane_of_entity,
            self._offsets_maps,
            self._held,
            self.row_features,
            self.row_entities,
            self.full_offsets_base,
        )

    def with_fused_state(self, state):
        c = copy.copy(self)
        (
            buckets,
            c._entity_indices,
            c._lane_of_entity,
            c._offsets_maps,
            c._held,
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
