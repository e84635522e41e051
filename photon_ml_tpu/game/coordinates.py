"""GAME coordinates: fixed-effect and random-effect update/score units.

Rebuild of ``algorithm/Coordinate.scala:28-55`` and its concrete types.
A coordinate owns its (device-resident) training design and exposes:

  update(params, partial_scores, key) -> (params', SolverResult)
      solve the coordinate's subproblem with the OTHER coordinates' scores
      added to the offsets — the residual trick of
      ``algorithm/Coordinate.scala:45-48`` — warm-starting from the current
      parameters (``FixedEffectCoordinate.scala:69-71``)
  score(params) -> (n,) margins for the coordinate's own rows

FixedEffectCoordinate (``algorithm/FixedEffectCoordinate.scala:33-179``):
one global GLM solve; under a mesh the batch is 'data'-sharded and the
solve runs SPMD. Optional down-sampling is a weight transform (static
shapes; ``sampler/*DownSampler.scala`` semantics).

RandomEffectCoordinate (``algorithm/RandomEffectCoordinate.scala:36-214``):
ONE vmapped solver call over the padded (entities, rows, dim) design — the
reference's millions of per-entity in-executor solves. Per-entity
convergence reasons come back as an (E,) int array for the tracker
histogram (``RandomEffectOptimizationTracker.scala:33-110``).
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

import numpy as np

from photon_ml_tpu import obs
from photon_ml_tpu.core.tasks import TaskType
from photon_ml_tpu.core.types import LabeledBatch
from photon_ml_tpu.game.data import (
    COMPACT_BLOCK,
    BucketedRandomEffectDesign,
    RandomEffectDesign,
    gather_offsets_compact,
    offsets_gather_maps,
)
from photon_ml_tpu.models.training import OptimizerType
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.solvers import (
    SolverConfig,
    minimize_lbfgs,
    minimize_newton,
    minimize_owlqn,
    minimize_tron,
)
from photon_ml_tpu.solvers.newton import solves_elementwise


@dataclasses.dataclass(frozen=True)
class CoordinateConfig:
    """Per-coordinate optimization knobs — the typed analog of the
    reference's GLMOptimizationConfiguration mini-DSL
    ("maxIter,tol,lambda,downSampleRate,optimizer,regType",
    ``optimization/game/GLMOptimizationConfiguration.scala:32-80``).
    Defaults per ``GLMOptimizationConfiguration.scala:33-38``."""

    shard: str
    task: TaskType = TaskType.LOGISTIC_REGRESSION
    optimizer: OptimizerType = OptimizerType.TRON
    reg_weight: float = 50.0
    l1_ratio: float = 0.0  # >0 selects OWL-QN (elastic-net alpha)
    max_iters: int = 20
    tolerance: float = 1e-5
    # fixed-effect only: None = no down-sampling; else keep rate in (0,1)
    down_sampling_rate: Optional[float] = None
    # random-effect only
    random_effect: Optional[str] = None
    active_cap: Optional[int] = None
    # per-iteration solver tapes (values/grad norms/radius/step — the
    # obs/convergence.py decode surface). Off by default: vmapped
    # per-entity solves would carry (entities, max_iters+1) tracker
    # state; the fleet summaries (reason/iterations/final grad norm)
    # don't need it.
    track_states: bool = False
    # TRON only: the inner CG's iteration budget an outer iteration
    # (None: the solver's, 20). Under a tolerance of zero the budget is the
    # rule (``solvers.tron``): every CG then runs exactly this many
    # Hessian-vector passes unless it reaches the trust-region boundary,
    # so a budgeted solve states it like ``max_iters``.
    tron_max_cg: Optional[int] = None

    def solver_config(self) -> SolverConfig:
        budget = (
            {} if self.tron_max_cg is None
            else {"tron_max_cg": self.tron_max_cg}
        )
        return SolverConfig(
            max_iters=self.max_iters,
            tolerance=self.tolerance,
            track_states=self.track_states,
            **budget,
        )


def _make_solve(config: CoordinateConfig, batched: bool):
    """jitted solve(w0, reg_weight, features, labels, offsets, weights,
    mask) for one subproblem; vmapped over the leading axis when `batched`
    — reg_weight is a TRACED scalar (per-entity in the batched case, the
    honest analog of ``RandomEffectOptimizationProblem.scala:41-110``'s
    per-entity objective functions). The cache key zeroes reg_weight so a
    lambda grid sweep reuses ONE compilation."""
    return _make_solve_cached(
        dataclasses.replace(config, reg_weight=0.0), batched
    )


@lru_cache(maxsize=128)
def _make_solve_cached(config: CoordinateConfig, batched: bool):
    loss = loss_for_task(config.task)
    scfg = config.solver_config()
    use_owlqn = config.l1_ratio > 0.0
    use_tron = config.optimizer == OptimizerType.TRON
    use_newton = config.optimizer == OptimizerType.NEWTON
    if (use_tron or use_newton) and not loss.twice_differentiable:
        # the GLM driver's validate() never runs for GAME coordinates, so
        # enforce the second-order requirement here at build time
        raise ValueError(
            f"{config.task} is first-order only; {config.optimizer.name} "
            "needs a twice-differentiable loss (use LBFGS)"
        )

    def entity_minor(dim: int) -> bool:
        """Whether the small-d algebra of a solve at this dimension is
        element by element (entities on the lanes under ``vmap``): both
        the Hessian's choice and what ``game.solve_layout`` books."""
        return use_newton and solves_elementwise(dim)

    def solve_one(w0, reg_weight, features, labels, offsets, weights, mask):
        l1 = reg_weight * config.l1_ratio
        l2 = reg_weight * (1.0 - config.l1_ratio)
        batch = LabeledBatch(features, labels, offsets, weights, mask)
        obj = GLMObjective(loss=loss, l2_weight=l2)
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
            # one Hessian form a dimension, batched or not, so that a
            # bucket's lane and the same entity solved alone sum alike
            if entity_minor(features.shape[-1]):
                hess = lambda w: obj.hessian_row_sum(w, batch)
            else:
                hess = lambda w: obj.hessian_full(w, batch)
            return minimize_newton(vg, hess, w0, scfg)
        return minimize_lbfgs(vg, w0, scfg)

    if not batched:
        return jax.jit(solve_one)

    def solve_bucket(w0, reg_weight, features, labels, offsets, weights, mask):
        # runs while a bucket's solve is traced, never in a pass: says
        # which form the bucket took and how long its trace was
        entities, depth, dim = features.shape
        layout = "entity_minor" if entity_minor(dim) else "block_minor"
        obs.registry().inc("game.solve_layout." + layout)
        with obs.span(
            "game.solve_layout", cat="solver", layout=layout,
            optimizer=config.optimizer.name, dim=dim, depth=depth,
            entities=entities,
        ):
            return jax.vmap(solve_one)(
                w0, reg_weight, features, labels, offsets, weights, mask
            )

    return jax.jit(solve_bucket)


def _downsample_budget(
    labels: np.ndarray, mask: np.ndarray, rate: float, binary: bool
) -> int:
    """Static row budget for the gathered down-sampled batch: expected
    keep count + 6 standard deviations of the Bernoulli draw, so overflow
    (kept rows beyond the budget, which are dropped) is vanishingly rare."""
    real = mask > 0
    n = int(real.sum())
    if binary:
        pos = int(((labels > 0) & real).sum())
        neg = n - pos
        mean = pos + rate * neg
        var = rate * (1.0 - rate) * neg
    else:
        mean = rate * n
        var = rate * (1.0 - rate) * n
    return min(n, int(np.ceil(mean + 6.0 * np.sqrt(max(var, 1.0)))) + 1)


def _make_gathered_solve(config: CoordinateConfig, budget: int):
    """jitted solve over the GATHERED down-sampled batch: rows with
    positive post-sampling weight are packed (stable order) into a
    (budget, d) batch; dropped and overflow rows carry weight 0. Cache
    key zeroes reg_weight (traced) like _make_solve."""
    return _make_gathered_solve_cached(
        dataclasses.replace(config, reg_weight=0.0), budget
    )


@lru_cache(maxsize=64)
def _make_gathered_solve_cached(config: CoordinateConfig, budget: int):
    solve = _make_solve(config, batched=False)

    @jax.jit
    def gather_solve(w, reg_weight, features, labels, offsets, weights, mask):
        kept = weights > 0.0
        # stable partition: kept-row indices first
        order = jnp.argsort(~kept)  # False (kept) sorts before True
        idx = order[:budget]
        valid = kept[idx]
        sub_mask = jnp.where(valid, mask[idx], 0.0)
        with jax.named_scope("fe_solve"):
            result = solve(
                w,
                reg_weight,
                features[idx],
                labels[idx],
                offsets[idx],
                jnp.where(valid, weights[idx], 0.0),
                sub_mask,
            )
        # rescore the FULL batch in the same dispatch
        return result, features @ result.w

    return gather_solve


def _make_fixed_update_and_score(config: CoordinateConfig):
    """solve + full-batch rescore in ONE dispatch (cache key zeroes the
    traced reg_weight like _make_solve)."""
    return _make_fixed_update_and_score_cached(
        dataclasses.replace(config, reg_weight=0.0)
    )


@lru_cache(maxsize=128)
def _make_fixed_update_and_score_cached(config: CoordinateConfig):
    solve = _make_solve(config, batched=False)

    @jax.jit
    def run(w, reg_weight, features, labels, offsets, weights, mask):
        with jax.named_scope("fe_solve"):
            result = solve(
                w, reg_weight, features, labels, offsets, weights, mask
            )
        return result, features @ result.w

    return run


def _make_fixed_update_and_score_permuted(config: CoordinateConfig):
    return _make_fixed_update_and_score_permuted_cached(
        dataclasses.replace(config, reg_weight=0.0)
    )


@lru_cache(maxsize=128)
def _make_fixed_update_and_score_permuted_cached(config: CoordinateConfig):
    """Hybrid-representation variant: the batch lives in the hybrid's
    stored (bucketed) row order, while partial scores arrive — and
    rescores must leave — in the GLOBAL row order the descent loop sums
    over. Both permutation gathers ride inside the single dispatch."""
    solve = _make_solve(config, batched=False)

    @jax.jit
    def run(w, reg_weight, features, labels, offsets_base, partial_scores,
            weights, mask, perm, inv):
        offsets = offsets_base + partial_scores[perm]
        with jax.named_scope("fe_solve"):
            result = solve(
                w, reg_weight, features, labels, offsets, weights, mask
            )
        return result, (features @ result.w)[inv]

    return run


class FixedEffectCoordinate:
    """Global GLM coordinate. Owns a device LabeledBatch (shard view).

    ``hot_columns`` (with a padded-ELL batch) re-represents the shard as
    dense-hot + bucketed-cold (``ops.sparse.to_hybrid``) INSIDE the
    coordinate: the hybrid's row permutation is private here — incoming
    partial scores and outgoing rescores are bridged by two in-dispatch
    gathers, so the descent loop keeps its global row order."""

    @staticmethod
    def hybridize_batch(batch: LabeledBatch, hot_columns: int):
        """(permuted hybrid batch, row_perm, inv_perm) — the host-side
        re-pack, exposed so grid sweeps can build it ONCE per coordinate
        (it depends on data + hot_columns, never on reg weight)."""
        from photon_ml_tpu.ops.sparse import is_sparse, to_hybrid

        if not is_sparse(batch.features):
            raise ValueError(
                "hot_columns requires a padded-ELL (sparse) shard"
            )
        hf = to_hybrid(batch.features, hot_columns=hot_columns)
        perm = np.asarray(hf.row_perm)
        batch = dataclasses.replace(
            batch,
            features=hf,
            labels=batch.labels[perm],
            offsets=batch.offsets[perm],
            weights=batch.weights[perm],
            mask=batch.mask[perm],
        )
        return batch, jnp.asarray(perm), jnp.asarray(np.argsort(perm))

    def __init__(
        self,
        batch: LabeledBatch,
        config: CoordinateConfig,
        hot_columns: int = 0,
        hybrid_pack=None,
    ):
        if config.random_effect is not None:
            raise ValueError("config names a random effect; wrong coordinate")
        self._row_perm = None
        self._inv_perm = None
        if hybrid_pack is not None:
            batch, self._row_perm, self._inv_perm = hybrid_pack
        elif hot_columns:
            batch, self._row_perm, self._inv_perm = self.hybridize_batch(
                batch, hot_columns
            )
        self.batch = batch
        self.config = config
        # the effective reg weight: a Python float normally, or a traced
        # scalar when a grid sweep threads per-combo weights through the
        # fused state (``descent.run_grid``). update_step reads THIS, so
        # one compilation serves every lambda.
        self._reg_weight = config.reg_weight
        self._update_and_score = (
            _make_fixed_update_and_score_permuted(config)
            if self._row_perm is not None
            else _make_fixed_update_and_score(config)
        )
        from photon_ml_tpu.ops.sparse import matvec as _matvec

        self._score = (
            jax.jit(lambda w, feats: _matvec(feats, w)[self._inv_perm])
            if self._row_perm is not None
            else jax.jit(lambda w, feats: feats @ w)
        )
        self._downsample = (
            jax.jit(_binary_downsample_weights, static_argnums=(3,))
            if config.down_sampling_rate is not None
            and config.task.is_classifier
            else jax.jit(_uniform_downsample_weights, static_argnums=(3,))
            if config.down_sampling_rate is not None
            else None
        )
        # Down-sampling must SAVE work, not just zero weights (the
        # reference's down-sampler cuts the fixed-effect solve cost —
        # ``sampler/BinaryClassificationDownSampler.scala:36-66``): kept
        # rows are gathered into a smaller STATIC batch sized for the
        # expected keep count plus a 6-sigma margin, so every pass reuses
        # one compilation. The dense path only — gathering padded-ELL rows
        # is the sparse container's own re-pack problem.
        from photon_ml_tpu.ops.sparse import is_structured

        self._ds_budget = None
        if self._downsample is not None and not is_structured(
            batch.features
        ):
            self._ds_budget = _downsample_budget(
                np.asarray(batch.labels),
                np.asarray(batch.mask),
                config.down_sampling_rate,
                binary=config.task.is_classifier,
            )
            self._gather_solve = _make_gathered_solve(
                config, self._ds_budget
            )

    @property
    def dim(self) -> int:
        return self.batch.num_features

    def initial_params(self) -> jax.Array:
        from photon_ml_tpu.models.training import solve_dtype

        return jnp.zeros((self.dim,), solve_dtype(self.batch))

    def update(
        self, w: jax.Array, partial_scores: jax.Array, key=None
    ) -> Tuple[jax.Array, object]:
        """Compatibility form of :meth:`update_and_score` (the descent loop
        uses the fused form; this one computes-and-drops the rescore)."""
        params, result, _ = self.update_and_score(w, partial_scores, key)
        return params, result

    def update_and_score(
        self, w: jax.Array, partial_scores: jax.Array, key=None
    ) -> Tuple[jax.Array, object, jax.Array]:
        """update + full-batch rescore, fused into one dispatch (every
        dispatch costs a host->device launch and, when its result is
        read, a fetch; the coordinate-descent loop uses this form)."""
        return self.update_step(w, partial_scores, key)

    def wrap_tracker(self, tracker):
        """Fused-pass hook: raw tracker pytree -> history object (identity
        here; SolverResult is already what materialize() reads)."""
        return tracker

    def fused_state(self):
        """Device-resident arrays update_step reads, as an explicit
        pytree. The fused whole-pass jit threads these as ARGUMENTS:
        closed-over concrete arrays are not hoisted by tracing (they are
        not tracers) and lower to HLO literals — the compiled program
        would carry the whole dataset (observed: multi-hundred-MB
        modules). The reg weight rides along so grid sweeps can vmap a
        combo axis over it."""
        return self.fused_state_for_reg(self._reg_weight)

    def fused_state_for_reg(self, reg_weight):
        """The fused state with a specific reg weight — the grid-sweep
        axis (``descent.run_grid`` stacks these across combos). The
        scalar keeps the DEFAULT float width (f64 under x64) so fused
        modes see the exact same lambda the plain loop casts from the
        config float — a forced f32 here would silently perturb
        non-representable lambdas (e.g. 0.1) in float64 runs.

        SAME-OBJECT CONTRACT: every leaf that does not depend on
        ``reg_weight`` must be returned as the IDENTICAL array object
        on every call (here: ``self.batch``/perm attributes, never
        copies). ``run_grid`` discovers broadcastable leaves by object
        identity across two probe calls; a fresh-but-equal object is
        stacked once per combo instead of broadcast — n_combo x the
        leaf's HBM (run_grid warns when a large leaf trips this)."""
        return (
            self.batch,
            self._row_perm,
            self._inv_perm,
            jnp.asarray(reg_weight, jnp.result_type(float)),
        )

    def with_fused_state(self, state):
        import copy

        c = copy.copy(self)
        c.batch, c._row_perm, c._inv_perm, c._reg_weight = state
        return c

    def reg_term(self, params: jax.Array) -> jax.Array:
        """Penalty under the EFFECTIVE reg weight (grid sweeps thread it
        per combo; identical to the config formula otherwise)."""
        lam = jnp.asarray(self._reg_weight, params.dtype)
        l2 = lam * (1.0 - self.config.l1_ratio)
        l1 = lam * self.config.l1_ratio
        return 0.5 * l2 * jnp.vdot(params, params) + l1 * jnp.sum(
            jnp.abs(params)
        )

    def update_step(
        self, w: jax.Array, partial_scores: jax.Array, key=None
    ) -> Tuple[jax.Array, object, jax.Array]:
        """TRACE-SAFE update + rescore: pure function of device values
        (jit-inlinable), returning only pytrees — the unit the fused
        whole-pass CD dispatch composes (``descent.py``)."""
        weights = self.batch.weights
        if self._downsample is not None:
            if key is None:
                raise ValueError(
                    "down-sampling needs a PRNG key per update; a fixed "
                    "default would drop the SAME rows every pass"
                )
            weights = self._downsample(
                key,
                weights * self.batch.mask,
                self.batch.labels,
                self.config.down_sampling_rate,
            )
            if self._ds_budget is not None:
                result, scores = self._gather_solve(
                    w,
                    jnp.asarray(self._reg_weight, w.dtype),
                    self.batch.features,
                    self.batch.labels,
                    self.batch.offsets + partial_scores,
                    weights,
                    self.batch.mask,
                )
                return result.w, result, scores
        if self._row_perm is not None:
            # hybrid batch: partial scores arrive in global row order, the
            # batch lives in stored order — the permutation gathers ride
            # inside the dispatch
            result, scores = self._update_and_score(
                w,
                jnp.asarray(self._reg_weight, w.dtype),
                self.batch.features,
                self.batch.labels,
                self.batch.offsets,
                partial_scores,
                weights,
                self.batch.mask,
                self._row_perm,
                self._inv_perm,
            )
            return result.w, result, scores
        result, scores = self._update_and_score(
            w,
            jnp.asarray(self._reg_weight, w.dtype),
            self.batch.features,
            self.batch.labels,
            self.batch.offsets + partial_scores,
            weights,
            self.batch.mask,
        )
        return result.w, result, scores

    def score(self, w: jax.Array) -> jax.Array:
        """Broadcast-dot scoring (``FixedEffectCoordinate.scala:171-178``),
        WITHOUT the dataset offset (scores must sum across coordinates)."""
        return self._score(w, self.batch.features)


@dataclasses.dataclass
class RandomEffectUpdateSummary:
    """Per-entity tracker view of one (possibly multi-bucket) update —
    the fields CoordinateDescent's histogram consumes, concatenated over
    buckets with sharding-padding lanes removed
    (``RandomEffectOptimizationTracker.scala:33-110``).

    LAZY: holds device arrays until `.reason` / `.iterations` /
    `.grad_norms` is first read, so the coordinate-descent loop can
    enqueue the next update without a device->host sync per pass (the
    reference pays a collect per tracker read; we defer it to history
    materialization)."""

    # [(reason_dev (E_b,), iterations_dev (E_b,), grad_norm_dev (E_b,),
    #   valid mask, entity_index (E_b,)), ...]
    pending: list

    def _materialize(self):
        if self.pending is not None:
            self._reason = np.concatenate(
                [np.asarray(r)[v] for r, _, _, v, _ in self.pending]
            )
            self._iterations = np.concatenate(
                [np.asarray(i)[v] for _, i, _, v, _ in self.pending]
            )
            self._grad_norms = np.concatenate(
                [np.asarray(g)[v] for _, _, g, v, _ in self.pending]
            )
            self._entity_ids = np.concatenate(
                [np.asarray(e)[v] for _, _, _, v, e in self.pending]
            )
            self.pending = None

    @property
    def reason(self) -> np.ndarray:  # (E_active,) int32
        self._materialize()
        return self._reason

    @property
    def iterations(self) -> np.ndarray:  # (E_active,) int32
        self._materialize()
        return self._iterations

    @property
    def grad_norms(self) -> np.ndarray:  # (E_active,) final ||grad||
        self._materialize()
        return self._grad_norms

    @property
    def entity_ids(self) -> np.ndarray:  # (E_active,) table rows
        self._materialize()
        return self._entity_ids


def _make_multi_bucket_update(config: CoordinateConfig):
    """ONE jitted call updating ALL buckets of a random effect: route the
    residual offsets into every bucket's padded slots (one gather of the
    held rows through the coordinate's static maps,
    ``game.data.gather_offsets_compact``); per bucket, gather warm starts
    from the global table and
    solve the bucket's entities in one vmapped call; then write every
    solution into the table at once, by a gather through the static
    entity -> lane map (``_lane_of_entity``): a table row reads its lane,
    or keeps its value where no lane holds it. Sentinel indices
    (== num_entities) clip on gather and no table row points at them.

    Fusing the whole multi-bucket pass into a single dispatch saves
    per-dispatch launch latency (a 4-bucket update would otherwise cost
    4+ launches per CD pass). Cache
    key zeroes reg_weight (traced per entity) so a lambda grid reuses one
    compile."""
    return _make_multi_bucket_update_cached(
        dataclasses.replace(config, reg_weight=0.0)
    )


@lru_cache(maxsize=128)
def _make_multi_bucket_update_cached(config: CoordinateConfig):
    return jax.jit(_multi_bucket_update_body(config))


def _multi_bucket_update_body(config: CoordinateConfig):
    """The un-jitted update of every bucket of one random effect: THE
    body :class:`RandomEffectCoordinate` runs over the whole table and
    :class:`EntityShardedRandomEffectCoordinate` runs a shard, under its
    ``shard_map``, over the shard's block of it (every index then
    shard-local). One definition, so a change to either path is a change
    to both."""
    solve = _make_solve(config, batched=True)
    from photon_ml_tpu.solvers.common import final_grad_norm

    def update_all(
        table, reg_weights, full_offsets, entity_indices, lane_of_entity,
        offsets_maps, buckets, row_features, row_entities,
    ):
        # runs while a coordinate's update is traced, never in a pass
        obs.registry().inc("game.table_write.inverse_gather")
        obs.registry().inc("game.offsets_gather.compact")
        # the residual offsets of every bucket: one index a held row and
        # one a slot, where a gather a bucket paid one a PADDED slot
        # (PERF.md section 6, PR 35)
        with jax.named_scope("re_gather"), jax.named_scope("offsets"):
            bucket_offsets = gather_offsets_compact(
                full_offsets, offsets_maps, [b.mask for b in buckets]
            )
        solved = []
        trackers = []
        for eidx, bucket, offsets in zip(
            entity_indices, buckets, bucket_offsets
        ):
            # every bucket warm-starts from the table as it came in: an
            # entity sits in at most one lane, so no bucket reads a row
            # that another wrote
            with jax.named_scope("re_gather"):
                with jax.named_scope("warm_start"):
                    w0 = jnp.take(table, eidx, axis=0, mode="clip")
                with jax.named_scope("reg_weight"):
                    lam = jnp.take(reg_weights, eidx, mode="clip")
            with jax.named_scope("re_newton_solve"):
                result = solve(
                    w0, lam, bucket.features, bucket.labels, offsets,
                    bucket.weights, bucket.mask,
                )
            solved.append(result.w)
            # final per-entity gradient norm rides the tracker tuple
            # (valid with tracking on or off), feeding the fleet-level
            # convergence summaries' worst-k signal for free — it is
            # computed in-program, no extra dispatch
            trackers.append(
                (result.reason, result.iterations, final_grad_norm(result))
            )
        # the lanes are a fixed permutation of the table rows they hold,
        # so the write is read from the table's side: one gather of
        # num_entities rows where a scatter a bucket cost eleven times a
        # row (PERF.md section 6, PR 33)
        with jax.named_scope("re_scatter"):
            lanes = jnp.concatenate(solved)
            written = jnp.take(
                lanes, jnp.maximum(lane_of_entity, 0), axis=0, mode="clip"
            )
            table = jnp.where(lane_of_entity[:, None] >= 0, written, table)
        # full-row rescore in the same dispatch
        with jax.named_scope("re_score"):
            scores = _score_rows_by_entity(table, row_features, row_entities)
        return table, tuple(trackers), scores

    return update_all


def _lane_of_entity(entity_index, num_entities: int) -> np.ndarray:
    """(num_entities,) int32, entity -> position of its lane in the
    concatenation of the buckets' lanes (bucket 0's first, in lane order);
    -1 for an entity that sits in no lane. Sentinel lanes (index ==
    num_entities, the sharding pads) are never pointed at. The inverse of
    ``design.entity_index``, which is static for the coordinate's life."""
    lanes = np.concatenate(
        [np.asarray(ei, np.int64).reshape(-1) for ei in entity_index]
    )
    position = np.flatnonzero(lanes < num_entities)
    entities = lanes[position]
    twice = np.flatnonzero(np.bincount(entities, minlength=1) > 1)
    if twice.size:
        raise ValueError(
            f"{twice.size} entities sit in more than one lane of the "
            f"bucketed design (first: {int(twice[0])}); the table write "
            "needs every entity in at most one lane"
        )
    lane_of = np.full(num_entities, -1, np.int32)
    lane_of[entities] = position
    return lane_of


def _offsets_gather_maps(shards):
    """``game.data.offsets_gather_maps`` a shard of a coordinate (a plain
    coordinate is one shard), each shard a list of its buckets' host
    (row_index, mask), with the two numbers that say how far the compact
    gather engages booked as gauges: the indices a pass gathers (held
    rows and runs) beside the padded slots it fills."""
    maps = [offsets_gather_maps(buckets) for buckets in shards]
    reg = obs.registry()
    reg.set_gauge(
        "game.offsets_gather.gather_indices",
        sum(perm.size + sum(s.size for s in starts) for perm, starts in maps),
    )
    reg.set_gauge(
        "game.offsets_gather.padded_slots",
        sum(np.size(m) for buckets in shards for _, m in buckets),
    )
    return maps


def _design_offsets_maps(design: BucketedRandomEffectDesign):
    """The offsets-gather maps of an unsharded bucketed design, on the
    device. A bucket that is a global array over several processes
    (``parallel.multihost.make_global_re_design``) is fetched replicated
    first, so every process derives the same maps."""
    from photon_ml_tpu.parallel.multihost import fetch_replicated

    host = [
        tuple(
            np.asarray(fetch_replicated(a)) for a in (b.row_index, b.mask)
        )
        for b in design.buckets
    ]
    return jax.tree_util.tree_map(
        jnp.asarray, _offsets_gather_maps([host])[0]
    )


def _score_rows_by_entity(table, feats, ents):
    """Embedding-style per-row scoring with the -1 = unknown-entity ->
    score-0 convention (``model/RandomEffectModel.scala:117-146``). The
    ONE definition both the fused update path and score() use."""
    safe = jnp.maximum(ents, 0)
    per_row = jnp.einsum("nd,nd->n", feats, table[safe])
    return jnp.where(ents >= 0, per_row, 0.0)


def _part_count(dtype) -> int:
    """bfloat16 parts (8 significant bits each) that hold a value of
    ``dtype`` exactly: 3 for float32, 7 for float64."""
    return -(-(jnp.finfo(dtype).nmant + 1) // 8)


def _product_tiles(width: int, dtype) -> int:
    """128-wide MXU tiles a lane's products take at ``width``: its blocks'
    parts stacked, ceil(parts x blocks / 128)."""
    return -(-_part_count(dtype) * (width // COMPACT_BLOCK) // COMPACT_BLOCK)


def _bf16_parts(x):
    """x cut into :func:`_part_count` bfloat16 parts, high to low, each the
    rest so far truncated to bfloat16's 8 significant bits (its sign,
    exponent and 7 highest mantissa bits kept by a mask). The parts carry
    x's sign on disjoint bits of its significand, so any subset of them
    sums exactly, in any order, and all of them to x (where every part is
    a normal bfloat16: float32 above 2^-103 in magnitude, float64 above
    2^-74)."""
    width = 8 * x.dtype.itemsize
    keep = jnp.asarray((1 << width) - (1 << (jnp.finfo(x.dtype).nmant - 7)),
                       f"uint{width}")
    bits = keep.dtype
    parts, rest = [], x
    for _ in range(_part_count(x.dtype) - 1):
        part = jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(rest, bits) & keep, x.dtype)
        parts.append(part)
        rest = rest - part
    return [part.astype(jnp.bfloat16) for part in parts + [rest]]


def _split_columns(columns, width: int, parts: int):
    """A local id as (block, lane of the block), one-hots exact in any
    float type: the block over ``parts`` stacked copies of the width's
    blocks (entry q is block q % blocks), the lane over a block's 128."""
    blocks = width // COMPACT_BLOCK
    pick = columns[..., None] // COMPACT_BLOCK == jnp.arange(
        parts * blocks, dtype=columns.dtype) % blocks
    lane = columns[..., None] % COMPACT_BLOCK == jnp.arange(
        COMPACT_BLOCK, dtype=columns.dtype)
    return pick, lane


def _one_pass(spec, x, y, dtype):
    """A contraction of bfloat16 one-hots and exact bfloat16 parts in one
    MXU pass (no HIGHEST split: the operands are bfloat16 already),
    accumulated in ``dtype``."""
    return jnp.einsum(spec, x.astype(jnp.bfloat16), y,
                      preferred_element_type=dtype,
                      precision=jax.lax.Precision.DEFAULT)


def _lane_pick(w, columns):
    """(s, R) w[columns] of one lane's compact ELL rows, equal to the
    gather to the bit, read as two one-hot contractions: every block's
    coefficient at the id's lane of a block, by one bfloat16 MXU pass over
    the 128 lanes against the blocks' :func:`_bf16_parts` stacked
    ((parts x blocks, 128): one 128-wide tile up to 42 blocks in float32),
    then the parts of the id's block selected and summed. A one-hot is
    exact in bfloat16, each product is one part accumulated with zeros,
    and the parts sum exactly, so the one pass is as exact as HIGHEST's
    six (half of which multiply the one-hot's zero low parts). XLA's
    gather costs about 7 ns an index on the chip (PERF.md section 5), the
    products a fraction of that. The other order (the id's block of 128
    coefficients by the product, then its lane selected) compiled for TPU
    v5e reads 0.85 of the margins' size wrong on the chip (PERF.md section
    6); this one is checked against ``jnp.take`` on the chip by
    ``tests/test_index_map_sparse_re.py``."""
    width = w.shape[0]
    parts = _bf16_parts(w.reshape(width // COMPACT_BLOCK, COMPACT_BLOCK))
    pick, lane = _split_columns(columns, width, len(parts))
    column = _one_pass("srl,ql->srq", lane, jnp.concatenate(parts), w.dtype)
    return jnp.sum(jnp.where(pick, column, 0.0), axis=-1)


def _lane_matvec(w, columns, values):
    """One lane's margins over its compact ELL rows: (s, R) local ids and
    values against its (k,) coefficients -> (R,), by :func:`_lane_pick`."""
    with jax.named_scope("sparse_re/matvec"):
        return jnp.sum(values * _lane_pick(w, columns), axis=0)


def _lane_rmatvec(a, columns, values, width: int):
    """The transpose: per-row (R,) weights -> the lane's (k,) vector, the
    segment sum of its stored entries by local id, read as the transposed
    one-hot contraction, no scatter: each entry's ``values * a`` cut into
    :func:`_bf16_parts`, each part laid on its id's block among the
    stacked parts' ((s, R, parts x blocks)), summed over the entries
    against the lane one-hot by one bfloat16 MXU pass accumulated in a's
    dtype (as HIGHEST's six passes are), then the parts added. Checked
    against ``np.add.at`` on the chip by
    ``tests/test_index_map_sparse_re.py``."""
    with jax.named_scope("sparse_re/rmatvec"):
        parts = _bf16_parts(values * a[None, :])
        pick, lane = _split_columns(columns, width, len(parts))
        part_of = jnp.arange(pick.shape[-1]) // (width // COMPACT_BLOCK)
        spread = parts[-1][..., None]
        for p in range(len(parts) - 2, -1, -1):
            spread = jnp.where(part_of == p, parts[p][..., None], spread)
        spread = jnp.where(pick, spread, jnp.zeros((), jnp.bfloat16))
        summed = _one_pass("srl,srq->ql", lane, spread, a.dtype)
        return jnp.sum(summed.reshape(len(parts), -1), axis=0)


def _compact_lane_objective(loss, lam, columns, values, labels, offsets,
                            weights, width: int):
    """(value_and_grad, hvp, hvp_at, value_grad_curvature) of one lane's
    L2 GLM in its compact column space: ``GLMObjective``'s algebra with
    the margins a gather of the lane's vector at its rows' stored entries
    and the gradient and Hessian-vector product a segment sum back into
    it, ``weights`` already masked. Built once a bucket traced, which
    books the MXU tiles its products take
    (``game.sparse_re.product_tiles``)."""
    obs.registry().inc("game.sparse_re.product_tiles",
                       _product_tiles(width, values.dtype))

    def vgc(w):
        z = _lane_matvec(w, columns, values) + offsets
        val = jnp.sum(weights * loss.value(z, labels)) + 0.5 * lam * jnp.vdot(
            w, w)
        grad = _lane_rmatvec(
            weights * loss.d1(z, labels), columns, values, width
        ) + lam * w
        return val, grad, weights * loss.d2(z, labels)

    def hvp_at(curvature, v):
        dz = _lane_matvec(v, columns, values)
        return _lane_rmatvec(curvature * dz, columns, values, width) + lam * v

    def value_and_grad(w):
        val, grad, _ = vgc(w)
        return val, grad

    def hvp(w, v):
        return hvp_at(vgc(w)[2], v)

    return value_and_grad, hvp, hvp_at, vgc


_COMPACT_OPTIMIZERS = (OptimizerType.TRON, OptimizerType.LBFGS)


@lru_cache(maxsize=64)
def _make_compact_solve(config: CoordinateConfig):
    """solve(w0 (E_b, k_b), reg_weight (E_b,), columns, values, labels,
    offsets, weights, mask) of every lane of one compact ELL bucket
    (``game.data.CompactEllBucket``), vmapped: TRON with its
    Hessian-vector product (or L-BFGS; OWL-QN under an L1 share), never a
    (k, k) Hessian, so NEWTON is refused here, at build time. Un-jitted:
    it runs inside the coordinate's update."""
    loss = loss_for_task(config.task)
    scfg = config.solver_config()
    use_owlqn = config.l1_ratio > 0.0
    if not use_owlqn and config.optimizer not in _COMPACT_OPTIMIZERS:
        raise ValueError(
            f"a random effect over a sparse shard (INDEX_MAP, compact "
            f"columns) solves by {[o.name for o in _COMPACT_OPTIMIZERS]} "
            f"(OWL-QN under an L1 share), not {config.optimizer.name}: its "
            "lanes' Hessians would be (k, k) at the width of their union"
        )
    if config.optimizer == OptimizerType.TRON and not (
        loss.twice_differentiable
    ):
        raise ValueError(
            f"{config.task} is first-order only; TRON needs a "
            "twice-differentiable loss (use LBFGS)"
        )

    def solve_one(w0, reg_weight, columns, values, labels, offsets, weights,
                  mask):
        l1 = reg_weight * config.l1_ratio
        lam = reg_weight * (1.0 - config.l1_ratio)
        vg, hvp, hvp_at, vgc = _compact_lane_objective(
            loss, lam, columns, values, labels, offsets, weights * mask,
            w0.shape[0])
        if use_owlqn:
            return minimize_owlqn(vg, w0, l1, scfg)
        if config.optimizer == OptimizerType.TRON:
            return minimize_tron(vg, hvp, w0, scfg, hvp_at_fn=hvp_at,
                                 vgc_fn=vgc)
        return minimize_lbfgs(vg, w0, scfg)

    return jax.vmap(solve_one)


def _score_compact_rows(table, row_slots, row_values):
    """Every row's score from the flat ragged table: a gather at its
    stored entries' flat positions ((s, n)), times their values, summed
    (an entry outside its entity's union carries value 0)."""
    with jax.named_scope("sparse_re/score"):
        return jnp.sum(
            row_values * jnp.take(table, row_slots, axis=0, mode="clip"),
            axis=0)


def _lane_passes(result) -> jax.Array:
    """(E_b,) int32: the passes over its compact rows a lane's solve made
    (TRON: its outer iterations + 1 value/gradient, and its CG
    iterations, one Hessian-vector product each; L-BFGS: its counted
    evaluations)."""
    iterations = result.iterations.astype(jnp.int32)
    if result.cg_iterations is not None:
        return iterations + 1 + result.cg_iterations.astype(jnp.int32)
    if result.evals is not None:
        return result.evals.astype(jnp.int32)
    return iterations + 1


def _make_index_map_update(config: CoordinateConfig):
    """ONE jitted update of every bucket of an INDEX_MAP random effect over
    a sparse shard and its rescore (the eager ``update`` dispatches it,
    the fused pass inlines it): the residual offsets routed into the
    buckets by the compact gather (as a plain random effect's), each
    bucket's lanes read as one (E_b, k_b) block of the flat table (its
    static slice), solved by :func:`_make_compact_solve`, and the table
    written back as the blocks end to end, with no gather and no scatter;
    then every row rescored by :func:`_score_compact_rows`. ``widths`` is
    static. Cache key zeroes reg_weight (a traced scalar, as the fixed
    effect's, so that a grid sweep reuses one compile)."""
    return _make_index_map_update_cached(
        dataclasses.replace(config, reg_weight=0.0))


@lru_cache(maxsize=64)
def _make_index_map_update_cached(config: CoordinateConfig):
    solve = _make_compact_solve(config)
    from photon_ml_tpu.solvers.common import final_grad_norm

    def update_all(table, reg_weight, full_offsets, offsets_maps, buckets,
                   row_slots, row_values, *, widths):
        # runs while a coordinate's update is traced, never in a pass
        obs.registry().inc("game.offsets_gather.compact")
        with jax.named_scope("re_gather"), jax.named_scope("offsets"):
            bucket_offsets = gather_offsets_compact(
                full_offsets, offsets_maps, [b.mask for b in buckets]
            )
        solved, trackers, at = [], [], 0
        for bucket, offsets, width in zip(buckets, bucket_offsets, widths):
            lanes = bucket.columns.shape[0]
            w0 = jax.lax.slice_in_dim(
                table, at, at + lanes * width).reshape(lanes, width)
            at += lanes * width
            with jax.named_scope("sparse_re/solve"):
                result = solve(
                    w0, jnp.full((lanes,), reg_weight, w0.dtype),
                    bucket.columns, bucket.values, bucket.labels, offsets,
                    bucket.weights, bucket.mask,
                )
            solved.append(result.w.reshape(-1))
            trackers.append((result.reason, result.iterations,
                             final_grad_norm(result), _lane_passes(result)))
        table = jnp.concatenate(solved)
        scores = _score_compact_rows(table, row_slots, row_values)
        return table, tuple(trackers), scores

    return jax.jit(update_all, static_argnames=("widths",))


class RandomEffectCoordinate:
    """Per-entity batched coordinate.

    Owns the padded active design plus full-row (features, entity index)
    for scoring. Scoring covers ALL rows — active and passive — through the
    coefficient table (``RandomEffectCoordinate.scala:116-170``).

    Accepts either a single global-cap :class:`RandomEffectDesign` or a
    :class:`BucketedRandomEffectDesign`; a plain design is treated as one
    bucket whose lanes ARE the table rows.
    """

    def __init__(
        self,
        design,  # RandomEffectDesign | BucketedRandomEffectDesign
        row_features: jax.Array,  # (n, d) full scoring view
        row_entities: jax.Array,  # (n,) int32, -1 = unknown entity
        full_offsets_base: jax.Array,  # (n,) data offsets
        config: CoordinateConfig,
        reg_weights: Optional[jax.Array] = None,  # (E,) per-entity lambdas
    ):
        if config.random_effect is None:
            raise ValueError("config lacks random_effect; wrong coordinate")
        if isinstance(design, RandomEffectDesign):
            design = BucketedRandomEffectDesign(
                buckets=[design],
                entity_index=[
                    np.arange(design.num_entities, dtype=np.int32)
                ],
                num_entities=design.num_entities,
            )
        self.design = design
        self.row_features = row_features
        self.row_entities = row_entities
        self.full_offsets_base = full_offsets_base
        self.config = config
        # (E,) per-entity regularization weights
        # (``RandomEffectOptimizationProblem.scala:41-110``: each entity may
        # carry a distinct objective); shared config weight by default
        self._uniform_reg = reg_weights is None
        if reg_weights is None:
            reg_weights = jnp.full(
                (design.num_entities,), config.reg_weight, jnp.float32
            )
        else:
            reg_weights = jnp.asarray(reg_weights, jnp.float32)
            if reg_weights.shape != (design.num_entities,):
                raise ValueError(
                    f"reg_weights must be ({design.num_entities},), got "
                    f"{reg_weights.shape}"
                )
        self.reg_weights = reg_weights
        self._update_all = _make_multi_bucket_update(config)
        self._entity_indices = tuple(
            jnp.asarray(ei) for ei in design.entity_index
        )
        self._lane_of_entity = jnp.asarray(
            _lane_of_entity(design.entity_index, design.num_entities)
        )
        # static for the coordinate's life, like the lane map: the rows
        # the offsets gather reads and where every slot's run starts
        self._offsets_maps = _design_offsets_maps(design)
        # static per-bucket masks of real (non-sharding-pad) lanes
        self._valid_lanes = [
            np.asarray(ei) < design.num_entities
            for ei in design.entity_index
        ]

        self._score = jax.jit(_score_rows_by_entity)

    @property
    def num_entities(self) -> int:
        return self.design.num_entities

    @property
    def dim(self) -> int:
        return self.design.dim

    def initial_params(self) -> jax.Array:
        from photon_ml_tpu.models.training import solve_dtype

        return jnp.zeros(
            (self.num_entities, self.dim),
            solve_dtype(self.design.buckets[0]),
        )

    def update(
        self, table: jax.Array, partial_scores: jax.Array, key=None
    ) -> Tuple[jax.Array, object]:
        table, summary, _ = self.update_and_score(
            table, partial_scores, key=key
        )
        return table, summary

    def update_and_score(
        self, table: jax.Array, partial_scores: jax.Array, key=None
    ) -> Tuple[jax.Array, object, jax.Array]:
        """All bucket solves + the full-row rescore in ONE dispatch."""
        table, trackers, scores = self.update_step(
            table, partial_scores, key
        )
        return table, self.wrap_tracker(trackers), scores

    def update_step(
        self, table: jax.Array, partial_scores: jax.Array, key=None
    ) -> Tuple[jax.Array, tuple, jax.Array]:
        """Trace-safe form: returns the RAW per-bucket tracker tuple (a
        pytree) instead of the lazy summary object, so the fused CD pass
        can return it through jit."""
        return self._update_all(
            table,
            self.reg_weights,
            self.full_offsets_base + partial_scores,
            self._entity_indices,
            self._lane_of_entity,
            self._offsets_maps,
            tuple(self.design.buckets),
            self.row_features,
            self.row_entities,
        )

    def wrap_tracker(self, trackers: tuple) -> "RandomEffectUpdateSummary":
        """Raw (reason, iterations, grad_norm) bucket tuple -> lazy
        history summary (valid-lane masks and the lanes' table-row
        indices are host-side statics attached here)."""
        pending = [
            (reason, iters, gnorm, valid, np.asarray(ei))
            for (reason, iters, gnorm), valid, ei in zip(
                trackers, self._valid_lanes, self.design.entity_index
            )
        ]
        return RandomEffectUpdateSummary(pending=pending)

    def fused_state(self):
        """See ``FixedEffectCoordinate.fused_state``."""
        return (
            self.reg_weights,
            self.full_offsets_base,
            self._entity_indices,
            self._lane_of_entity,
            self._offsets_maps,
            tuple(self.design.buckets),
            self.row_features,
            self.row_entities,
        )

    def fused_state_for_reg(self, reg_weight):
        """The fused state with every entity's reg weight set to
        ``reg_weight`` — the grid-sweep axis (``descent.run_grid``).
        The grid REPLACES the coordinate-level lambda, like the
        reference's ``GLMOptimizationConfiguration`` grid; a coordinate
        built with CUSTOM per-entity weights refuses (silently
        discarding them would break run_grid's sequential-equivalence
        guarantee).

        SAME-OBJECT CONTRACT (see the fixed-effect counterpart): only
        the freshly-built per-entity weight vector may vary per call;
        the design buckets, row features, entity indices, the entity ->
        lane map, the offsets-gather maps and offsets must be the SAME
        objects every time so
        run_grid broadcasts them instead of stacking n_combo copies of
        the dataset."""
        if not getattr(self, "_uniform_reg", True):
            raise ValueError(
                "grid sweeps replace the coordinate's shared reg weight; "
                "this RandomEffectCoordinate carries CUSTOM per-entity "
                "reg_weights — run its combos sequentially instead"
            )
        return (
            jnp.full(
                (self.design.num_entities,), reg_weight, jnp.float32
            ),
            self.full_offsets_base,
            self._entity_indices,
            self._lane_of_entity,
            self._offsets_maps,
            tuple(self.design.buckets),
            self.row_features,
            self.row_entities,
        )

    def with_fused_state(self, state):
        import copy

        c = copy.copy(self)
        (
            c.reg_weights,
            c.full_offsets_base,
            c._entity_indices,
            c._lane_of_entity,
            c._offsets_maps,
            buckets,
            c.row_features,
            c.row_entities,
        ) = state
        c.design = dataclasses.replace(self.design, buckets=list(buckets))
        return c

    def score(self, table: jax.Array) -> jax.Array:
        return self._score(table, self.row_features, self.row_entities)

    def reg_term(self, table: jax.Array) -> jax.Array:
        """Penalty with PER-ENTITY weights — what the vmapped solves
        minimized (``RandomEffectOptimizationProblem.getRegularizationTermValue``)."""
        lam = self.reg_weights.astype(table.dtype)
        l2 = lam * (1.0 - self.config.l1_ratio)
        l1 = lam * self.config.l1_ratio
        sq = jnp.sum(table * table, axis=-1)
        ab = jnp.sum(jnp.abs(table), axis=-1)
        return jnp.sum(0.5 * l2 * sq + l1 * ab)


def _exchange_rows(x, send, recv, num_shards: int, scope: str):
    """One direction of a :class:`game.data.RowExchangePlan` on one shard,
    inside a ``shard_map`` over the 'entity' axis: pack this shard's rows
    into one block a destination (a gather through ``send``), move block
    (p, q) to shard q with one ``all_to_all``, and read every row of the
    arriving order out of what came by one gather through the static
    inverse map ``recv``. A pad slot or pad row (-1) carries 0."""
    from photon_ml_tpu.parallel.mesh import ENTITY_AXIS

    with jax.named_scope("re_exchange"), jax.named_scope(scope):
        packed = jnp.where(
            send >= 0, jnp.take(x, jnp.maximum(send, 0), mode="clip"), 0
        )
        arrived = jax.lax.all_to_all(
            packed.reshape(num_shards, -1), ENTITY_AXIS, 0, 0
        )
        return jnp.where(
            recv >= 0,
            jnp.take(arrived.reshape(-1), jnp.maximum(recv, 0), mode="clip"),
            0,
        )


class EntityShardedRandomEffectCoordinate:
    """Entity-sharded random-effect coordinate: the per-entity vmapped
    solves run under ``shard_map`` over the 'entity' mesh axis
    (docs/PARALLEL.md) — each shard gathers warm starts from ITS table
    block, solves ITS entities, writes them back locally and rescores
    ITS rows, through the same update body as
    :class:`RandomEffectCoordinate` (``_multi_bucket_update_body``) with
    every index shard-local.

    Contract (``game.data``): entity ownership follows the sharded
    checkpoint writer's round-robin rule (``EntityShardAssignment``),
    the table is stored SHARD-MAJOR (pad rows zero), and the design, the
    row features and the row entities are in the coordinate's OWN
    entity-partitioned row order (``EntityRowPartition``), so every
    entity's rows live on its owner shard — the device analog of the
    reference's ``RandomEffectIdPartitioner`` placement. Sentinel
    lanes/rows mask to zero and no table row reads a sentinel lane.

    Residual offsets come in, and scores go out, in the CANONICAL row
    order (the first sharded random effect's partition, which the
    descent's labels, weights and every other coordinate's scores live
    in). Where ``partition.exchange`` is None the two orders are one and
    the update has ZERO collectives. Where it is a
    :class:`game.data.RowExchangePlan` (any later random effect: rows
    cannot be grouped by two entity types at once) the one program is
    ``offsets_own = exchange(base + partial_scores)``, the per-shard
    update, ``scores = exchange(scores_own)``: two ``all_to_all``s with
    static shapes, packed and unpacked by gathers, the reference's
    shuffle between two partitioners. No option selects this: the
    coordinate sees it from the partition it was given.

    Exposes the full fused surface (update_step / fused_state /
    with_fused_state / wrap_tracker), so whole-pass and superpass
    dispatches compose — the shard_map nests inside the pass jit.
    """

    def __init__(
        self,
        design,  # BucketedRandomEffectDesign on the OWN-order rows, GLOBAL ids
        row_features: jax.Array,  # (n_pad, d) own order
        row_entities: jax.Array,  # (n_pad,) own order, GLOBAL ids, -1 unknown
        full_offsets_base: jax.Array,  # (n_canonical_pad,) CANONICAL order
        config: CoordinateConfig,
        mesh,
        assignment,  # game.data.EntityShardAssignment
        partition,  # game.data.EntityRowPartition (+ its exchange plan)
        reg_weights: Optional[jax.Array] = None,  # (E,) GLOBAL order
    ):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from photon_ml_tpu.game.data import BucketedRandomEffectDesign
        from photon_ml_tpu.parallel.mesh import ENTITY_AXIS

        if config.random_effect is None:
            raise ValueError("config lacks random_effect; wrong coordinate")
        if isinstance(design, RandomEffectDesign):
            design = BucketedRandomEffectDesign(
                buckets=[design],
                entity_index=[
                    np.arange(design.num_entities, dtype=np.int32)
                ],
                num_entities=design.num_entities,
            )
        n_shards = mesh.shape[ENTITY_AXIS]
        if assignment.num_shards != n_shards:
            raise ValueError(
                f"assignment built for {assignment.num_shards} shards, "
                f"mesh 'entity' axis has {n_shards}"
            )
        if partition.num_shards != n_shards:
            raise ValueError(
                f"row partition built for {partition.num_shards} shards, "
                f"mesh 'entity' axis has {n_shards}"
            )
        e_global = assignment.num_entities
        if design.num_entities != e_global:
            raise ValueError(
                f"design covers {design.num_entities} entities, "
                f"assignment {e_global}"
            )
        b_rows = assignment.rows_per_shard
        r_rows = partition.rows_per_shard
        n_pad = partition.padded_rows
        if int(np.shape(row_entities)[0]) != n_pad:
            raise ValueError(
                f"row arrays must be in the partitioned row space "
                f"({n_pad} rows), got {np.shape(row_entities)[0]}"
            )
        plan = partition.exchange
        n_canonical = n_pad if plan is None else plan.canonical_padded_rows
        if int(np.shape(full_offsets_base)[0]) != n_canonical:
            raise ValueError(
                f"full_offsets_base must be in the canonical row order "
                f"({n_canonical} rows), got {np.shape(full_offsets_base)[0]}"
            )
        self.config = config
        self.mesh = mesh
        self.assignment = assignment
        self.partition = partition
        self._dim = design.dim

        # the host regroup and the device placement: one span, the
        # bytes put on the mesh counted as they go
        with obs.span(
            "partition.coordinate", cat="partition",
            random_effect=config.random_effect, shards=n_shards, rows=n_pad,
        ) as sp:
            ent_spec = lambda nd: NamedSharding(
                mesh, P(ENTITY_AXIS, *([None] * (nd - 1)))
            )
            placed_bytes = 0

            def place(x):
                # straight to its shards: a host array staged through
                # ``jnp.asarray`` would sit whole on the first device
                nonlocal placed_bytes
                placed_bytes += sum(
                    int(leaf.nbytes) for leaf in jax.tree.leaves(x)
                )
                return jax.device_put(x, ent_spec(np.ndim(x)))

            # per-entity reg weights, stored shard-major (pad rows keep the
            # config weight — no lane holds them)
            self._uniform_reg = reg_weights is None
            if reg_weights is None:
                reg_stored = np.full(
                    (assignment.padded_rows,), config.reg_weight, np.float32
                )
            else:
                reg_weights = np.asarray(reg_weights, np.float32)
                if reg_weights.shape != (e_global,):
                    raise ValueError(
                        f"reg_weights must be ({e_global},), got "
                        f"{reg_weights.shape}"
                    )
                reg_stored = assignment.table_from_global(reg_weights)
            self.reg_weights = place(reg_stored)

            # regroup every bucket's lanes by owner shard: shard p's lanes
            # contiguous, padded to the max per-shard count; indices go
            # shard-LOCAL (table rows within the block, sentinel b_rows;
            # offset rows within the block, sentinel -1)
            g2s = assignment.global_to_stored
            buckets = []
            eidx_local = []
            held_rows = []  # a bucket's regrouped (row_index, mask), host
            self._valid_lanes = []
            self._lane_entities = []
            for bucket, eidx in zip(design.buckets, design.entity_index):
                eidx = np.asarray(eidx, np.int64)
                stored = np.where(
                    eidx < e_global, g2s[np.minimum(eidx, e_global)],
                    assignment.padded_rows,
                )
                owner = assignment.shard_of_stored(
                    np.minimum(stored, assignment.padded_rows - 1)
                )
                owner = np.where(
                    stored < assignment.padded_rows, owner, 0
                )  # sentinels balance onto shard 0's padding
                counts = np.bincount(owner, minlength=n_shards)
                l_b = max(int(counts.max()), 1)
                order = np.argsort(owner, kind="stable")
                starts = np.concatenate([[0], np.cumsum(counts)])[:-1]
                slot = np.arange(eidx.size) - starts[owner[order]]
                lane_of = owner[order] * l_b + slot  # new lane of old lane
                new_lanes = n_shards * l_b
                new_stored = np.full(new_lanes, assignment.padded_rows, np.int64)
                new_stored[lane_of] = stored[order]
                local = np.where(
                    new_stored < assignment.padded_rows,
                    new_stored - (np.arange(new_lanes) // l_b) * b_rows,
                    b_rows,
                ).astype(np.int32)

                old_lane = np.full(new_lanes, -1, np.int64)
                old_lane[lane_of] = order

                def regroup(x, fill=0.0):
                    # one gather of whole lanes, the pad lanes overwritten
                    out = np.take(
                        np.asarray(x), np.maximum(old_lane, 0), axis=0
                    )
                    out[old_lane < 0] = fill
                    return out

                ri = np.asarray(bucket.row_index, np.int64)
                shard_of_lane = np.arange(new_lanes) // l_b
                ri_new = regroup(ri, fill=-1)
                ri_local = np.where(
                    ri_new >= 0,
                    ri_new - shard_of_lane[:, None] * r_rows,
                    -1,
                ).astype(np.int32)
                mask_new = regroup(bucket.mask)
                held_rows.append((ri_local, mask_new))
                buckets.append(
                    RandomEffectDesign(
                        features=place(regroup(bucket.features)),
                        labels=place(regroup(bucket.labels)),
                        weights=place(regroup(bucket.weights)),
                        mask=place(mask_new),
                        row_index=place(ri_local),
                    )
                )
                eidx_local.append(local)
                self._valid_lanes.append(
                    new_stored < assignment.padded_rows
                )
                glob = np.full(new_lanes, e_global, np.int64)
                real = new_stored < assignment.padded_rows
                glob[real] = assignment.stored_to_global[new_stored[real]]
                self._lane_entities.append(glob.astype(np.int32))
            self._buckets = tuple(buckets)
            # the offsets-gather maps a shard, from ITS lanes' block of every
            # bucket; a shard's rows to gather padded (row 0, past every run)
            # to the longest shard's
            shard_maps = _offsets_gather_maps(
                [
                    [
                        tuple(np.split(a, n_shards)[p] for a in held)
                        for held in held_rows
                    ]
                    for p in range(n_shards)
                ]
            )
            longest = max(perm.size for perm, _ in shard_maps)
            offsets_maps = (
                place(
                    np.concatenate(
                        [
                            np.pad(perm, (0, longest - perm.size))
                            for perm, _ in shard_maps
                        ]
                    )
                ),
                tuple(
                    place(np.concatenate([starts[b] for _, starts in shard_maps]))
                    for b in range(len(buckets))
                ),
            )
            # (every bucket's lanes, their shard-local inverse: block p maps
            # the rows of ITS table block to the concatenation of ITS lanes,
            # the offsets-gather maps)
            self._entity_indices = (
                tuple(place(li) for li in eidx_local),
                place(
                    np.concatenate(
                        [
                            _lane_of_entity(
                                [
                                    li.reshape(n_shards, -1)[p]
                                    for li in eidx_local
                                ],
                                b_rows,
                            )
                            for p in range(n_shards)
                        ]
                    )
                ),
                offsets_maps,
            )

            # per-row scoring inputs, shard-local entity rows
            re_ids = np.asarray(row_entities, np.int64)
            known = re_ids >= 0
            ents_local = np.full(re_ids.shape, -1, np.int32)
            shard_of_row = np.arange(n_pad) // r_rows
            ents_local[known] = (
                g2s[re_ids[known]] - shard_of_row[known] * b_rows
            ).astype(np.int32)
            self.row_features = place(row_features)
            self.row_entities_local = place(ents_local)
            self.full_offsets_base = place(full_offsets_base)
            # the exchange plan's four index arrays, a shard its segment; ()
            # where the own order is the canonical one
            self._exchange = (
                ()
                if plan is None
                else tuple(
                    place(a)
                    for a in (
                        plan.send_to_owner,
                        plan.recv_at_owner,
                        plan.send_to_canonical,
                        plan.recv_at_canonical,
                    )
                )
            )
            sp.set(bytes_placed=placed_bytes)

        body = _multi_bucket_update_body(
            dataclasses.replace(config, reg_weight=0.0)
        )

        def sharded(fn):
            """``fn`` a shard, every argument and result split on its
            leading axis over the 'entity' mesh axis."""
            return jax.shard_map(
                fn,
                mesh=mesh,
                in_specs=P(ENTITY_AXIS),
                out_specs=P(ENTITY_AXIS),
                # per-shard solver while_loops have no replication rule;
                # every output is genuinely shard-varying anyway
                check_vma=False,
            )

        def to_owner(x, exchange):
            return _exchange_rows(
                x, exchange[0], exchange[1], n_shards, "to_owner"
            )

        def to_canonical(x, exchange):
            return _exchange_rows(
                x, exchange[2], exchange[3], n_shards, "to_canonical"
            )

        def update_shard(
            table, reg, offsets, lanes, bks, feats, ents, exchange
        ):
            if exchange:
                offsets = to_owner(offsets, exchange)
            table, trackers, scores = body(
                table, reg, offsets, *lanes, bks, feats, ents
            )
            if exchange:
                scores = to_canonical(scores, exchange)
            return table, trackers, scores

        def update_all(
            table, reg, offsets, lanes, bks, feats, ents, exchange=()
        ):
            if exchange:
                # runs while an update is traced, never in a pass
                obs.registry().inc("game.exchange.programs")
                obs.registry().set_gauge(
                    "game.exchange.bytes_per_pass",
                    2 * plan.exchanged_rows * offsets.dtype.itemsize,
                )
            return sharded(update_shard)(
                table, reg, offsets, lanes, bks, feats, ents, exchange
            )

        self._update_all = jax.jit(update_all)

        def score_shard(table, feats, ents, exchange):
            scores = _score_rows_by_entity(table, feats, ents)
            return to_canonical(scores, exchange) if exchange else scores

        self._score = jax.jit(sharded(score_shard))

    @property
    def num_entities(self) -> int:
        return self.assignment.num_entities

    @property
    def dim(self) -> int:
        return self._dim

    def initial_params(self) -> jax.Array:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from photon_ml_tpu.models.training import solve_dtype
        from photon_ml_tpu.parallel.mesh import ENTITY_AXIS

        return jax.device_put(
            jnp.zeros(
                (self.assignment.padded_rows, self.dim),
                solve_dtype(self._buckets[0]),
            ),
            NamedSharding(self.mesh, P(ENTITY_AXIS, None)),
        )

    def global_table(self, table: jax.Array) -> np.ndarray:
        """Stored (shard-major, padded) table -> global entity order —
        the equivalence bridge to an unsharded RandomEffectCoordinate."""
        return self.assignment.table_to_global(np.asarray(table))

    def update(self, table, partial_scores, key=None):
        table, summary, _ = self.update_and_score(
            table, partial_scores, key=key
        )
        return table, summary

    def update_and_score(self, table, partial_scores, key=None):
        table, trackers, scores = self.update_step(
            table, partial_scores, key
        )
        return table, self.wrap_tracker(trackers), scores

    def update_step(self, table, partial_scores, key=None):
        """Trace-safe: the whole multi-bucket update + rescore is ONE
        shard_map'd program whose only collectives are the exchange's
        two ``all_to_all``s — none at all for the canonical coordinate
        (asserted in tests/test_partition.py and
        tests/test_game_sharded_multi_re.py via the compiled HLO)."""
        return self._update_all(
            table,
            self.reg_weights,
            self.full_offsets_base + partial_scores,
            self._entity_indices,
            self._buckets,
            self.row_features,
            self.row_entities_local,
            self._exchange,
        )

    def wrap_tracker(self, trackers: tuple) -> "RandomEffectUpdateSummary":
        pending = [
            (reason, iters, gnorm, valid, ents)
            for (reason, iters, gnorm), valid, ents in zip(
                trackers, self._valid_lanes, self._lane_entities
            )
        ]
        return RandomEffectUpdateSummary(pending=pending)

    def fused_state(self):
        """See ``FixedEffectCoordinate.fused_state``."""
        return (
            self.reg_weights,
            self.full_offsets_base,
            self._entity_indices,
            self._buckets,
            self.row_features,
            self.row_entities_local,
            self._exchange,
        )

    def with_fused_state(self, state):
        import copy

        c = copy.copy(self)
        (
            c.reg_weights,
            c.full_offsets_base,
            c._entity_indices,
            c._buckets,
            c.row_features,
            c.row_entities_local,
            c._exchange,
        ) = state
        return c

    def score(self, table: jax.Array) -> jax.Array:
        return self._score(
            table, self.row_features, self.row_entities_local,
            self._exchange,
        )

    def reg_term(self, table: jax.Array) -> jax.Array:
        """Per-entity penalty; pad rows are zero so their lam is inert."""
        lam = self.reg_weights.astype(table.dtype)
        l2 = lam * (1.0 - self.config.l1_ratio)
        l1 = lam * self.config.l1_ratio
        sq = jnp.sum(table * table, axis=-1)
        ab = jnp.sum(jnp.abs(table), axis=-1)
        return jnp.sum(0.5 * l2 * sq + l1 * ab)


# -- down-samplers (``sampler/``) -------------------------------------------


def _binary_downsample_weights(key, weights, labels, rate: float):
    """Keep positives; keep negatives w.p. rate with weight / rate
    (``sampler/BinaryClassificationDownSampler.scala:36-66``). Static
    shapes: dropped rows get weight 0."""
    keep = jax.random.uniform(key, weights.shape) < rate
    neg = labels <= 0.0
    w = jnp.where(neg & keep, weights / rate, weights)
    return jnp.where(neg & ~keep, 0.0, w)


def _uniform_downsample_weights(key, weights, labels, rate: float):
    """Uniform Bernoulli down-sampling with reweighting
    (``sampler/DefaultDownSampler.scala:30``)."""
    keep = jax.random.uniform(key, weights.shape) < rate
    return jnp.where(keep, weights / rate, 0.0)
