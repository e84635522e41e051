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

import dataclasses
from functools import lru_cache
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

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


@lru_cache(maxsize=64)
def _make_latent_solve(config: CoordinateConfig, num_buckets: int):
    """jitted solve for the shared projection B over `num_buckets` bucket
    designs. The objective treats vec(B) as the coefficient vector of a
    GLM on the VIRTUAL Kronecker features x (x) gamma — contracted lazily:

      margin_er = einsum('erd,dk,ek->er', X_b, B, gamma_b)
      grad_dk   = einsum('er,erd,ek->dk', c, X_b, gamma_b) + lambda B
      (Hv)_dk   = same contraction with c2 * dmargin(V)

    Bucket tensors arrive as positional args (pytrees of varying shapes),
    so one compilation serves a whole training run."""
    loss = loss_for_task(config.task)
    scfg = config.solver_config()
    use_tron = config.optimizer == OptimizerType.TRON
    use_owlqn = config.l1_ratio > 0.0
    l2 = config.reg_weight * (1.0 - config.l1_ratio)
    l1 = config.reg_weight * config.l1_ratio
    lam = l2

    def solve(b0, gammas, buckets_offsets, buckets):
        d, k = b0.shape

        def margins(B, bucket, gamma_b, offsets):
            xb = jnp.einsum("erd,dk->erk", bucket.features, B)
            return jnp.einsum("erk,ek->er", xb, gamma_b) + offsets

        def value_and_grad(vecB):
            B = vecB.reshape(d, k)
            val = 0.5 * lam * jnp.vdot(B, B)
            grad = lam * B
            for bucket, gamma_b, offsets in zip(
                buckets, gammas, buckets_offsets
            ):
                w = bucket.weights * bucket.mask
                z = margins(B, bucket, gamma_b, offsets)
                val = val + jnp.sum(w * loss.value(z, bucket.labels))
                c = w * loss.d1(z, bucket.labels)
                cg = jnp.einsum("er,ek->erk", c, gamma_b)
                grad = grad + jnp.einsum(
                    "erd,erk->dk", bucket.features, cg
                )
            return val, grad.reshape(-1)

        def hvp(vecB, vecV):
            B = vecB.reshape(d, k)
            V = vecV.reshape(d, k)
            out = lam * V
            for bucket, gamma_b, offsets in zip(
                buckets, gammas, buckets_offsets
            ):
                w = bucket.weights * bucket.mask
                z = margins(B, bucket, gamma_b, offsets)
                dz = margins(V, bucket, gamma_b, jnp.zeros_like(offsets))
                c2 = w * loss.d2(z, bucket.labels) * dz
                cg = jnp.einsum("er,ek->erk", c2, gamma_b)
                out = out + jnp.einsum("erd,erk->dk", bucket.features, cg)
            return out.reshape(-1)

        if use_owlqn:
            return minimize_owlqn(value_and_grad, b0.reshape(-1), l1, scfg)
        if use_tron:
            return minimize_tron(value_and_grad, hvp, b0.reshape(-1), scfg)
        return minimize_lbfgs(value_and_grad, b0.reshape(-1), scfg)

    return jax.jit(solve)


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
        self.row_features = row_features
        self.row_entities = row_entities
        self.full_offsets_base = full_offsets_base
        self.config = re_config
        self.factored = factored
        self._seed = seed

        latent_cfg = factored.latent_factor_config or re_config
        self._latent_cfg = latent_cfg
        self._re_solve = _make_solve(
            dataclasses.replace(re_config, random_effect=None), batched=True
        )
        self._latent_solve = _make_latent_solve(
            dataclasses.replace(latent_cfg, random_effect=None),
            design.num_buckets,
        )

        @jax.jit
        def score_rows(params: FactoredParams, feats, ents):
            latent = feats @ params.projection  # (n, k)
            safe = jnp.maximum(ents, 0)
            per_row = jnp.einsum("nk,nk->n", latent, params.gamma[safe])
            return jnp.where(ents >= 0, per_row, 0.0)

        self._score = score_rows

    @property
    def num_entities(self) -> int:
        return self.design.num_entities

    @property
    def dim(self) -> int:
        """Original feature dimension of the underlying design."""
        return self.design.dim

    def initial_params(self) -> FactoredParams:
        """Gamma zeros; B a Gaussian N(0, 1/d) like the reference's random
        projection init (``FactoredRandomEffectOptimizationProblem``)."""
        from photon_ml_tpu.models.training import solve_dtype

        d = self.design.dim
        k = self.factored.latent_dim
        rng = np.random.default_rng(self._seed)
        b = rng.normal(0.0, 1.0 / np.sqrt(d), size=(d, k))
        dtype = solve_dtype(self.design.buckets[0])
        return FactoredParams(
            gamma=jnp.zeros((self.num_entities, k), dtype),
            projection=jnp.asarray(b, dtype),
        )

    def update(
        self, params: FactoredParams, partial_scores: jax.Array, key=None
    ) -> Tuple[FactoredParams, object]:
        design = self.design
        full_offsets = self.full_offsets_base + partial_scores
        bucket_offsets = gather_offsets_compact(
            full_offsets, self._offsets_maps, [b.mask for b in design.buckets]
        )
        gamma, b = params.gamma, params.projection
        lam_re = jnp.full(
            (design.num_entities,), self.config.reg_weight, gamma.dtype
        )
        result = None
        for _ in range(self.factored.num_inner_iterations):
            # (a) latent-space per-entity solves, bucket by bucket
            for bucket, entity_index, offsets in zip(
                design.buckets, design.entity_index, bucket_offsets
            ):
                eidx = jnp.asarray(entity_index)
                g0 = jnp.take(gamma, eidx, axis=0, mode="clip")
                lam_b = jnp.take(lam_re, eidx, mode="clip")
                latent_feats = jnp.einsum(
                    "erd,dk->erk", bucket.features, b
                )
                result = self._re_solve(
                    g0,
                    lam_b,
                    latent_feats,
                    bucket.labels,
                    offsets,
                    bucket.weights,
                    bucket.mask,
                )
                gamma = gamma.at[eidx].set(result.w, mode="drop")
            # (b) shared projection over ALL buckets, einsum-contracted
            gammas = tuple(
                jnp.take(gamma, jnp.asarray(ei), axis=0, mode="clip")
                for ei in design.entity_index
            )
            latent_result = self._latent_solve(
                b, gammas, tuple(bucket_offsets), tuple(design.buckets)
            )
            b = latent_result.w.reshape(b.shape)
        return FactoredParams(gamma=gamma, projection=b), result

    def score(self, params: FactoredParams) -> jax.Array:
        return self._score(params, self.row_features, self.row_entities)

    def update_step(
        self, params: FactoredParams, partial_scores: jax.Array, key=None
    ) -> Tuple[FactoredParams, object, jax.Array]:
        """Trace-safe update + rescore (the fused CD pass's unit): the
        alternating gamma/B loop above is pure jnp, so it inlines."""
        new_params, result = self.update(params, partial_scores, key)
        return new_params, result, self.score(new_params)

    def wrap_tracker(self, tracker):
        return tracker

    def fused_state(self):
        """See ``FixedEffectCoordinate.fused_state``. The (E,)-int
        entity_index lists stay trace-time constants (small next to the
        designs)."""
        return (
            tuple(self.design.buckets),
            self._offsets_maps,
            self.row_features,
            self.row_entities,
            self.full_offsets_base,
        )

    def with_fused_state(self, state):
        import copy

        c = copy.copy(self)
        (
            buckets,
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
