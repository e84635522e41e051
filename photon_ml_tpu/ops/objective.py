"""Fused GLM objectives: value / gradient / Hessian-vector / Hessian-diagonal.

This is the TPU replacement for the reference's whole L2 layer:
``function/DiffFunction.scala``, ``function/TwiceDiffFunction.scala``,
``function/ValueAndGradientAggregator.scala``,
``function/HessianVectorAggregator.scala`` and
``function/GeneralizedLinearModelLossFunction.scala``.

Where the reference tree-aggregates a per-datum scalar loop over executors, we
compute the same sums as two matmuls on the MXU:

    margins = X @ (w * factor) + margin_shift(w) + offsets          (n,)
    a       = weight * mask * l'(margins, labels)                   (n,)
    grad    = factor * (X^T @ a) - (shift*factor) * sum(a)          (d,)

The (factor, shift) algebra is exactly the reference aggregators'
effectiveCoefficients / margin-shift trick
(``ValueAndGradientAggregator.scala:87-118``): features are never whitened in
memory; normalization costs one extra rank-1 correction. The Hessian-vector
product uses the analytic second derivative the same way
(``HessianVectorAggregator.scala:57-117``) — no double-backprop graph.

Distribution: every method computes *local* partial sums. Under shard_map with
the batch axis sharded, pass ``axis_name`` and the partials are psum-reduced
over ICI — the one-line equivalent of the reference's
``RDD.treeAggregate(depth)`` (``function/DiffFunction.scala:126-143``). Under
plain jit with sharded inputs, leave ``axis_name=None`` and XLA inserts the
collectives from the sharding annotations.

L2 regularization is folded into the objective (value/grad/HVP/diag), L1 is
*not* — it is exposed as ``l1_weight`` for the OWL-QN optimizer, mirroring
``function/L1RegularizationTerm.scala`` + ``optimization/LBFGS.scala:56-98``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from photon_ml_tpu.core.normalization import NormalizationContext, no_normalization
from photon_ml_tpu.core.types import LabeledBatch
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.ops.sparse import (
    colsum,
    is_feature_sharded,
    matvec,
    matvec_and_feature_dots,
    rmatvec,
)


def _maybe_psum(x, axis_name):
    return jax.lax.psum(x, axis_name) if axis_name is not None else x


_REG_TYPES = ("NONE", "L1", "L2", "ELASTIC_NET")


@dataclasses.dataclass(frozen=True)
class RegularizationContext:
    """Elastic-net split of a single regularization weight
    (``optimization/RegularizationContext.scala:25-47``):
    l1 = alpha * lambda, l2 = (1 - alpha) * lambda."""

    reg_type: str = "NONE"  # NONE | L1 | L2 | ELASTIC_NET
    alpha: float = 0.0  # elastic-net mixing; 1.0 = pure L1

    def __post_init__(self):
        if self.reg_type not in _REG_TYPES:
            raise ValueError(
                f"unknown reg_type {self.reg_type!r}; expected one of {_REG_TYPES}"
            )
        if not 0.0 <= self.alpha <= 1.0:
            raise ValueError(f"elastic-net alpha must be in [0,1], got {self.alpha}")

    def l1_weight(self, reg_weight: float) -> float:
        if self.reg_type == "L1":
            return reg_weight
        if self.reg_type == "ELASTIC_NET":
            return self.alpha * reg_weight
        return 0.0

    def l2_weight(self, reg_weight: float) -> float:
        if self.reg_type == "L2":
            return reg_weight
        if self.reg_type == "ELASTIC_NET":
            return (1.0 - self.alpha) * reg_weight
        return 0.0


@dataclasses.dataclass(frozen=True)
class GLMObjective:
    """A pointwise loss bound to normalization + regularization.

    All methods are pure functions of (w, batch) and are safe under jit, grad,
    vmap and shard_map — this single implementation serves both of the
    reference's execution regimes (the ``Either[RDD, Iterable]`` duality,
    ``optimization/Optimizer.scala:163-212``): the "global" instantiation runs
    batch-sharded with psum, the "per-entity" instantiation runs vmapped.
    """

    loss: PointwiseLoss
    normalization: NormalizationContext = dataclasses.field(
        default_factory=no_normalization
    )
    l2_weight: float = 0.0
    l1_weight: float = 0.0  # consumed by OWL-QN, NOT added to value/grad here
    axis_name: Optional[str] = None
    # Feature-sharded designs: ride the scalar feature-space dots (L2
    # value term, margin shift) on the margins block-sum so one bucketed
    # all-reduce serves the whole pass (ops.sparse.matvec_and_feature_dots;
    # BENCH_r05's sparse_fs_scaling 2-device regression). False restores
    # the one-collective-per-contraction formulation — kept for the
    # before/after cost-book comparison, not for production use.
    fuse_feature_reductions: bool = True

    @property
    def _has_l2(self) -> bool:
        """Trace-safe L2 gate: reg weights may be traced scalars (the lambda
        path jits ONE solve reused across lambdas), in which case the term is
        always emitted and XLA folds the zero case."""
        if isinstance(self.l2_weight, (int, float)):
            return self.l2_weight != 0.0
        return True

    # -- margins ---------------------------------------------------------

    def margins(self, w: jax.Array, batch: LabeledBatch) -> jax.Array:
        return self._dmargin_dot(w, batch) + batch.offsets

    def _dmargin_dot(self, v: jax.Array, batch: LabeledBatch) -> jax.Array:
        """(d margin / d w) @ v for each row — normalized-feature dot.
        Dispatches dense (MXU matmul) / sparse ELL (gather kernel). On
        feature-sharded designs with whitening shifts, the margin-shift
        dot rides the margins block-sum (one bucketed all-reduce)."""
        norm = self.normalization
        eff = norm.effective_coefficients(v)
        if (
            self.fuse_feature_reductions
            and norm.shifts is not None
            and is_feature_sharded(batch.features)
        ):
            z0, (ms,) = matvec_and_feature_dots(
                batch.features, eff, ((norm.shifts, eff),)
            )
            return z0 - ms
        return matvec(batch.features, eff) + norm.margin_shift(v)

    def _backproject(self, a: jax.Array, batch: LabeledBatch) -> jax.Array:
        """X'^T @ a where X' is the (virtually) normalized design matrix."""
        norm = self.normalization
        g = rmatvec(batch.features, a)
        if norm.factors is not None:
            g = g * norm.factors
        if norm.shifts is not None:
            shift_eff = norm.shifts * (
                norm.factors if norm.factors is not None else 1.0
            )
            g = g - shift_eff * jnp.sum(a)
        return g

    # -- value / gradient ------------------------------------------------

    def value(self, w: jax.Array, batch: LabeledBatch) -> jax.Array:
        return self.value_and_grad(w, batch)[0]

    def value_and_grad(self, w: jax.Array, batch: LabeledBatch):
        """Fused loss+gradient — the reference's hot aggregator
        (``ValueAndGradientAggregator.scala:204-235``) as two matmuls.
        (The unused curvature output is dead-code-eliminated under jit.)"""
        val, grad, _ = self.value_grad_curvature(w, batch)
        return val, grad

    def grad(self, w: jax.Array, batch: LabeledBatch) -> jax.Array:
        return self.value_and_grad(w, batch)[1]

    @jax.named_scope("objective_pass")
    def value_grad_curvature(self, w: jax.Array, batch: LabeledBatch):
        """(value, gradient, curvature weights) from ONE margins pass.
        The curvature weights c = w_i * l''(z_i) are what
        :meth:`hessian_vector_at` needs — TRON's acceptance evaluation
        already computes z at the trial point, so on acceptance the next
        iteration's CG starts with c for free (no separate
        :meth:`hessian_coefficients` pass).

        Collectives: the value/grad partials reduce in ONE tuple psum
        (one collective per pass, not two); on feature-sharded designs
        the L2 value dot and margin shift additionally ride the margins
        block-sum (``matvec_and_feature_dots``)."""
        norm = self.normalization
        wdot = None
        if (
            self.fuse_feature_reductions
            and is_feature_sharded(batch.features)
            and (self._has_l2 or norm.shifts is not None)
        ):
            eff = norm.effective_coefficients(w)
            pairs = []
            if norm.shifts is not None:
                pairs.append((norm.shifts, eff))
            if self._has_l2:
                pairs.append((w, w))
            z0, dots = matvec_and_feature_dots(batch.features, eff, pairs)
            if norm.shifts is not None:
                z0 = z0 - dots[0]
                dots = dots[1:]
            z = z0 + batch.offsets
            if self._has_l2:
                wdot = dots[0]
        else:
            z = self.margins(w, batch)
            if self._has_l2:
                wdot = jnp.vdot(w, w)
        ew = batch.effective_weights()
        val = jnp.sum(ew * self.loss.value(z, batch.labels))
        a = ew * self.loss.d1(z, batch.labels)
        grad = self._backproject(a, batch)
        c = ew * self.loss.d2(z, batch.labels)
        val, grad = _maybe_psum((val, grad), self.axis_name)
        if self._has_l2:
            val = val + 0.5 * self.l2_weight * wdot
            grad = grad + self.l2_weight * w
        return val, grad, c

    # -- second-order ----------------------------------------------------

    def hessian_vector(
        self, w: jax.Array, v: jax.Array, batch: LabeledBatch
    ) -> jax.Array:
        """H(w) @ v via analytic d2 (``HessianVectorAggregator.scala:57-117``).
        One CG iteration of TRON = one call here."""
        return self.hessian_vector_at(
            self.hessian_coefficients(w, batch), v, batch
        )

    @jax.named_scope("objective_pass")
    def hessian_coefficients(
        self, w: jax.Array, batch: LabeledBatch
    ) -> jax.Array:
        """(n,) per-row curvature weights c = w_i * l''(z_i, y_i) — the
        only w-dependent part of H(w) @ v. Loop-INVARIANT across an inner
        CG solve (w is fixed while CG iterates over v), so TRON computes
        this once per outer iteration and each CG step saves the margins
        pass: 2 design reads per HVP instead of 3."""
        z = self.margins(w, batch)
        return batch.effective_weights() * self.loss.d2(z, batch.labels)

    @jax.named_scope("objective_pass")
    def hessian_vector_at(
        self, c: jax.Array, v: jax.Array, batch: LabeledBatch
    ) -> jax.Array:
        """H @ v with the curvature weights ``c`` precomputed by
        :meth:`hessian_coefficients`. TRON's inner CG loop is almost
        entirely this call."""
        zv = self._dmargin_dot(v, batch)
        hv = self._backproject(c * zv, batch)
        hv = _maybe_psum(hv, self.axis_name)
        if self._has_l2:
            hv = hv + self.l2_weight * v
        return hv

    @jax.named_scope("objective_pass")
    def hessian_diagonal(self, w: jax.Array, batch: LabeledBatch) -> jax.Array:
        """diag(H) for coefficient variances
        (``TwiceDiffFunction.scala:179-394``, used by
        ``OptimizationProblem.updateCoefficientsVariances``)."""
        norm = self.normalization
        x = batch.features
        z = self.margins(w, batch)
        c = batch.effective_weights() * self.loss.d2(z, batch.labels)
        d_x2 = colsum(x, c, square=True)
        if norm.shifts is not None:
            d_x = colsum(x, c)
            s = norm.shifts
            diag = d_x2 - 2.0 * s * d_x + s * s * jnp.sum(c)
        else:
            diag = d_x2
        if norm.factors is not None:
            diag = diag * norm.factors**2
        diag = _maybe_psum(diag, self.axis_name)
        if self._has_l2:
            diag = diag + self.l2_weight
        return diag

    def _dense_hessian(self, w, batch, contract, caller: str) -> jax.Array:
        """X'^T diag(c) X' + l2 I with ``contract(x, c)`` as the sum over
        the rows: what :meth:`hessian_full` and :meth:`hessian_row_sum`
        share, which is all but the order of that sum."""
        norm = self.normalization
        if norm.shifts is not None:
            raise ValueError(
                f"{caller} supports scale-only normalization (whiten "
                "shifts change X densely; use hessian_vector instead)"
            )
        x = batch.features
        from photon_ml_tpu.ops.sparse import is_structured

        if is_structured(x):
            raise ValueError(f"{caller} requires dense features")
        z = self.margins(w, batch)
        c = batch.effective_weights() * self.loss.d2(z, batch.labels)
        h = contract(x, c)
        if norm.factors is not None:
            h = h * jnp.outer(norm.factors, norm.factors)
        h = _maybe_psum(h, self.axis_name)
        if self._has_l2:
            h = h + self.l2_weight * jnp.eye(w.shape[-1], dtype=h.dtype)
        return h

    @jax.named_scope("objective_pass")
    def hessian_full(self, w: jax.Array, batch: LabeledBatch) -> jax.Array:
        """The EXPLICIT (d, d) Hessian X'^T diag(c) X' + l2 I — only
        sensible for small d, where it is one MXU-friendly pass.

        The reference has no analog: on Spark a d^2 treeAggregate is
        prohibitive, which is why its only optimizers are L-BFGS and
        Hessian-VECTOR TRON. On TPU a d<=O(10^3) cross-product is trivial
        (n d^2 matmul FLOPs, d^2 output), enabling exact Newton steps —
        one pass replaces an entire inner CG loop. Dense features with
        scale-only (or no) normalization."""
        return self._dense_hessian(
            w, batch, lambda x, c: jnp.einsum("ni,n,nj->ij", x, c, x),
            "hessian_full",
        )

    @jax.named_scope("objective_pass")
    def hessian_row_sum(self, w: jax.Array, batch: LabeledBatch) -> jax.Array:
        """:meth:`hessian_full` as a sum over the batch's rows of c_n x_n
        x_n^T, by multiply and reduce in the features' own precision: for
        the small-d Newton solve of one entity's rows.

        Under ``vmap`` over entities the einsum of :meth:`hessian_full` is
        E matmuls of (d, r) x (r, d): the TPU runs them with the block,
        not the entities, on the lanes, after a transposing copy of the
        design, and in a bucket of many thin entities that is most of the
        solve (PERF.md section 6, PR 31). This form reads the design once
        in the layout it is stored in, entities minor, and stays in
        float32. Same restrictions as :meth:`hessian_full`."""

        def contract(x, c):
            with jax.named_scope("hessian_row_sum"):
                xc = x * c[:, None]
                return jnp.sum(xc[:, :, None] * x[:, None, :], axis=0)

        return self._dense_hessian(w, batch, contract, "hessian_row_sum")

    # -- variations ------------------------------------------------------

    def with_l2(self, l2_weight: float) -> "GLMObjective":
        return dataclasses.replace(self, l2_weight=l2_weight)

    def with_axis(self, axis_name: Optional[str]) -> "GLMObjective":
        return dataclasses.replace(self, axis_name=axis_name)

    def with_regularization(
        self, reg: RegularizationContext, reg_weight: float
    ) -> "GLMObjective":
        """``DiffFunction.withRegularization`` (``DiffFunction.scala:198-321``):
        L2 into the objective, L1 as an optimizer flag."""
        return dataclasses.replace(
            self,
            l2_weight=reg.l2_weight(reg_weight),
            l1_weight=reg.l1_weight(reg_weight),
        )
