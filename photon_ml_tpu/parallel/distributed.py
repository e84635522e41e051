"""Distributed (multi-chip) GLM training over a device mesh.

The reference's fixed-effect regime: examples partitioned across workers,
loss/grad/HVP partials tree-reduced, coefficients broadcast each iteration
(``function/ValueAndGradientAggregator.scala:204-220``,
``optimization/Optimizer.scala:142-151``). Here the WHOLE solve — solver
loop, line searches, CG, convergence — is one jitted SPMD computation over
the mesh: batch arrays arrive 'data'-sharded, coefficients replicated, and
XLA's partitioner inserts the all-reduces where the objective contracts
over the row axis. No per-iteration host round-trip, no broadcast cost.

Two entry points:
  - ``distributed_train_glm``: GSPMD path — jit + sharding constraints;
    collectives are inferred. The default.
  - ``shard_map_value_and_grad``: explicit-collective path — shard_map with
    the objective's ``axis_name`` psum, for when manual scheduling beats the
    partitioner (and as the analog of the reference's explicit
    treeAggregate contract, tested for equality like
    ``ObjectiveFunctionIntegTest``'s RDD-vs-local duality).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import dataclasses

import numpy as np

from photon_ml_tpu.core.types import Coefficients, LabeledBatch
from photon_ml_tpu.models.training import (
    GLMTrainingConfig,
    TrainedModel,
    train_glm,
)
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.parallel.mesh import (
    DATA_AXIS,
    FEATURE_AXIS,
    replicated,
    shard_batch,
)


def distributed_train_glm(
    batch: LabeledBatch,
    config: GLMTrainingConfig,
    mesh: Mesh,
    **kwargs,
) -> Sequence[TrainedModel]:
    """``train_glm`` with the batch sharded over the mesh's 'data' axis.

    The solver code is unchanged — that is the point: the reference needs
    two code paths (RDD vs Iterable, ``optimization/Optimizer.scala:163-212``);
    here distribution is a data-placement property. Results are bitwise
    deterministic for a fixed mesh shape.
    """
    sharded = shard_batch(batch, mesh)
    with jax.set_mesh(mesh):
        return train_glm(sharded, config, **kwargs)


def feature_sharded_train_glm(
    batch: LabeledBatch,
    config: GLMTrainingConfig,
    mesh: Mesh,
    initial_coefficients: Optional[Coefficients] = None,
    **kwargs,
) -> Sequence[TrainedModel]:
    """``train_glm`` with the design sharded over BOTH ('data', 'feature')
    axes and the coefficient vector sharded over 'feature' — the huge-d
    regime (hundreds of billions of coefficients, README.md:58) where
    replicating w per device is impossible. Margins contract over the
    sharded feature axis (XLA inserts the psum); the gradient/CG vectors
    inherit w's sharding through the jitted solver, so the whole solve is
    SPMD with coefficient state split across devices.

    Dense designs shard by contiguous column pad; SPARSE (padded-ELL)
    designs are column-BLOCKED into a ``FeatureShardedSparse`` container
    (round-robin columns -> blocks, local ids per block) so the gradient /
    CG scatter targets are each device's local coefficient block — the
    sparse analog of the reference's per-block aggregation
    (``function/ValueAndGradientAggregator.scala:204-220``) at the
    >200k-feature scale of ``util/PalDBIndexMap.scala:43``.

    Normalization and box constraints are supported in both cases: the
    (d,)-vectors they carry (factors, shifts, bounds, intercept position)
    are re-laid-out into the blocked coefficient space, exactly as the
    reference's normalization algebra rides its aggregators unchanged
    (``normalization/NormalizationContext.scala:41-151``). Rows pad to
    the 'data' extent; columns added by blocking/padding solve to 0 and
    are dropped from the returned coefficients.

    Collectives (PR 5, the BENCH_r05 ``sparse_fs_scaling`` 2-device
    regression chase): each objective pass used to pay one all-reduce
    per feature-space reduction — the (n,) margin block-sum, the L2
    value dot w.w, the normalization margin shift — so a normalized L2
    solve paid up to 4 per pass. The objective now coalesces them: all
    scalar feature-space dots CONCATENATE onto the margin partials and
    reduce in ONE bucketed all-reduce
    (``ops.sparse.matvec_and_feature_dots``; on by default via
    ``GLMObjective.fuse_feature_reductions``), and the value/grad psums
    of the explicit-collective path fused into one tuple psum. The
    before/after collective counts are machine-readable in the bench's
    cost book (``sparse.objective_pass`` vs
    ``sparse.objective_pass_unfused`` per mesh width F).
    """
    from photon_ml_tpu.ops import sparse as sparse_ops

    if sparse_ops.is_hybrid(batch.features):
        raise ValueError(
            "feature sharding takes dense or ELL (SparseFeatures) designs; "
            "hybrid containers are a single-chip layout — pass the ELL"
        )
    if sparse_ops.is_feature_sharded(batch.features):
        raise ValueError(
            "feature sharding takes dense or ELL (SparseFeatures) designs; "
            "the batch is already column-blocked — pass the pre-blocking ELL "
            "(blocking is internal to feature_sharded_train_glm)"
        )

    n_rows_shards = mesh.shape[DATA_AXIS]
    n_col_shards = mesh.shape[FEATURE_AXIS]
    d = batch.num_features
    n = batch.batch_size
    n_pad = -(-n // n_rows_shards) * n_rows_shards
    row_spec = NamedSharding(mesh, P(DATA_AXIS))

    if sparse_ops.is_sparse(batch.features):
        # PHOTON_COLLECTIVE_MODE=overlap row-balances the blocked
        # container (stored slots track entries, not the max lane —
        # the slot-inflation term); the balanced virtual-row
        # scatter routes within a block, so it requires the row axis
        # unsharded. fused keeps the PR-5 flat layout as the
        # equivalence oracle (docs/PARALLEL.md).
        from photon_ml_tpu.parallel.overlap import collective_mode

        balance = (
            collective_mode() == "overlap"
            and n_rows_shards == 1
            and n_col_shards > 1
        )
        blocked = sparse_ops.shard_columns(
            batch.features, n_col_shards, balance_rows=balance
        )
        col_map = sparse_ops.blocked_column_map(d, n_col_shards)
        d_block = n_col_shards * blocked.d_shard
        padded = LabeledBatch.pad_to(
            dataclasses.replace(batch, features=blocked), n_pad
        )
        feat_spec = NamedSharding(mesh, P(DATA_AXIS, FEATURE_AXIS, None))
    else:
        d_block = -(-d // n_col_shards) * n_col_shards
        col_map = np.arange(d, dtype=np.int64)
        padded = LabeledBatch.pad_to(batch, n_pad)
        padded = dataclasses.replace(
            padded,
            features=jnp.pad(padded.features, ((0, 0), (0, d_block - d))),
        )
        feat_spec = NamedSharding(mesh, P(DATA_AXIS, FEATURE_AXIS))

    def _place_feature_leaf(x):
        # a balanced container's (V, F) row map shards over 'feature'
        # only; the 3-D indices/values keep the full spec
        if np.ndim(x) == 2 and sparse_ops.is_feature_sharded(
            padded.features
        ):
            return jax.device_put(
                x, NamedSharding(mesh, P(None, FEATURE_AXIS))
            )
        return jax.device_put(x, feat_spec)

    padded = LabeledBatch(
        features=jax.tree_util.tree_map(
            _place_feature_leaf, padded.features
        ),
        labels=jax.device_put(padded.labels, row_spec),
        offsets=jax.device_put(padded.offsets, row_spec),
        weights=jax.device_put(padded.weights, row_spec),
        mask=jax.device_put(padded.mask, row_spec),
    )

    def block_vector(v, fill):
        # returned as a plain array: GLMTrainingConfig.__post_init__ wraps
        # it in a content-hashed HashableBounds, so the d_block-length
        # blocked bounds never hash/compare elementwise in the solver cache
        if v is None:
            return None
        out = np.full((d_block,), fill, dtype=float)
        out[col_map] = np.asarray(v, dtype=float)
        return out

    blocked_config = dataclasses.replace(
        config,
        intercept_index=(
            None
            if config.intercept_index is None
            else int(col_map[config.intercept_index])
        ),
        lower_bounds=block_vector(config.lower_bounds, -np.inf),
        upper_bounds=block_vector(config.upper_bounds, np.inf),
    )

    dtype = np.dtype(jnp.promote_types(padded.features.dtype, jnp.float32))
    if initial_coefficients is not None:
        w0_host = np.zeros((d_block,), dtype)
        w0_host[col_map] = np.asarray(initial_coefficients.means, dtype)
        init = Coefficients(
            means=jax.device_put(
                jnp.asarray(w0_host), NamedSharding(mesh, P(FEATURE_AXIS))
            )
        )
    else:
        init = Coefficients(
            means=jax.device_put(
                jnp.zeros((d_block,), dtype),
                NamedSharding(mesh, P(FEATURE_AXIS)),
            )
        )
    with jax.set_mesh(mesh):
        models = train_glm(
            padded, blocked_config, initial_coefficients=init, **kwargs
        )
    # map every returned model back to the original column order
    unblock = jnp.asarray(col_map)
    out = []
    for tm in models:
        coef = tm.model.coefficients
        coef = dataclasses.replace(
            coef,
            means=coef.means[unblock],
            variances=(
                None
                if coef.variances is None
                else coef.variances[unblock]
            ),
        )
        out.append(
            dataclasses.replace(
                tm, model=tm.model.with_coefficients(coef)
            )
        )
    return out


def hierarchical_value_and_grad(objective: GLMObjective, mesh: Mesh):
    """Explicit-collective value+grad over a 2-D ('host', 'device') mesh
    with the HIERARCHICAL reduction order (docs/PARALLEL.md): per-shard
    partials reduce-scatter over the fast intra-host axis first, the
    1/D shards all-reduce over DCN, and one intra-host all-gather
    re-replicates — ``parallel.multihost.hierarchical_psum`` applied to
    the same (value, gradient) tuple ``shard_map_value_and_grad`` psums
    flat. Returns f(w, sharded_batch) -> (val, grad), rows sharded over
    both axes flattened (``mesh.batch_sharding``). Equivalence with the
    flat psum path is drilled <= 1e-12 in tests/test_partition.py."""
    from photon_ml_tpu.parallel.mesh import DEVICE_AXIS, HOST_AXIS
    from photon_ml_tpu.parallel.multihost import hierarchical_psum

    if HOST_AXIS not in mesh.axis_names or DEVICE_AXIS not in mesh.axis_names:
        raise ValueError(
            f"hierarchical_value_and_grad needs a ('{HOST_AXIS}', "
            f"'{DEVICE_AXIS}') mesh (make_host_device_mesh); got axes "
            f"{mesh.axis_names}"
        )
    # L2 applies to the REPLICATED w once, after the reduction — the
    # shard-local objective must produce pure data partials (the same
    # split objective.value_grad_curvature makes around its psum)
    obj0 = dataclasses.replace(objective, axis_name=None, l2_weight=0.0)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), P((HOST_AXIS, DEVICE_AXIS))),
        out_specs=(P(), P()),
        # the replication checker cannot see through the
        # psum_scatter -> psum -> all_gather chain (it infers 'host'
        # replication from the psum but not the gathered 'device' axis);
        # the outputs ARE replicated by construction
        check_vma=False,
    )
    def vg(w, batch: LabeledBatch):
        val, grad = obj0.value_and_grad(w, batch)
        val, grad = hierarchical_psum(
            (val, grad), intra_axis=DEVICE_AXIS, inter_axis=HOST_AXIS
        )
        if not (
            isinstance(objective.l2_weight, (int, float))
            and objective.l2_weight == 0.0
        ):
            val = val + 0.5 * objective.l2_weight * jnp.vdot(w, w)
            grad = grad + objective.l2_weight * w
        return val, grad

    return vg


def _eager_and_traced(*args) -> bool:
    """True when we are on the HOST side of a dispatch (no argument is a
    jit tracer) AND an obs tracer is active — the only situation where
    wrapping a collective dispatch in a blocking profile window is both
    meaningful and paid for by someone who asked for it."""
    from photon_ml_tpu import obs

    if obs.get_tracer() is None:
        return False
    return not any(
        isinstance(leaf, jax.core.Tracer)
        for leaf in jax.tree_util.tree_leaves(args)
    )


def shard_map_value_and_grad(
    objective: GLMObjective, mesh: Mesh
):
    """Explicit-collective value+grad: shard_map over 'data' with in-kernel
    psum (``objective.axis_name``). Returns f(w, sharded_batch) -> (val, grad)
    with replicated outputs.

    Collective profiling (``obs.collectives``): an EAGER call under an
    active tracer blocks on the result and records one
    ``collective.psum.value_and_grad.w<N>`` span +
    ``collective.psum.value_and_grad.w<N>.{count,bytes,wall_ms}``
    metrics, N = the 'data' mesh width and bytes = the psum payload
    (value scalar + gradient). Calls from inside a jit trace — and every
    untraced call — take the raw path unchanged: profiling must never
    alter the async dispatch semantics of a run nobody is observing.
    """
    obj = objective.with_axis(DATA_AXIS)
    width = mesh.shape[DATA_AXIS]

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), P(DATA_AXIS)),
        out_specs=(P(), P()),
    )
    def vg_raw(w, batch: LabeledBatch):
        return obj.value_and_grad(w, batch)

    def vg(w, batch: LabeledBatch):
        if not _eager_and_traced(w, batch):
            return vg_raw(w, batch)
        from photon_ml_tpu.obs import collectives as obs_coll

        nbytes = (int(np.size(w)) + 1) * np.dtype(
            getattr(w, "dtype", np.float64)
        ).itemsize
        with obs_coll.collective_span(
            "psum.value_and_grad", mesh_width=width, nbytes=nbytes
        ):
            out = jax.block_until_ready(vg_raw(w, batch))
        return out

    return vg
