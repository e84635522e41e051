"""Collective strategy knob + the chunked overlap reduction schedule.

On 8 virtual CPU devices ``sparse_fs_scaling`` still scaled INVERSELY
after PR 5 coalesced the per-pass collective COUNT to one (a CPU
timing; real chips not measured). Two distinct costs remained, and this
module owns the strategy that removes both:

1. **The reduction schedule.** The coalesced formulation issues ONE
   bucketed all-reduce of the whole (n + P,) payload at the END of the
   objective pass — the reduction cannot start until the last row block
   is contracted, and nothing computes while it drains. The ``overlap``
   strategy chunks the row axis: each chunk's block-partials reduce via
   a reduce-scatter issued as soon as THAT chunk is contracted, with one
   trailing all-gather reassembling the replicated margins. Dataflow
   between chunk *i*'s reduction and chunk *i+1*'s contraction is
   independent, which is exactly what lets XLA's async collectives run
   the wire under the next chunk's compute on real ICI (the PR-8
   superpass made whole passes one program, so the scheduler can
   actually see across the pass).

2. **The blocked-ELL padding inflation.** ``ops.sparse.shard_columns``
   pads every (row, block) lane to the DATASET max entry count; at
   width 8 a mean-4 lane pads to the max ~15 and the stored slot count
   (the irregular-access cost driver) inflates ~3.7x —
   the dominant inverse-scaling term measured on the bench box. The
   ``overlap`` strategy row-balances the blocked container
   (``shard_columns(..., balance_rows=True)``): each block packs its
   entries into width-k0 *virtual rows* (a row with c entries occupies
   ceil(c/k0) of them), so padded slots track the actual entry count
   instead of the max row.

``PHOTON_COLLECTIVE_MODE`` selects the strategy:

- ``overlap`` (default): balanced layout + chunked
  reduce-scatter/all-gather pipeline.
- ``fused``: the PR-5 formulation exactly — max-width blocked ELL and
  one trailing bucketed all-reduce. Kept as the EQUIVALENCE ORACLE:
  ``overlap`` must match it to <= 1e-6 (f32) / 1e-10 (f64) per pass and
  per solve (tests/test_partition.py), and bench_overlap records the
  fused-vs-overlap pass wall and ``collective_wall_frac`` per width so
  the win is gated, not asserted.

The chunked schedule only activates under an ACTIVE mesh that carries
the 'feature' axis (``jax.set_mesh``); everywhere else both
modes lower to the identical local sum, so single-device numerics are
bit-for-bit unchanged.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

__all__ = [
    "COLLECTIVE_MODE_ENV",
    "OVERLAP_CHUNKS_ENV",
    "COLLECTIVE_MODES",
    "collective_mode",
    "overlap_chunks",
    "feature_block_sum",
]

COLLECTIVE_MODE_ENV = "PHOTON_COLLECTIVE_MODE"
OVERLAP_CHUNKS_ENV = "PHOTON_OVERLAP_CHUNKS"
COLLECTIVE_MODES = ("fused", "overlap")

# Row-axis chunks of the overlapped reduce-scatter pipeline. More chunks
# = finer compute/communication interleave but more collective launches;
# 4 keeps each chunk's payload large enough that launch overhead stays
# noise while the tail exposure (the last chunk's reduction, which
# nothing can hide under) shrinks 4x vs the fused single shot.
_DEFAULT_CHUNKS = 4


def collective_mode() -> str:
    """The validated ``PHOTON_COLLECTIVE_MODE`` (default ``overlap``)."""
    mode = (
        os.environ.get(COLLECTIVE_MODE_ENV, "overlap").strip().lower()
        or "overlap"
    )
    if mode not in COLLECTIVE_MODES:
        raise ValueError(
            f"{COLLECTIVE_MODE_ENV}={mode!r}: expected one of "
            f"{COLLECTIVE_MODES}"
        )
    return mode


def overlap_chunks() -> int:
    """Row-axis chunk count of the overlap pipeline (>= 1)."""
    try:
        c = int(os.environ.get(OVERLAP_CHUNKS_ENV, _DEFAULT_CHUNKS))
    except ValueError:
        return _DEFAULT_CHUNKS
    return max(1, c)


def _feature_axis_sharding(axis_name: str):
    """(per-chunk sharded, replicated) NamedShardings over the mesh that
    ``jax.set_mesh`` installed, or None when it has no ``axis_name`` axis
    of extent >= 2. Readable from inside a jit trace."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    if mesh.shape.get(axis_name, 1) < 2:
        return None
    return NamedSharding(mesh, P(axis_name)), NamedSharding(mesh, P())


def feature_block_sum(
    payload: jax.Array, axis_name: str = "feature"
) -> jax.Array:
    """``sum(payload, axis=0)`` of an (F, m) per-block partials payload —
    THE feature-space reduction of an objective pass — under the
    configured collective strategy.

    fused (or no mesh / no 'feature' axis / one chunk): one trailing
    sum, which the partitioner lowers to the PR-5 single bucketed
    all-reduce when the block axis is sharded.

    overlap: the m axis splits into ``overlap_chunks()`` chunks; each
    chunk sums over blocks into an output CONSTRAINED sharded over the
    feature axis (the partitioner lowers a sharded-output cross-replica
    sum to a reduce-scatter), and the concatenated result re-replicates
    through one trailing all-gather. Chunk *i*'s reduce-scatter has no
    dataflow edge to chunk *i+1*'s compute, so XLA's async collective
    scheduler runs them concurrently on hardware with a DMA engine.

    Per-element operand sets are identical in both schedules, so the
    modes agree to f32 rounding (<= 1e-6; drilled in
    tests/test_partition.py)."""
    if payload.ndim != 2:
        raise ValueError(
            f"feature_block_sum takes (F, m) block partials; got shape "
            f"{payload.shape}"
        )
    chunks = overlap_chunks()
    if collective_mode() != "overlap" or chunks < 2:
        return jnp.sum(payload, axis=0)
    shardings = _feature_axis_sharding(axis_name)
    if shardings is None:
        return jnp.sum(payload, axis=0)
    sharded, replicated = shardings
    m = payload.shape[1]
    if m < chunks:
        chunks = max(1, m)
    bounds = [round(j * m / chunks) for j in range(chunks + 1)]
    parts = []
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        if hi <= lo:
            continue
        part = jnp.sum(payload[:, lo:hi], axis=0)
        parts.append(jax.lax.with_sharding_constraint(part, sharded))
    out = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
    return jax.lax.with_sharding_constraint(out, replicated)
