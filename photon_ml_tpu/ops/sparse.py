"""Sparse (wide) feature batches: padded row-wise (ELL) format + kernels.

The reference reaches >200k-feature spaces with Breeze sparse vectors and an
off-heap feature index (``util/PalDBIndexMap.scala:43``; tree-aggregation
depth bumps above 200k features, ``cli/game/training/Driver.scala:336-341``).
On TPU, CSR's ragged rows are hostile to XLA's static shapes, so the batch
format here is ELL: every row holds up to ``k`` (column, value) pairs, padded
with column id ``d`` (one past the last feature) and value 0 — padding is
algebraically invisible because gathers fill 0 and scatters drop
out-of-bounds ids. The three kernels below are exactly the contractions the
dense objective needs (margins, gradient back-projection, Hessian diagonal),
so ``GLMObjective`` runs unchanged on either representation:

    matvec:   z_i = sum_k v_ik * w[c_ik]              (gather + row reduce)
    rmatvec:  g_j = sum_{ik: c_ik=j} v_ik * a_i       (scatter-add)
    colsum:   s_j = sum_{ik: c_ik=j} f(v_ik) * c_i    (scatter-add)

All are single XLA ops (gather / scatter-add) that shard cleanly over the
'data' mesh axis: indices/values are row-leading, so batch sharding and the
psum-reduced partials work exactly as for dense features.

Each contraction has ONE implementation per container, chosen by the
container's type and nothing else: plain ELL (``SparseFeatures``) is the
XLA gather / scatter-add above; the hybrid container adds a dense slab
product for its hot columns and contracts all of its cold segments with
one flat gather / scatter-add (``_cold_matvec``); the feature-sharded
container scatters per block. Every consumer (``GLMObjective``, GAME
random-effect batches, serving scorers) calls ``matvec`` / ``rmatvec`` /
``colsum`` and never names a lowering.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from photon_ml_tpu.ops.bucketing import (
    split_histogram_minimizing_padding,
    split_minimizing_padding,
)


@dataclasses.dataclass(frozen=True)
class SparseFeatures:
    """(n, k) padded sparse design matrix with static width ``d``.

    indices: (n, k) int32 column ids; padding slots hold ``d`` (out of
             bounds — gather-fills 0.0, scatter-drops).
    values:  (n, k) float payloads; padding slots hold 0.0.
    d:       number of feature columns (static aux data, not a leaf).
    """

    indices: jax.Array
    values: jax.Array
    d: int

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.indices.shape[-2], self.d)

    @property
    def ndim(self) -> int:  # row-leading container, like a (n, d) matrix
        return 2

    @property
    def dtype(self):
        return self.values.dtype

    @property
    def nnz_per_row(self) -> int:
        return self.indices.shape[-1]

    def __matmul__(self, w: jax.Array) -> jax.Array:
        return matvec(self, w)


def _flatten(sf: SparseFeatures):
    return (sf.indices, sf.values), sf.d


def _unflatten(d, children):
    return SparseFeatures(indices=children[0], values=children[1], d=d)


jax.tree_util.register_pytree_node(SparseFeatures, _flatten, _unflatten)


@dataclasses.dataclass(frozen=True)
class HybridFeatures:
    """Power-law split of a sparse matrix: dense slab for the hot columns,
    row-bucketed padded-ELL for the cold tail.

    On the v5e XLA's gather costs 8.0 ns and its scatter-add (with the
    sort it lowers to) 10.6 ns a stored ELL SLOT, padding included,
    whichever slot it is (ledger, PR 26, ``glm_hashed_sparse.solve``:
    9.12 s and 12.15 s over 28 evaluations of 40.9 M slots; r5 had read
    "~8 ns"), while a dense slab column costs one HBM pass (n * 4 bytes
    at the 670 GB/s a slab product reads at; my chip run, PR 27)
    regardless of sparsity — so any column with enough
    entries is cheaper densified (the "feature-hashing into dense-ish
    blocks" direction of SURVEY §7 hard-part 3). The rates and the
    break-even live in one place: ``_SPLIT_RATES`` and
    ``hot_column_break_even``. CTR-style feature data is Zipf-distributed,
    so a small slab absorbs most entries: ``split_hot_cold`` makes the
    split on the device from the design's own column counts, and
    ``train_glm`` asks it on every plain padded-ELL design.

    Because the irregular cost scales with padded SLOTS, the cold tail is
    additionally row-bucketed: rows sorted by cold-entry count, split
    into contiguous segments by the same exact-DP padding minimizer the
    GAME random-effect designs use (``game/data.py``), each segment an
    ELL at its own width. Rows therefore live in a PERMUTED order;
    ``row_perm[i]`` is the original index of stored row i. Training is
    row-order-invariant — callers permute the rest of the batch once at
    construction (labels, offsets, weights, mask) and everything else
    follows.

    dense:         (n, H) slab holding the hot columns (stored row order).
    hot_ids:       (H,) int32 original column ids of the slab columns.
    cold_segments: contiguous-row ELL segments over the SAME d covering
                   all n rows in stored order (hot columns never appear).
    row_perm:      (n,) int32 stored-row -> original-row map.
    """

    dense: jax.Array
    hot_ids: jax.Array
    cold_segments: Tuple[SparseFeatures, ...]
    row_perm: jax.Array

    @property
    def d(self) -> int:
        return self.cold_segments[0].d

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.dense.shape[-2], self.d)

    @property
    def ndim(self) -> int:
        return 2

    @property
    def dtype(self):
        return self.dense.dtype

    def segment_bounds(self) -> Tuple[Tuple[int, int], ...]:
        """[(lo, hi)) stored-row ranges, one per cold segment (static)."""
        bounds = []
        lo = 0
        for seg in self.cold_segments:
            hi = lo + seg.indices.shape[-2]
            bounds.append((lo, hi))
            lo = hi
        return tuple(bounds)

    def __matmul__(self, w: jax.Array) -> jax.Array:
        return matvec(self, w)


def _flatten_hybrid(hf: HybridFeatures):
    return (hf.dense, hf.hot_ids, hf.cold_segments, hf.row_perm), None


def _unflatten_hybrid(_, children):
    return HybridFeatures(
        dense=children[0],
        hot_ids=children[1],
        cold_segments=tuple(children[2]),
        row_perm=children[3],
    )


jax.tree_util.register_pytree_node(
    HybridFeatures, _flatten_hybrid, _unflatten_hybrid
)


@dataclasses.dataclass(frozen=True)
class FeatureShardedSparse:
    """Column-blocked padded-ELL for coefficient-sharded (huge-d) solves.

    The regime of the reference's off-heap coefficient index
    (``util/PalDBIndexMap.scala:43-212``, "hundreds of billions of
    coefficients" ``README.md:58``): w no longer fits replicated, so the
    scatter TARGET — coefficients, gradient, CG vectors — must shard over
    the 'feature' mesh axis. A flat ELL cannot express that (each row's
    column ids cross shard boundaries), so entries are grouped by column
    BLOCK: block f holds the original columns ``{c : c % F == f}``
    (round-robin, so frequency-sorted vocabularies balance), stored with
    LOCAL ids ``c // F``.

    indices: (n, F, k) int32 local column ids; padding slots hold
             ``d_shard`` (out of local bounds: gather-fills 0, scatter-drops).
    values:  (n, F, k) float payloads; padding slots hold 0.0.
    d_shard: columns per block (static). Solver-visible width = F * d_shard.
    d_orig:  pre-blocking column count (static; blocked positions >= it in
             no block are real columns — they solve to exactly 0).

    Sharded P('data', 'feature', None) on a ('data', 'feature') mesh, every
    kernel is SPMD with NO communication except one O(n) psum of margin
    partials over 'feature' (matvec's block sum): the gather/scatter run
    against each device's LOCAL (d_shard,) coefficient block — XLA
    partitions the vmapped gather/scatter along the block axis, which is a
    batch dimension on both operand and indices. This is the TPU analog of
    the reference's per-feature-block aggregation
    (``function/ValueAndGradientAggregator.scala:204-220``).

    ROW-BALANCED layout (``shard_columns(..., balance_rows=True)``, the
    ``PHOTON_COLLECTIVE_MODE=overlap`` strategy): the flat layout pads
    every (row, block) lane to the DATASET max per-block entry count, so
    at width F the stored slots — the irregular-access cost driver —
    inflate toward max/mean over rows (measured 3.7x at F=8 on the
    bench workload — a count, platform-independent). Balanced blocks instead pack each block's
    entries into width-``k`` VIRTUAL rows: a row with c entries in
    block f occupies ceil(c/k) of them, and ``row_map[v, f]`` records
    the original row virtual row v of block f contributes to (sentinel
    ``num_rows`` = empty pad lane: gathers fill 0, scatters drop).
    Margins then need one extra per-block scatter-add of the virtual-row
    partials into (n,) — O(V) — against an O(slots) padding saving.

    row_map:  (V, F) int32 virtual row -> original row, or None for the
              flat layout.
    num_rows: logical row count n when ``row_map`` is set (the leading
              axis is V, not n).
    aligned_rows: the first ``aligned_rows`` virtual rows are IDENTITY
              mapped (virtual row v holds row v's first <= k entries in
              every block), so their margin partials need no scatter
              routing and their back-projection weights no gather — the
              O(V) routing cost only touches the overflow tail.
    """

    indices: jax.Array
    values: jax.Array
    d_shard: int
    d_orig: int
    row_map: Optional[jax.Array] = None
    num_rows: Optional[int] = None
    aligned_rows: int = 0

    @property
    def num_blocks(self) -> int:
        return self.indices.shape[-2]

    @property
    def is_balanced(self) -> bool:
        return self.row_map is not None

    @property
    def shape(self) -> Tuple[int, int]:
        # solver-visible width: the blocked coefficient vector
        rows = (
            self.num_rows
            if self.num_rows is not None
            else self.indices.shape[-3]
        )
        return (rows, self.num_blocks * self.d_shard)

    @property
    def ndim(self) -> int:
        return 2

    @property
    def dtype(self):
        return self.values.dtype

    def __matmul__(self, w: jax.Array) -> jax.Array:
        return matvec(self, w)


def _flatten_fsharded(fs: FeatureShardedSparse):
    return (
        (fs.indices, fs.values, fs.row_map),
        (fs.d_shard, fs.d_orig, fs.num_rows, fs.aligned_rows),
    )


def _unflatten_fsharded(aux, children):
    return FeatureShardedSparse(
        indices=children[0],
        values=children[1],
        d_shard=aux[0],
        d_orig=aux[1],
        row_map=children[2],
        num_rows=aux[2],
        aligned_rows=aux[3],
    )


jax.tree_util.register_pytree_node(
    FeatureShardedSparse, _flatten_fsharded, _unflatten_fsharded
)


# -- kernels (dispatch on representation) -----------------------------------


def is_sparse(x) -> bool:
    return isinstance(x, SparseFeatures)


def is_hybrid(x) -> bool:
    return isinstance(x, HybridFeatures)


def is_feature_sharded(x) -> bool:
    return isinstance(x, FeatureShardedSparse)


def is_structured(x) -> bool:
    """Any non-plain-array representation this module owns."""
    return is_sparse(x) or is_hybrid(x) or is_feature_sharded(x)


def cast_values(x, dtype):
    """Representation-preserving device cast: plain arrays, ELL values,
    or hybrid slab+cold values to ``dtype``. The one place that knows how
    to move every feature container to the device at a target precision."""
    if is_hybrid(x):
        return dataclasses.replace(
            x,
            dense=jnp.asarray(x.dense, dtype),
            cold_segments=tuple(
                dataclasses.replace(
                    seg, values=jnp.asarray(seg.values, dtype)
                )
                for seg in x.cold_segments
            ),
        )
    if is_feature_sharded(x):
        return dataclasses.replace(
            x,
            indices=jnp.asarray(x.indices),
            values=jnp.asarray(x.values, dtype),
            row_map=(
                None if x.row_map is None else jnp.asarray(x.row_map)
            ),
        )
    if is_sparse(x):
        return dataclasses.replace(
            x,
            indices=jnp.asarray(x.indices),
            values=jnp.asarray(x.values, dtype),
        )
    return jnp.asarray(x, dtype)


def _low_precision_dot(x: jax.Array, w: jax.Array):
    """x @ w keeping the CONTRACTION in the matrix's low precision with
    f32 accumulation (``preferred_element_type``) when the design is
    stored bf16/f16. Without this, jnp's type promotion upcasts the
    matrix to the vector's f32 — and XLA MATERIALIZES the converted
    design as a temp, so every pass pays ~3 extra design-sized HBM
    round trips (measured on chip in r5: the dense TRON solve ran
    6.0 ms/pass where the roofline pass is ~1.2 ms). The vector rounds
    to bf16 — the same precision class the stored design already
    imposes (coefficients agree with the all-f32 solve to ~2e-4).
    Full-precision designs are untouched."""
    low_x = x.dtype in (jnp.bfloat16, jnp.float16)
    low_w = w.dtype in (jnp.bfloat16, jnp.float16)
    if low_x != low_w:  # mixed precision: round the f32 side DOWN
        if low_x:
            w = w.astype(x.dtype)
        else:
            x = x.astype(w.dtype)
        return jnp.matmul(x, w, preferred_element_type=jnp.float32)
    return x @ w


def _slab_dot(x: jax.Array, w: jax.Array):
    """A hybrid slab product. A full-precision slab contracts at
    ``Precision.HIGHEST``: left to the default, the TPU may send a float32
    product through the MXU in one bfloat16 pass, which is another result
    (the benchmark's bf16 control reads ``value_gap`` 2.9e-3 against a
    limit of 3e-6), not a faster one. A slab stored low goes the way of
    every low-precision design (``_low_precision_dot``)."""
    low = (jnp.bfloat16, jnp.float16)
    if x.dtype in low or w.dtype in low:
        return _low_precision_dot(x, w)
    return jnp.matmul(x, w, precision=lax.Precision.HIGHEST)


def _block_margin_partials(x: "FeatureShardedSparse", w: jax.Array):
    """(F, n) per-block margin partials of a blocked container — the
    payload whose block-axis sum is THE feature-space reduction of every
    objective pass (``parallel.overlap.feature_block_sum`` owns the
    fused-vs-overlap schedule). Flat layout: gather + row reduce.
    Balanced layout: gather + virtual-row reduce + one per-block
    scatter-add routing virtual rows to their original rows (sentinel
    lanes drop)."""
    w2 = w.reshape(x.num_blocks, x.d_shard)
    gathered = jax.vmap(  # per-block local gather; block axis = batch dim
        lambda wf, idxf: wf.at[idxf].get(mode="fill", fill_value=0.0),
        in_axes=(0, 1),
        out_axes=1,
    )(w2, x.indices)
    if not x.is_balanced:
        return jnp.einsum("nfk,nfk->fn", x.values, gathered)
    partial_v = jnp.einsum("vfk,vfk->fv", x.values, gathered)  # (F, V)
    n = x.shape[0]
    a = x.aligned_rows
    if a:
        # identity head: virtual row v == row v, no routing; only the
        # overflow tail scatters (its rows route by row_map)
        head = partial_v[:, :a]
        if a < n:
            head = jnp.pad(head, ((0, 0), (0, n - a)))
        if partial_v.shape[1] == a:
            return head

        def route_tail(pv, rm):
            return jnp.zeros((n,), pv.dtype).at[rm].add(pv, mode="drop")

        tail = jax.vmap(route_tail)(
            partial_v[:, a:], x.row_map[a:].T
        )
        return head + tail

    def route(pv, rm):
        return jnp.zeros((n,), pv.dtype).at[rm].add(pv, mode="drop")

    return jax.vmap(route)(partial_v, x.row_map.T)


def _cold_matvec(x: "HybridFeatures", w: jax.Array) -> jax.Array:
    """Margins of the cold segments, in stored row order: ONE gather over
    the slots of all segments, then each segment's row sums. One gather
    (and in ``_cold_scatter`` one scatter-add with its one sort) a pass,
    however many row buckets: XLA compiles each such op for seconds, so a
    gather and a scatter a segment made the solve program compile for
    minutes (PERF.md section 6, PR 27)."""
    segs = x.cold_segments
    with jax.named_scope("sparse_gather"):
        gathered = w.at[_cold_flat_indices(segs)].get(
            mode="fill", fill_value=0.0
        )
        rows, lo = [], 0
        for seg in segs:
            hi = lo + seg.indices.size
            mine = gathered[lo:hi].reshape(seg.indices.shape[::-1])
            rows.append(jnp.sum(seg.values.T * mine, axis=0))
            lo = hi
        return jnp.concatenate(rows)


def _cold_flat_indices(segs) -> jax.Array:
    """The column ids of every slot of the cold segments as one vector,
    each segment SLOT-major (its first slots of all rows, then its second
    slots, ...). A gather or a scatter-add does not care in which order
    it meets the slots, and the TPU keeps a (rows, few) array with the
    rows along the lanes: slot-major is the order the segment is stored
    in, row-major a transposing copy of it."""
    return jnp.concatenate([seg.indices.T.reshape(-1) for seg in segs])


def _cold_scatter(x: "HybridFeatures", a: jax.Array, payload):
    """(d,) sum over the cold segments' slots of ``payload(seg) * a_row``
    by ONE scatter-add (see ``_cold_matvec``). ``a`` is in stored row
    order."""
    segs = x.cold_segments
    with jax.named_scope("sparse_scatter"):
        upd = jnp.concatenate([
            (payload(seg).T * a[lo:hi]).reshape(-1)
            for (lo, hi), seg in zip(x.segment_bounds(), segs)
        ])
        return (
            jnp.zeros((x.d,), upd.dtype)
            .at[_cold_flat_indices(segs)]
            .add(upd, mode="drop")
        )


def matvec(x, w: jax.Array) -> jax.Array:
    """margins contraction: (n, d) @ (d,) -> (n,). Hybrid output is in
    STORED (permuted) row order, matching the permuted batch."""
    if is_feature_sharded(x):
        from photon_ml_tpu.parallel.overlap import feature_block_sum

        with jax.named_scope("sparse_gather"):
            return feature_block_sum(_block_margin_partials(x, w))
    if is_hybrid(x):
        # dtype promotion mirrors the dense path (bf16 slab @ f32 w -> f32)
        return _slab_dot(x.dense, w[x.hot_ids]) + _cold_matvec(x, w)
    if not is_sparse(x):
        return _low_precision_dot(x, w)
    with jax.named_scope("sparse_gather"):
        gathered = w.at[x.indices].get(mode="fill", fill_value=0.0)
        return jnp.sum(x.values * gathered, axis=-1)


def rmatvec(x, a: jax.Array) -> jax.Array:
    """gradient back-projection: (n, d)^T @ (n,) -> (d,). Hybrid `a` is
    in stored row order."""
    if is_feature_sharded(x):
        with jax.named_scope("sparse_scatter"):
            return _rmatvec_feature_sharded(x, a)
    if is_hybrid(x):
        g = _cold_scatter(x, a, lambda seg: seg.values)
        return g.at[x.hot_ids].add(_slab_dot(a, x.dense))
    if not is_sparse(x):
        return _low_precision_dot(x.T, a)
    with jax.named_scope("sparse_scatter"):
        upd = (x.values * a[..., None]).reshape(-1)
        return (
            jnp.zeros((x.d,), upd.dtype)
            .at[x.indices.reshape(-1)]
            .add(upd, mode="drop")
        )


def _rmatvec_feature_sharded(x, a: jax.Array) -> jax.Array:
    """``rmatvec`` of a feature-sharded design: a per-block local scatter
    into the block's coefficients."""
    if x.is_balanced:
        # route each virtual row's weight from its original row; the
        # identity-aligned head broadcasts straight from ``a``
        # (sentinel lanes gather-fill 0, so their slots contribute 0)
        al = x.aligned_rows
        if al:
            head = jnp.broadcast_to(
                a[:al, None], (al, x.num_blocks)
            )
            tail = a.at[x.row_map[al:]].get(
                mode="fill", fill_value=0.0
            )
            a_v = jnp.concatenate([head, tail], axis=0)
        else:
            a_v = a.at[x.row_map].get(mode="fill", fill_value=0.0)
        upd = x.values * a_v[..., None]
    else:
        upd = x.values * a[:, None, None]
    g2 = jax.vmap(  # per-block local scatter into the block's coefficients
        lambda idxf, updf: jnp.zeros((x.d_shard,), updf.dtype)
        .at[idxf.reshape(-1)]
        .add(updf.reshape(-1), mode="drop"),
        in_axes=(1, 1),
    )(x.indices, upd)
    return g2.reshape(-1)


def colsum(x, c: jax.Array, square: bool = False) -> jax.Array:
    """sum_i c_i * x_ij (or x_ij^2) -> (d,): the Hessian-diagonal sums."""
    if is_feature_sharded(x):
        v = x.values * x.values if square else x.values
        return rmatvec(dataclasses.replace(x, values=v), c)
    if is_hybrid(x):
        v = x.dense * x.dense if square else x.dense
        hot = jnp.einsum(
            "n,nh->h", c, v, precision=lax.Precision.HIGHEST
        )
        g = _cold_scatter(
            x, c,
            lambda seg: seg.values * seg.values if square else seg.values,
        )
        return g.at[x.hot_ids].add(hot)
    if not is_sparse(x):
        v = x * x if square else x
        return jnp.einsum("n,nd->d", c, v)
    with jax.named_scope("sparse_scatter"):
        v = x.values * x.values if square else x.values
        upd = (v * c[..., None]).reshape(-1)
        return (
            jnp.zeros((x.d,), upd.dtype)
            .at[x.indices.reshape(-1)]
            .add(upd, mode="drop")
        )


def matvec_and_feature_dots(
    x, w: jax.Array, dot_pairs: Sequence[Tuple[jax.Array, jax.Array]] = ()
):
    """``(matvec(x, w), tuple(vdot(u, v) for u, v in dot_pairs))`` with
    the feature-space dots RIDING THE MARGINS REDUCTION when ``x`` is
    feature-sharded.

    Under a ('data', 'feature') mesh every feature-space contraction —
    the (n,) margin block-sum AND each scalar dot over the sharded
    coefficient space (the L2 value term w.w, the normalization margin
    shift s.w_eff) — costs one all-reduce, and BENCH_r05's
    ``sparse_fs_scaling`` showed those per-pass collectives are what
    broke 2-device scaling (5.32 s @2 vs 3.08 s @1). Here the per-block
    margin partials (n,) and the per-block scalar partials (1,) each
    concatenate into ONE (n + P,) payload whose single sharded-axis sum
    lowers to a single bucketed all-reduce; the XLA partitioner sees one
    reduction instead of 1 + P.

    For every other representation (nothing sharded to coalesce) the
    dots are computed directly — ``jnp.vdot`` — and results are
    bit-identical to the unfused formulation.
    """
    if not is_feature_sharded(x) or not dot_pairs:
        return matvec(x, w), tuple(jnp.vdot(u, v) for u, v in dot_pairs)
    from photon_ml_tpu.parallel.overlap import feature_block_sum

    n = x.shape[0]
    with jax.named_scope("sparse_gather"):
        zb = _block_margin_partials(x, w)  # (F, n) partials
    cols = [zb]
    for u, v in dot_pairs:
        ub = u.reshape(x.num_blocks, x.d_shard)
        vb = v.reshape(x.num_blocks, x.d_shard)
        cols.append(jnp.sum(ub * vb, axis=-1, keepdims=True))  # (F, 1)
    payload = jnp.concatenate(cols, axis=-1)  # (F, n + P), sharded on F
    # fused: ONE bucketed all-reduce of (n + P,). overlap: the row axis
    # chunks into reduce-scatters issued as their chunk's partials land,
    # plus one trailing all-gather (parallel.overlap.feature_block_sum —
    # the schedule is the PHOTON_COLLECTIVE_MODE knob, equivalence
    # drilled in tests/test_partition.py).
    total = feature_block_sum(payload)
    # collective profiler (obs.collectives): this function only ever
    # runs under tracing, so the note fires once per COMPILATION —
    # recording the bucketed reduction's payload geometry
    # (collective.traced.matvec_and_feature_dots.w<F>.{count,bytes})
    # with zero cost in the compiled program; callers that know their
    # pass counts (bench.py) scale it
    from photon_ml_tpu.obs import collectives as _obs_coll

    _obs_coll.note_traced_collective(
        "matvec_and_feature_dots",
        mesh_width=x.num_blocks,
        nbytes=(n + len(dot_pairs)) * jnp.dtype(total.dtype).itemsize,
    )
    return total[:n], tuple(total[n + i] for i in range(len(dot_pairs)))


def pad_rows(x, pad: int):
    """Append `pad` all-padding rows (index d, value 0), preserving the
    padding invariant that plain zero-padding would break."""
    if is_feature_sharded(x):
        if x.is_balanced:
            # appended rows hold no entries; only the logical row count
            # (the scatter target / margin length) grows. Virtual-row
            # sentinels already point at num_rows and keep dropping.
            return dataclasses.replace(x, num_rows=x.shape[0] + pad)
        return dataclasses.replace(
            x,
            indices=jnp.pad(
                x.indices, ((0, pad), (0, 0), (0, 0)), constant_values=x.d_shard
            ),
            values=jnp.pad(x.values, ((0, pad), (0, 0), (0, 0))),
        )
    if is_hybrid(x):
        n = x.dense.shape[-2]
        segs = list(x.cold_segments)
        segs[-1] = pad_rows(segs[-1], pad)
        return HybridFeatures(
            dense=jnp.pad(x.dense, ((0, pad), (0, 0))),
            hot_ids=x.hot_ids,
            cold_segments=tuple(segs),
            row_perm=jnp.concatenate(
                [x.row_perm, jnp.arange(n, n + pad, dtype=jnp.int32)]
            ),
        )
    return SparseFeatures(
        indices=jnp.pad(x.indices, ((0, pad), (0, 0)), constant_values=x.d),
        values=jnp.pad(x.values, ((0, pad), (0, 0))),
        d=x.d,
    )


def row_density(x) -> jax.Array:
    """Per-row stored-entry count (diagnostic; hybrid in stored order)."""
    if is_hybrid(x):
        cold = jnp.concatenate(
            [row_density(seg) for seg in x.cold_segments]
        )
        return jnp.sum(x.dense != 0, axis=-1) + cold
    if not is_sparse(x):
        return jnp.sum(x != 0, axis=-1)
    return jnp.sum(x.indices < x.d, axis=-1)


def stored_cold_entries(hf: HybridFeatures) -> int:
    """Total stored (non-padding) entries across the cold segments."""
    return sum(
        int(np.sum(np.asarray(seg.indices) < seg.d))
        for seg in hf.cold_segments
    )


def cold_padded_slots(hf: HybridFeatures) -> int:
    """Total padded ELL slots across the cold segments (the quantity the
    irregular-access cost scales with)."""
    return sum(
        int(np.prod(seg.indices.shape)) for seg in hf.cold_segments
    )


def cold_as_single_ell(hf: HybridFeatures) -> SparseFeatures:
    """Concatenate the cold segments back into one ELL at the max segment
    width (stored row order). Re-inflates padding — for once-per-run
    consumers (statistics), not hot kernels."""
    kmax = max(seg.nnz_per_row for seg in hf.cold_segments)
    ind = []
    val = []
    for seg in hf.cold_segments:
        extra = kmax - seg.nnz_per_row
        ind.append(
            jnp.pad(seg.indices, ((0, 0), (0, extra)), constant_values=seg.d)
        )
        val.append(jnp.pad(seg.values, ((0, 0), (0, extra))))
    return SparseFeatures(
        indices=jnp.concatenate(ind),
        values=jnp.concatenate(val),
        d=hf.d,
    )


def feature_sharded_as_ell(fs: FeatureShardedSparse) -> SparseFeatures:
    """View a blocked container as one flat ELL over the BLOCKED column
    space (width F * d_shard): global id = block * d_shard + local. For
    once-per-run consumers (feature statistics), not hot kernels.
    Balanced containers rebuild host-side through their row map (their
    virtual rows are per-block packings, not batch rows)."""
    d_block = fs.num_blocks * fs.d_shard
    if fs.is_balanced:
        ind = np.asarray(fs.indices)
        val = np.asarray(fs.values)
        rm = np.asarray(fs.row_map)
        v_rows, F, k = ind.shape
        keep = ind < fs.d_shard
        vv, ff, _ = np.nonzero(keep)
        rows = rm[vv, ff]
        cols = ff.astype(np.int64) * fs.d_shard + ind[keep]
        return from_coo(
            rows,
            cols,
            val[keep],
            fs.shape[0],
            d_block,
            dtype=fs.values.dtype,
        )
    n, F, k = fs.indices.shape
    base = (
        jnp.arange(F, dtype=fs.indices.dtype) * fs.d_shard
    )[None, :, None]
    glob = jnp.where(fs.indices < fs.d_shard, fs.indices + base, d_block)
    return SparseFeatures(
        indices=glob.reshape(n, F * k),
        values=fs.values.reshape(n, F * k),
        d=d_block,
    )


def blocked_column_map(d: int, num_blocks: int) -> np.ndarray:
    """(d,) original column -> blocked position, for the round-robin
    blocking ``shard_columns`` applies: column c lives in block c % F at
    local id c // F. Used to block/unblock coefficient, bound, and
    normalization vectors."""
    c = np.arange(d, dtype=np.int64)
    d_shard = -(-d // num_blocks)
    return (c % num_blocks) * d_shard + c // num_blocks


def balanced_virtual_width(counts: np.ndarray) -> int:
    """The virtual-row width k0 minimizing the ALIGNED balanced
    layout's cost proxy ``slots + 2 * routed_virtual_rows`` (a
    scatter/gather routing touch costs ~2 stored-slot touches on the
    measured backends), given the (F, n) per-(block, row) entry counts.
    Every row owns one identity-aligned virtual row (n * k slots per
    block); only entries past k spill into routed overflow rows. Exact
    scan over candidate widths — counts are small ints."""
    kmax = int(counts.max()) if counts.size else 1
    if kmax <= 1:
        return 1
    best_k, best_cost = 1, None
    F, n = counts.shape
    for k in range(1, kmax + 1):
        over = np.maximum(counts - k, 0)
        # overflow rows pad to the max over blocks so the (V, F, k)
        # arrays stay rectangular
        v_ovf = int((-(-over // k)).sum(axis=1).max())
        cost = F * (n + v_ovf) * k + 2 * F * v_ovf
        if best_cost is None or cost < best_cost:
            best_k, best_cost = k, cost
    return best_k


def shard_columns(
    sf: SparseFeatures,
    num_blocks: int,
    dtype=None,
    balance_rows: bool = False,
) -> FeatureShardedSparse:
    """Block an ELL matrix by column for feature-sharded solves
    (host-side, once per dataset). Columns are assigned round-robin
    (block = c % F) so frequency-sorted vocabularies — the common layout
    after ``cli/build_index`` — spread their hot columns evenly across
    blocks. ``blocked_column_map`` gives the induced coefficient layout.

    Flat layout (default): the per-(row, block) width k is the max over
    the dataset; round-robin keeps the MEAN near nnz/F, but the max —
    which every lane pads to — concentrates near the binomial tail, so
    stored slots inflate as F grows (3.7x at F=8 on the bench workload).

    ``balance_rows=True`` (the ``PHOTON_COLLECTIVE_MODE=overlap``
    layout): each block packs its entries into width-k0 VIRTUAL rows
    (``balanced_virtual_width`` picks k0), recorded in ``row_map`` —
    stored slots then track the entry count instead of the max row.
    Same round-robin column map, so coefficients are interchangeable
    between layouts.
    """
    if num_blocks < 1:
        raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
    F = num_blocks
    d_shard = -(-sf.d // F)
    out_dtype = np.dtype(jnp.dtype(dtype or sf.values.dtype))
    ind = np.asarray(sf.indices)
    val = np.asarray(sf.values)
    n, k = ind.shape
    keep = ind < sf.d
    rows = np.broadcast_to(np.arange(n)[:, None], ind.shape)[keep]
    cols = ind[keep].astype(np.int64)
    vals = val[keep]
    blk = cols % F
    loc = cols // F
    key = rows * F + blk
    counts = np.bincount(key, minlength=n * F)
    order = np.argsort(key, kind="stable")
    starts = np.concatenate([[0], np.cumsum(counts)])[:-1]
    slot = np.arange(key.size) - starts[key[order]]
    if balance_rows and F > 1:
        cfr = counts.reshape(n, F).T  # (F, n) per-(block, row) counts
        k0 = balanced_virtual_width(cfr)
        # identity-aligned head: virtual row r == row r holds the first
        # <= k0 entries of every block; entries past k0 spill into
        # routed overflow rows appended after the head
        over = np.maximum(cfr - k0, 0)
        ovf_per = -(-over // k0)  # (F, n) overflow rows per (block, row)
        v_ovf = int(ovf_per.sum(axis=1).max())
        v_total = n + v_ovf if (n + v_ovf) else 1
        base = np.zeros((F, n), np.int64)
        base[:, 1:] = np.cumsum(ovf_per, axis=1)[:, :-1]
        r_o, b_o, s_o = rows[order], blk[order], slot
        in_head = s_o < k0
        vrow = np.where(
            in_head,
            r_o,
            n + base[b_o, r_o] + np.maximum(s_o - k0, 0) // k0,
        )
        pos = np.where(in_head, s_o, np.maximum(s_o - k0, 0) % k0)
        indices = np.full((v_total, F, k0), d_shard, np.int32)
        values = np.zeros((v_total, F, k0), out_dtype)
        row_map = np.full((v_total, F), n, np.int32)
        row_map[:n] = np.arange(n, dtype=np.int32)[:, None]
        indices[vrow, b_o, pos] = loc[order]
        values[vrow, b_o, pos] = vals[order]
        row_map[vrow, b_o] = r_o
        return FeatureShardedSparse(
            indices=jnp.asarray(indices),
            values=jnp.asarray(values),
            d_shard=d_shard,
            d_orig=sf.d,
            row_map=jnp.asarray(row_map),
            num_rows=n,
            aligned_rows=n,
        )
    k_new = int(counts.max()) if counts.size and counts.max() > 0 else 1
    indices = np.full((n, F, k_new), d_shard, np.int32)
    values = np.zeros((n, F, k_new), out_dtype)
    indices[rows[order], blk[order], slot] = loc[order]
    values[rows[order], blk[order], slot] = vals[order]
    return FeatureShardedSparse(
        indices=jnp.asarray(indices),
        values=jnp.asarray(values),
        d_shard=d_shard,
        d_orig=sf.d,
    )


# -- construction ------------------------------------------------------------


def from_coo(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    num_rows: int,
    num_cols: int,
    nnz_per_row: int = 0,
    dtype=jnp.float32,
    as_numpy: bool = False,
) -> SparseFeatures:
    """Build from COO triplets (host-side). Duplicate (row, col) entries are
    summed (the reference's dedup-by-sum, ``DataProcessingUtils.scala:70-76``).
    ``nnz_per_row`` pads/caps the row width; 0 means the max observed.
    ``as_numpy`` keeps the buffers host-side (no device placement) for
    containers that are re-cast per consumer (e.g. GAME shards)."""
    rows = np.asarray(rows, np.int64)
    cols = np.asarray(cols, np.int64)
    vals = np.asarray(vals, np.float64)
    # dedup-by-sum on (row, col)
    flat = rows * num_cols + cols
    uniq, inv = np.unique(flat, return_inverse=True)
    summed = np.zeros(uniq.size, np.float64)
    np.add.at(summed, inv, vals)
    r = (uniq // num_cols).astype(np.int64)
    c = (uniq % num_cols).astype(np.int64)
    counts = np.bincount(r, minlength=num_rows)
    k = int(counts.max()) if counts.size and counts.max() > 0 else 1
    if nnz_per_row:
        if k > nnz_per_row:
            raise ValueError(
                f"a row has {k} entries, above nnz_per_row={nnz_per_row}"
            )
        k = nnz_per_row
    indices = np.full((num_rows, k), num_cols, np.int64)
    values = np.zeros((num_rows, k), np.float64)
    # slot of each entry within its row (entries are sorted by flat id)
    starts = np.concatenate([[0], np.cumsum(counts)])[:-1]
    slot = np.arange(uniq.size) - starts[r]
    indices[r, slot] = c
    values[r, slot] = summed
    if as_numpy:
        return SparseFeatures(
            indices=indices.astype(np.int32),
            values=values.astype(np.dtype(jnp.dtype(dtype))),
            d=num_cols,
        )
    return SparseFeatures(
        indices=jnp.asarray(indices, jnp.int32),
        values=jnp.asarray(values, dtype),
        d=num_cols,
    )


def to_hybrid(
    sf: SparseFeatures,
    hot_columns: int = -1,
    dtype=None,
    min_count: int = 64,
    max_slab_bytes: int = 1 << 30,
    num_row_buckets: int = 8,
) -> HybridFeatures:
    """Split an ELL matrix into dense-hot + bucketed sparse-cold on the
    HOST (numpy, once per dataset): the form the ``hot_columns`` option of
    the drivers builds. A design that already lives on the device is
    split there by ``split_hot_cold``, which is where the rule lives:
    ``train_glm`` asks it on every plain padded-ELL design.

    ``hot_columns`` = H picks the H highest-count columns; -1 sizes the
    slab as r5 did: columns whose stored-entry count exceeds ``min_count``
    (64, r5's reading of the break-even; the ledger's line of PR 26 puts
    it near 670 entries at 2^20 float32 rows, ``hot_column_break_even``,
    and this default is left as it was until the option goes), hottest
    first, until the slab reaches ``max_slab_bytes`` at the target dtype.
    A slab with zero qualifying columns degrades to H=1 so shapes stay
    static.

    The cold rows are then sorted by remaining-entry count and split
    into at most ``num_row_buckets`` contiguous ELL segments by the
    exact-DP padding minimizer (``game/data.py``) — the irregular-access
    cost scales with padded SLOTS, and a single max-width ELL would hand
    the hottest row's width to every row. The returned ``row_perm``
    records stored-row -> original-row; callers permute the rest of the
    batch to match.

    The input must be dedup-summed — no (row, column) pair stored twice
    (``from_coo``'s invariant, which every ingest path goes through).
    Duplicate slots would sum into one slab cell, changing the SQUARED
    statistics (colsum(square=True) -> Hessian diagonal / variances)
    relative to the ELL, so they are rejected here rather than silently
    diverging. The device path accepts them: a pair stored twice sums in
    the slab cell, as ``matvec`` / ``rmatvec`` sum it in the ELL, and
    where the caller will ask for squared sums (``exact_squares``) the
    later copies stay in the cold segments, so no slab cell is a sum.
    """
    out_dtype = np.dtype(jnp.dtype(dtype or sf.values.dtype))
    ind = np.asarray(sf.indices)
    val = np.asarray(sf.values)
    n, k = ind.shape
    sorted_cols = np.sort(np.where(ind < sf.d, ind, -1), axis=-1)
    dup_rows = np.flatnonzero(
        ((sorted_cols[:, 1:] == sorted_cols[:, :-1])
         & (sorted_cols[:, 1:] >= 0)).any(axis=-1)
    )
    if dup_rows.size:
        raise ValueError(
            f"to_hybrid requires dedup-summed input (from_coo's "
            f"invariant); {dup_rows.size} rows store a (row, column) "
            f"pair twice, e.g. row {int(dup_rows[0])}"
        )
    flat = ind.reshape(-1)
    keep = flat < sf.d
    counts = np.bincount(flat[keep], minlength=sf.d)
    if hot_columns < 0:
        hot = np.flatnonzero(counts > min_count)
        hot = hot[np.argsort(-counts[hot], kind="stable")]
        h_cap = max(1, max_slab_bytes // (n * out_dtype.itemsize))
        hot = hot[:h_cap]
        if hot.size == 0:
            hot = np.argsort(-counts, kind="stable")[:1]
    else:
        h = max(1, min(hot_columns, sf.d))
        hot = np.argsort(-counts, kind="stable")[:h]
    H = hot.size
    hot_rank = np.full(sf.d + 1, -1, np.int64)
    hot_rank[hot] = np.arange(H)
    is_hot = hot_rank[ind] >= 0  # (n, k); padding col d is never hot

    # row permutation: ascending cold-entry count (matches the DP input)
    cold_entry = ~is_hot & (ind < sf.d)
    cold_counts = cold_entry.sum(axis=1)
    row_perm = np.argsort(cold_counts, kind="stable").astype(np.int32)
    sorted_counts = cold_counts[row_perm]
    bounds = split_minimizing_padding(
        sorted_counts, max(1, num_row_buckets)
    ) or [(0, n)]

    # slab built at the target dtype's f32/f64 (never narrower than f32 —
    # bf16 accumulation would lose dedup sums), in STORED row order
    acc_dtype = np.float64 if out_dtype == np.float64 else np.float32
    dense = np.zeros((n, H), acc_dtype)
    rows = np.broadcast_to(np.arange(n)[:, None], ind.shape)
    np.add.at(dense, (rows[is_hot], hot_rank[ind[is_hot]]), val[is_hot])
    inv_perm = np.empty(n, np.int64)
    inv_perm[row_perm] = np.arange(n)
    dense = dense[row_perm]

    # cold segments: contiguous stored-row ranges, each its own ELL width
    stored_rows = inv_perm[rows[cold_entry]]
    cold_cols = ind[cold_entry]
    cold_vals = val[cold_entry]
    order = np.argsort(stored_rows, kind="stable")
    stored_rows = stored_rows[order]
    cold_cols = cold_cols[order]
    cold_vals = cold_vals[order]
    entry_starts = np.searchsorted(stored_rows, [lo for lo, _ in bounds])
    entry_ends = np.searchsorted(stored_rows, [hi for _, hi in bounds])
    segments = []
    for (lo, hi), es, ee in zip(bounds, entry_starts, entry_ends):
        segments.append(
            from_coo(
                stored_rows[es:ee] - lo,
                cold_cols[es:ee],
                cold_vals[es:ee],
                hi - lo,
                sf.d,
                dtype=dtype or sf.values.dtype,
            )
        )
    return HybridFeatures(
        dense=jnp.asarray(dense, dtype or sf.values.dtype),
        hot_ids=jnp.asarray(hot.astype(np.int32)),
        cold_segments=tuple(segments),
        row_perm=jnp.asarray(row_perm),
    )


# -- the hot/cold split on the device ----------------------------------------

# What the split rule weighs, by ``device_kind``. A kind that is not here
# has no measured rates, and its designs stay as they are.
#   gather_s, scatter_s: XLA's gather and scatter-add (with the sort it
#     lowers to) a stored ELL slot, padding included, whichever slot it is:
#     9.12 s and 9.28 + 2.86 s over 28 evaluations of 2^20 x 39 slots
#     (ledger, PR 26, ``glm_hashed_sparse.solve``: ``fusion.60`` and
#     ``fusion.61`` + ``sort.4`` of its ``breakdown``).
#   slab_bytes_per_s: what a float32 slab product reads at, either way
#     round and at ``Precision.HIGHEST`` as at the default: 6.4 ms a pass
#     of a 2^20 x 1,024 slab, against 5.2 ms at the published 819 GB/s.
#   count_s: the exact counts sweep a slot (0.27 s at 2^20 x 39);
#     compare_s: the split's work a (slot x hot column) pair, both of its
#     compare-and-reduce sweeps together (45 + 31.5 ms at 1,024 columns);
#     fixed_s: its dispatches and its two blocking fetches.
#   (The last four: my chip runs, PR 27; PERF.md section 6.)
_SPLIT_RATES = {
    "TPU v5 lite": {
        "gather_s": 8.0e-9,
        "scatter_s": 10.6e-9,
        "slab_bytes_per_s": 670e9,
        "count_s": 6.6e-9,
        "compare_s": 1.9e-12,
        "fixed_s": 3e-3,
    },
}
# device bytes a stored slot costs the solve program beside the design
# itself (gathered values, scatter updates and the sort's copies): 4.69 GB
# reserved at 40.9 M slots less the solver's 30 coefficient vectors
# (ledger, PR 26, ``memory_stats`` of ``glm_hashed_sparse.solve``), rounded
# up. Counted for every slot of the plain design, though the solve on the
# split design gathers and scatters only the cold ones.
_SOLVE_SCRATCH_BYTES_A_SLOT = 80
# and what the split holds a slot while it runs, beside the design and the
# slab: the rows' compacted cold ids and values (8), the cut segments (up
# to 8), its programs' temporaries (the compiler's memory analysis for the
# v5e at 2^20 x 39: 13 B for the counts sort, 7 B for ``_split_rows``; PR
# 27), rounded up twofold. The loaded solve program keeps its scratch
# reserved meanwhile, so the two add up.
_SPLIT_SCRATCH_BYTES_A_SLOT = 40
_TOP_COLUMNS = 4096  # counts the host looks at: no slab is wider
_SLAB_LANES = 128  # a slab is whole lanes wide; the spare columns stay empty


def _device_profile(device):
    """(rates, free bytes, bytes the runtime holds reserved for the loaded
    programs' scratch) the split rule may count on for ``device``, or the
    reason why it cannot: ``"no_rates"`` for a device kind nobody measured,
    ``"no_memory_stats"`` where the backend reports none (the CPU)."""
    rates = _SPLIT_RATES.get(device.device_kind)
    if rates is None:
        return "no_rates"
    stats = device.memory_stats() or {}
    if "bytes_limit" not in stats:
        return "no_memory_stats"
    # ``largest_free_block_bytes`` is left out: it moved between the jobs
    # of one process by gigabytes (5.0 against 11 GB free), the hot count
    # with it, and every program of the job compiled again (PR 27)
    reserved = stats.get("bytes_reserved", 0)
    free = stats["bytes_limit"] - stats.get("bytes_in_use", 0) - reserved
    return rates, int(free), int(reserved)


def hot_column_break_even(
    n: int, k: int, itemsize: int, rates: dict, evaluations: float
) -> float:
    """Stored entries a column needs before a dense slab column is cheaper:
    every entry costs a gather and a scatter-add an evaluation, a slab
    column two reads of ``n`` values at the slab rate, and making the column
    its share of the split's two compare sweeps, once."""
    dense_s = 2.0 * n * itemsize / rates["slab_bytes_per_s"]
    make_s = n * k * rates["compare_s"] / max(float(evaluations), 1.0)
    return (dense_s + make_s) / (rates["gather_s"] + rates["scatter_s"])


@partial(jax.jit, static_argnames=("d", "h_max"))
def _top_column_counts(indices, *, d, h_max):
    """The ``h_max`` largest stored-slot counts of the design's columns,
    descending, and their column ids. Exact: designs that differ by a
    relabelling get the same counts. By a sort of the slots' ids and the
    lengths of its runs, which costs half of what a scatter-add of ones
    into ``d`` counters does (0.27 s against 0.51 s at 2^20 x 39, my chip
    run, PR 27); the slots in stored order, which is slot-major on the TPU
    (``_cold_flat_indices``)."""
    with jax.named_scope("split_counts"):
        ids = jnp.sort(indices.T.reshape(-1))
        size = ids.shape[0]
        at = jnp.arange(size, dtype=jnp.int32)
        first = jnp.concatenate([jnp.ones((1,), bool), ids[1:] != ids[:-1]])
        # a run ends where the next one starts
        next_first = lax.cummin(jnp.where(first, at, size), reverse=True)
        ends = jnp.concatenate(
            [next_first[1:], jnp.full((1,), size, jnp.int32)]
        )
        runs = jnp.where(first & (ids < d), ends - at, 0)  # padding: id d
        top, where = lax.top_k(runs, h_max)
        return top, ids[where]


def _first_occurrence(indices):
    """(n, k) bool: False on the second and later slots of a row that hold
    a column an earlier slot of the row holds."""
    k = indices.shape[-1]
    same = indices[:, :, None] == indices[:, None, :]  # [row, slot, other]
    earlier = jnp.tril(jnp.ones((k, k), bool), -1)  # other < slot
    return ~jnp.any(same & earlier, axis=-1)


@partial(jax.jit, static_argnames=("d", "h", "width", "exact_squares"))
def _split_rows(indices, values, top_ids, *, d, h, width, exact_squares):
    """Everything of the split whose shapes the cold counts do not set: the
    rows sorted by cold count, the slab, and every row's cold slots moved to
    its front. Slots go to the slab by compare-and-sum over the row's own
    slots, so nothing is gathered or scattered and a pair stored twice
    sums, as ``matvec`` / ``rmatvec`` treat it in the ELL. The slab is
    ``width >= h`` columns wide; the columns past ``h`` stay empty (their
    slots stay cold)."""
    n, k = indices.shape
    hot_ids = top_ids[:width]
    acc = jnp.promote_types(values.dtype, jnp.float32)

    with jax.named_scope("split_mark"):
        # padding holds column d, which is no hot column
        hot = jnp.any(indices[:, :, None] == hot_ids[:h], axis=-1)
        if exact_squares:
            # a later copy of a pair stays cold, so that no slab cell is a
            # sum and the squared column sums are the ELL's
            hot = hot & _first_occurrence(indices)
        cold = ~hot & (indices < d)
        cold_count = jnp.sum(cold, axis=-1, dtype=jnp.int32)
        histogram = jnp.sum(
            cold_count[:, None] == jnp.arange(k + 1, dtype=jnp.int32),
            axis=0, dtype=jnp.int32,
        )
        stored = jnp.sum(indices < d, dtype=jnp.int32)
        row_perm = jnp.argsort(cold_count, stable=True).astype(jnp.int32)

    with jax.named_scope("split_permute"):
        idx, val, cold = indices[row_perm], values[row_perm], cold[row_perm]

    with jax.named_scope("split_slab"):
        mine = idx[:, :, None] == jnp.where(
            jnp.arange(width) < h, hot_ids, -1
        )
        if exact_squares:
            mine = mine & hot[row_perm][:, :, None]
        dense = jnp.sum(
            jnp.where(mine, val[:, :, None].astype(acc), 0), axis=1
        ).astype(values.dtype)

    with jax.named_scope("split_compact"):
        _, cold_idx, cold_val = lax.sort(
            (
                (~cold).astype(jnp.int8),
                jnp.where(cold, idx, d),
                jnp.where(cold, val, 0),
            ),
            dimension=1, is_stable=True, num_keys=1,
        )
    counts = jnp.concatenate([histogram, stored[None]])
    return dense, hot_ids, cold_idx, cold_val, row_perm, counts


@partial(jax.jit, static_argnames=("d", "cuts"))
def _cut_segments(cold_idx, cold_val, *, d, cuts):
    return tuple(
        SparseFeatures(cold_idx[lo:hi, :w], cold_val[lo:hi, :w], d)
        for lo, hi, w in cuts
    )


def split_on_device(
    sf: SparseFeatures,
    top_ids: jax.Array,
    hot_columns: int,
    exact_squares: bool = False,
    num_row_buckets: int = 8,
    slab_columns: Optional[int] = None,
):
    """The mechanics of the device-side split: ``sf`` with the first
    ``hot_columns`` of ``top_ids`` densified, in a slab of ``slab_columns``
    (the same, unless given; the further columns take the next ids and
    stay empty). Returns the ``HybridFeatures``, the histogram of the rows'
    cold counts and the stored slots. Compiled device code and one fetch
    of ``k + 2`` integers; the static shapes of the cold segments come
    from that fetch."""
    n, k = sf.indices.shape
    dense, hot_ids, cold_idx, cold_val, row_perm, counts = _split_rows(
        sf.indices, sf.values, top_ids,
        d=sf.d, h=int(hot_columns), width=int(slab_columns or hot_columns),
        exact_squares=bool(exact_squares),
    )
    counts = np.asarray(counts)
    histogram, stored = counts[:-1], int(counts[-1])
    held = np.flatnonzero(histogram)
    bounds = split_histogram_minimizing_padding(
        held, histogram[held], max(1, num_row_buckets)
    ) or [(0, n)]
    ends = np.cumsum(histogram)
    cuts = tuple(
        # a segment is as wide as its last row's cold count
        (lo, hi, max(1, int(np.searchsorted(ends, hi, side="left"))))
        for lo, hi in bounds
    )
    segments = _cut_segments(cold_idx, cold_val, d=sf.d, cuts=cuts)
    hf = HybridFeatures(
        dense=dense, hot_ids=hot_ids, cold_segments=segments,
        row_perm=row_perm,
    )
    return hf, histogram, stored


def split_hot_cold(
    sf: SparseFeatures,
    evaluations: float,
    exact_squares: bool = False,
    solver_bytes: int = 0,
):
    """The rule: split ``sf`` hot/cold on its device where its own column
    counts say that pays over ``evaluations`` objective evaluations.

    Returns ``(HybridFeatures, info)``, or ``(None, info)`` with
    ``info["reason"]`` where the design stays as it is. Which columns are
    hot changes the speed of a pass and never its result, so the rule needs
    no option: it reads the design (its column counts, where its arrays
    live), the device (``_device_profile``: measured rates, free memory)
    and what the caller will do with it (``evaluations``; ``exact_squares``
    when it will ask for ``colsum(square=True)``; ``solver_bytes`` it will
    hold beside the design).

    A column goes to the slab when its stored entries pass
    ``hot_column_break_even``, cut at a whole count (columns that tie stay
    together, so designs that differ by a relabelling split alike and
    compile the same programs), as many as the free memory holds. The split
    is declined when no column passes, or when what it saves over the
    solve is under what it costs to make.
    """
    n, k = sf.indices.shape
    if not isinstance(sf.indices, jax.Array) or isinstance(
        sf.indices, jax.core.Tracer
    ):
        return None, {"reason": "not_on_device"}
    devices = sf.indices.sharding.device_set | sf.values.sharding.device_set
    if len(devices) > 1:
        return None, {"reason": "sharded"}
    profile = _device_profile(next(iter(devices)))
    if isinstance(profile, str):
        return None, {"reason": profile}
    rates, free, reserved = profile
    slot_s = rates["gather_s"] + rates["scatter_s"]
    itemsize = jnp.dtype(sf.values.dtype).itemsize
    evaluations = max(float(evaluations), 1.0)
    if evaluations * n * k * slot_s <= rates["fixed_s"] + (
        n * k * rates["count_s"]
    ):
        # not even a split that removed every slot would pay
        return None, {"reason": "does_not_pay"}

    # memory: the slab may take HALF of what is free beside what the solve
    # and the split will hold. The other half is for a second copy of the
    # slab, which XLA has kept in another layout (PERF.md section 6, PR 27,
    # finding 3). And down to a power of two: what is free follows the
    # process's state by some hundred MB, and a cap that followed it in
    # small steps would give the jobs of one process different shapes, each
    # with its own compile.
    scratch = solver_bytes + n * k * (
        _SOLVE_SCRATCH_BYTES_A_SLOT + _SPLIT_SCRATCH_BYTES_A_SLOT
    )
    h_cap = (free - max(scratch - reserved, 0)) // (2 * n * itemsize)
    if h_cap < 1:
        return None, {"reason": "no_memory"}
    h_cap = 1 << (int(h_cap).bit_length() - 1)

    top, top_ids = _top_column_counts(
        # a design of fewer slots than that has no more columns either
        sf.indices, d=sf.d, h_max=min(sf.d, n * k, _TOP_COLUMNS)
    )
    top = np.asarray(top)  # descending
    need = hot_column_break_even(n, k, itemsize, rates, evaluations)
    paying = int(np.searchsorted(-top, -need, side="left"))  # counts > need
    h = min(paying, h_cap)
    if h < paying or (h == len(top) and h < sf.d):
        # the cap cut columns that pay: cut at a whole count, so that the
        # columns that tie with the first one left out stay out with it
        h = int(np.searchsorted(-top, -top[min(h, len(top) - 1)], "left"))
    if h < 1:
        return None, {"reason": "no_hot_column"}
    hot_slots = int(top[:h].sum())
    saved_s = evaluations * (
        hot_slots * slot_s
        - 2.0 * h * n * itemsize / rates["slab_bytes_per_s"]
    )
    cost_s = rates["fixed_s"] + n * k * (
        rates["count_s"] + h * rates["compare_s"]
    )
    if saved_s <= cost_s:
        return None, {"reason": "does_not_pay"}

    # whole lanes: at a width that is no multiple of 128 the solve program
    # may keep a second copy of the slab in another layout (9.9 GB of
    # scratch at 1,586 columns against 3.0 GB at 1,536 or 1,664; PR 27)
    width = min(-(-h // _SLAB_LANES) * _SLAB_LANES, len(top))
    hf, histogram, stored = split_on_device(
        sf, top_ids, h, exact_squares=exact_squares,
        slab_columns=width if width <= max(h, h_cap) else h,
    )
    return hf, {
        "hot_columns": h,
        "hot_slot_share": 1.0 - float(
            np.dot(histogram, np.arange(k + 1))
        ) / max(stored, 1),
        "cold_padded_slots": cold_padded_slots(hf),
        "segments": len(hf.cold_segments),
    }


def from_dense(x: np.ndarray, nnz_per_row: int = 0, dtype=jnp.float32) -> SparseFeatures:
    """Sparsify a dense matrix (testing / oracles)."""
    x = np.asarray(x)
    r, c = np.nonzero(x)
    return from_coo(
        r, c, x[r, c], x.shape[0], x.shape[1], nnz_per_row, dtype
    )


def to_dense(sf) -> np.ndarray:
    """Densify (small problems / tests only). Hybrid matrices come back
    in ORIGINAL row order (row_perm inverted); feature-sharded matrices
    come back in BLOCKED column order (width F * d_shard)."""
    if is_feature_sharded(sf):
        return to_dense(feature_sharded_as_ell(sf))
    if is_hybrid(sf):
        stored = np.concatenate(
            [to_dense(seg) for seg in sf.cold_segments]
        )
        stored[:, np.asarray(sf.hot_ids)] += np.asarray(
            sf.dense, np.float64
        ).astype(stored.dtype)
        out = np.empty_like(stored)
        out[np.asarray(sf.row_perm)] = stored
        return out
    ind = np.asarray(sf.indices)
    val = np.asarray(sf.values)
    n, k = ind.shape
    out = np.zeros((n, sf.d), val.dtype)
    keep = ind < sf.d
    np.add.at(out, (np.repeat(np.arange(n), k)[keep.reshape(-1)], ind[keep]), val[keep])
    return out
