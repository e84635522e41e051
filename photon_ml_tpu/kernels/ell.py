"""Pallas kernels for the padded-ELL contractions.

Written to replace XLA's gather/scatter lowering of the three hot ops in
``ops/sparse.py`` — the ~90 ms/pass frontier BENCH_r05 measured at 92%
of the sparse solve's wall clock. They do NOT lower for TPU on jax 0.9.0
(docs/KERNELS.md "Status"), so today they run only in the interpreter:

    matvec:   z_i = sum_k v_ik * w[c_ik]      (gather + row reduce)
    rmatvec:  g_j = sum_{ik: c_ik=j} v_ik a_i (scatter-add)
    colsum:   s_j = sum_{ik: c_ik=j} f(v_ik) c_i (scatter-add)

Tiling scheme (docs/KERNELS.md):

- The grid runs over ROW BLOCKS of ``_ROW_BLOCK`` rows; Pallas's grid
  pipeline double-buffers each block's (indices, values) DMA against the
  previous block's compute, so the design streams HBM->VMEM at line
  rate.
- The coefficient table (matvec) and the output accumulator (scatter)
  are RESIDENT in VMEM for the whole grid, lane-padded to a multiple of
  128: every scatter-add is a dense accumulation into on-chip memory
  instead of XLA's serialized HBM scatter, and every gather hits VMEM.
  ``dispatch.accumulator_fits`` caps eligibility at the VMEM budget;
  wider problems keep the XLA path (the feature-sharded container
  already splits d per device, so its per-block width is small).
- Padding is algebraically invisible by the same convention as the XLA
  path: padding slots carry column id ``d`` and value 0; the table/
  accumulator is padded past ``d`` with an always-zero tail, so padded
  gathers read 0 and padded scatter lanes add 0 to the tail.
- The scatter kernels are DUPLICATE-SAFE without a scatter primitive:
  within each row, every slot is first replaced by its column GROUP
  TOTAL (a k x k same-column mask contraction), so the unordered vector
  store writes the same value from every duplicate lane; rows then
  accumulate sequentially. Duplicate (row, column) pairs — which
  ``from_coo``'s dedup-sum normally removes — therefore still sum
  exactly like XLA's scatter-add.

Off TPU the kernels run in Pallas interpret mode (tier-1 proves their
semantics on CPU); ``ops/sparse.py`` only routes here per
``kernels.dispatch``.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from photon_ml_tpu.kernels import dispatch

__all__ = ["ell_matvec", "ell_rmatvec", "ell_colsum", "ell_scatter_add"]

# Rows per grid step. 256 rows x k<=64 slots keeps a block's
# (indices, values) tiles well under 256 KiB while amortizing the grid
# step overhead; override for experiments via PHOTON_PALLAS_ROW_BLOCK.
_DEFAULT_ROW_BLOCK = 256


def _row_block(n: int) -> int:
    try:
        br = int(os.environ.get("PHOTON_PALLAS_ROW_BLOCK", _DEFAULT_ROW_BLOCK))
    except ValueError:
        br = _DEFAULT_ROW_BLOCK
    br = max(8, br)
    # shrink for small batches: one short block beats many empty ones
    return min(br, _round_up(max(n, 1), 8))


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _lane_pad(d: int) -> int:
    """Table/accumulator width: one past-d zero column for padding ids,
    rounded to full 128-lane tiles."""
    return _round_up(d + 1, 128)


def _pad_rows(indices, values, n_pad: int, d: int):
    n = indices.shape[0]
    if n_pad == n:
        return indices, values
    return (
        jnp.pad(indices, ((0, n_pad - n), (0, 0)), constant_values=d),
        jnp.pad(values, ((0, n_pad - n), (0, 0))),
    )


def _group_totals(ix, upd):
    """(rows, k) updates -> same shape with every slot carrying its
    row-local same-column group total. Makes the unordered vector store
    deterministic under duplicate columns (module docstring)."""
    eq = (ix[:, :, None] == ix[:, None, :]).astype(upd.dtype)
    return jnp.einsum("rjk,rk->rj", eq, upd)


# -- matvec ------------------------------------------------------------------


def _matvec_kernel(idx_ref, val_ref, w_ref, out_ref, *, compute_dtype):
    ix = idx_ref[...]
    v = val_ref[...].astype(compute_dtype)
    gathered = w_ref[0, :][ix]  # VMEM-resident table gather
    out_ref[...] = jnp.sum(v * gathered, axis=-1)


def ell_matvec(indices, values, w, d: int):
    """z = ELL(indices, values) @ w — (n,) in the XLA path's promoted
    dtype (bf16 values x f32 w accumulate in f32)."""
    n, k = indices.shape
    cd = jnp.result_type(values.dtype, w.dtype)
    br = _row_block(n)
    n_pad = _round_up(max(n, 1), br)
    d_pad = _lane_pad(d)
    idx_p, val_p = _pad_rows(indices, values, n_pad, d)
    w_p = jnp.pad(w.astype(cd), (0, d_pad - d)).reshape(1, d_pad)
    dispatch.record_kernel_cost(
        "ell_matvec", n, k, d, jnp.dtype(values.dtype).itemsize,
        extra_bytes=d_pad * jnp.dtype(cd).itemsize,
    )
    out = pl.pallas_call(
        functools.partial(_matvec_kernel, compute_dtype=cd),
        grid=(n_pad // br,),
        in_specs=[
            pl.BlockSpec((br, k), lambda i: (i, 0)),
            pl.BlockSpec((br, k), lambda i: (i, 0)),
            pl.BlockSpec((1, d_pad), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), cd),
        interpret=dispatch.interpret_mode(),
    )(idx_p, val_p, w_p)
    return out[:n]


# -- scatter-add (rmatvec / colsum) ------------------------------------------


def _scatter_kernel(idx_ref, upd_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ix = idx_ref[...]
    comb = _group_totals(ix, upd_ref[...])

    def body(r, carry):
        row_ix = ix[r, :]
        cur = out_ref[0, :][row_ix]
        out_ref[0, row_ix] = cur + comb[r, :]
        return carry

    jax.lax.fori_loop(0, ix.shape[0], body, 0)


def ell_scatter_add(indices, upd, d: int):
    """g_j = sum over slots with column j of ``upd`` — the shared core
    of rmatvec and colsum. The (1, d_pad) accumulator stays in VMEM
    across the whole row-block grid (kernel='fused'-style dense
    accumulation); output dtype is ``upd``'s."""
    n, k = indices.shape
    cd = upd.dtype
    br = _row_block(n)
    n_pad = _round_up(max(n, 1), br)
    d_pad = _lane_pad(d)
    idx_p, upd_p = _pad_rows(indices, upd, n_pad, d)
    out = pl.pallas_call(
        _scatter_kernel,
        grid=(n_pad // br,),
        in_specs=[
            pl.BlockSpec((br, k), lambda i: (i, 0)),
            pl.BlockSpec((br, k), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, d_pad), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, d_pad), cd),
        interpret=dispatch.interpret_mode(),
    )(idx_p, upd_p)
    return out[0, :d]


def ell_rmatvec(indices, values, a, d: int):
    """g = ELL^T @ a. The per-slot update v_ik * a_i is formed outside
    the kernel (elementwise, fused by XLA into the DMA feed); the
    scatter itself is the Pallas dense accumulation."""
    n, k = indices.shape
    upd = values * a[..., None]
    dispatch.record_kernel_cost(
        "ell_rmatvec", n, k, d, jnp.dtype(values.dtype).itemsize,
        extra_bytes=_lane_pad(d) * jnp.dtype(upd.dtype).itemsize,
    )
    return ell_scatter_add(indices, upd, d)


def ell_colsum(indices, values, c, d: int, square: bool = False):
    """s_j = sum_i c_i * v_ij (or v_ij^2) — the Hessian-diagonal sums."""
    n, k = indices.shape
    v = values * values if square else values
    upd = v * c[..., None]
    dispatch.record_kernel_cost(
        "ell_colsum", n, k, d, jnp.dtype(values.dtype).itemsize,
        extra_bytes=_lane_pad(d) * jnp.dtype(upd.dtype).itemsize,
    )
    return ell_scatter_add(indices, upd, d)
