"""Fused ELL objective passes: one design read per solver iteration.

The XLA objective walks the stored design up to three times per
iteration — margins (matvec), gradient back-projection (rmatvec), and
the Hessian-diagonal column sums (colsum) — and BENCH_r05 showed that
walk IS the sparse solve's wall clock (92% ceiling fit at ~90 ms/pass).
Because the pointwise losses are ROW-LOCAL (``ops/losses.py``: l, l',
l'' are elementwise in the margin), the whole forward+backward of one
iteration folds into a single row-block sweep that reads
``(indices, values)`` once:

- :func:`fused_value_grad_curvature` — margins on the forward, the
  weighted loss sum, the scatter-add gradient on the backward, sum(a)
  for the normalization rank-1 correction, and the curvature weights
  c_i = ew_i * l''(z_i) that TRON's next CG loop wants. Replaces the
  matvec + rmatvec pair (and the colsum-bearing sequence below): 3
  design reads -> 1.
- :func:`fused_hessian_vector` — one CG step's H@v: the v-margins
  gather-dot and the back-projection scatter in one sweep (2 reads ->
  1). TRON's inner loop is almost entirely these.
- :func:`fused_hessian_diagonal` — margins plus BOTH column sums
  (value and squared) plus sum(c) for the variance pass (3 reads -> 1).

Loss derivatives are traced straight into the kernel body (VPU
transcendentals); tiling, padding, duplicate-safety, and the VMEM
residency rules are exactly :mod:`photon_ml_tpu.kernels.ell`'s.
``GLMObjective`` applies normalization algebra, L2, and the psum OUTSIDE
— those touch (d,)/(n,) vectors, not the design.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from photon_ml_tpu.kernels import dispatch
from photon_ml_tpu.kernels.ell import (
    _group_totals,
    _lane_pad,
    _pad_rows,
    _round_up,
    _row_block,
)

__all__ = [
    "fused_value_grad_curvature",
    "fused_hessian_vector",
    "fused_hessian_diagonal",
]


def _scatter_rows(ix, comb, acc_ref):
    """Sequential per-row accumulate of group-totaled updates into the
    VMEM-resident (1, d_pad) accumulator (see ell.py on duplicate
    safety)."""

    def body(r, carry):
        row_ix = ix[r, :]
        cur = acc_ref[0, :][row_ix]
        acc_ref[0, row_ix] = cur + comb[r, :]
        return carry

    jax.lax.fori_loop(0, ix.shape[0], body, 0)


def _prep(indices, values, row_vecs, w_vec, d):
    """Shared row/lane padding + compute dtype for the fused passes."""
    n, k = indices.shape
    cd = jnp.result_type(values.dtype, w_vec.dtype, *[
        rv.dtype for rv in row_vecs
    ])
    br = _row_block(n)
    n_pad = _round_up(max(n, 1), br)
    d_pad = _lane_pad(d)
    idx_p, val_p = _pad_rows(indices, values, n_pad, d)
    rows_p = tuple(
        jnp.pad(rv.astype(cd), (0, n_pad - n)) for rv in row_vecs
    )
    w_p = jnp.pad(w_vec.astype(cd), (0, d_pad - d)).reshape(1, d_pad)
    return n, k, cd, br, n_pad, d_pad, idx_p, val_p, rows_p, w_p


# -- value / grad / curvature ------------------------------------------------


def _vgc_kernel(
    idx_ref, val_ref, y_ref, off_ref, ew_ref, w_ref,
    val_acc, asum_acc, grad_acc, c_ref, *, loss, compute_dtype,
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        val_acc[...] = jnp.zeros_like(val_acc)
        asum_acc[...] = jnp.zeros_like(asum_acc)
        grad_acc[...] = jnp.zeros_like(grad_acc)

    ix = idx_ref[...]
    v = val_ref[...].astype(compute_dtype)
    z = jnp.sum(v * w_ref[0, :][ix], axis=-1) + off_ref[...]
    y = y_ref[...]
    ew = ew_ref[...]
    val_acc[0, 0] += jnp.sum(ew * loss.value(z, y))
    a = ew * loss.d1(z, y)
    asum_acc[0, 0] += jnp.sum(a)
    c_ref[...] = ew * loss.d2(z, y)
    _scatter_rows(ix, _group_totals(ix, v * a[:, None]), grad_acc)


def fused_value_grad_curvature(
    indices, values, labels, offsets, ew, w_eff, d: int, loss
):
    """One design read -> (loss sum, raw gradient X^T a, sum(a),
    curvature weights c). ``offsets`` must already carry the margin
    shift; ``w_eff`` is the normalization-effective coefficient vector.
    The caller applies factors/shifts corrections, L2 and psum."""
    n, k, cd, br, n_pad, d_pad, idx_p, val_p, rows, w_p = _prep(
        indices, values, (labels, offsets, ew), w_eff, d
    )
    dispatch.record_kernel_cost(
        "fused_vgc", n, k, d, jnp.dtype(values.dtype).itemsize,
        flops_per_slot=4.0,
        extra_bytes=2 * d_pad * jnp.dtype(cd).itemsize,
    )
    val, asum, grad, c = pl.pallas_call(
        functools.partial(_vgc_kernel, loss=loss, compute_dtype=cd),
        grid=(n_pad // br,),
        in_specs=[
            pl.BlockSpec((br, k), lambda i: (i, 0)),
            pl.BlockSpec((br, k), lambda i: (i, 0)),
            pl.BlockSpec((br,), lambda i: (i,)),
            pl.BlockSpec((br,), lambda i: (i,)),
            pl.BlockSpec((br,), lambda i: (i,)),
            pl.BlockSpec((1, d_pad), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, d_pad), lambda i: (0, 0)),
            pl.BlockSpec((br,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), cd),
            jax.ShapeDtypeStruct((1, 1), cd),
            jax.ShapeDtypeStruct((1, d_pad), cd),
            jax.ShapeDtypeStruct((n_pad,), cd),
        ],
        interpret=dispatch.interpret_mode(),
    )(idx_p, val_p, *rows, w_p)
    return val[0, 0], grad[0, :d], asum[0, 0], c[:n]


# -- Hessian-vector ----------------------------------------------------------


def _hvp_kernel(
    idx_ref, val_ref, c_ref, shift_ref, v_ref,
    hv_acc, usum_acc, *, compute_dtype,
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        hv_acc[...] = jnp.zeros_like(hv_acc)
        usum_acc[...] = jnp.zeros_like(usum_acc)

    ix = idx_ref[...]
    v = val_ref[...].astype(compute_dtype)
    zv = jnp.sum(v * v_ref[0, :][ix], axis=-1) + shift_ref[0, 0]
    u = c_ref[...] * zv
    usum_acc[0, 0] += jnp.sum(u)
    _scatter_rows(ix, _group_totals(ix, v * u[:, None]), hv_acc)


def fused_hessian_vector(indices, values, c, v_eff, shift_v, d: int):
    """One design read -> (raw H@v back-projection X^T (c * (X@v_eff +
    shift_v)), sum(u)). ``c`` are the precomputed curvature weights;
    ``shift_v`` is the scalar margin shift of the CG direction."""
    n, k, cd, br, n_pad, d_pad, idx_p, val_p, rows, v_p = _prep(
        indices, values, (c,), v_eff, d
    )
    dispatch.record_kernel_cost(
        "fused_hvp", n, k, d, jnp.dtype(values.dtype).itemsize,
        flops_per_slot=4.0,
        extra_bytes=2 * d_pad * jnp.dtype(cd).itemsize,
    )
    shift_p = jnp.asarray(shift_v, cd).reshape(1, 1)
    hv, usum = pl.pallas_call(
        functools.partial(_hvp_kernel, compute_dtype=cd),
        grid=(n_pad // br,),
        in_specs=[
            pl.BlockSpec((br, k), lambda i: (i, 0)),
            pl.BlockSpec((br, k), lambda i: (i, 0)),
            pl.BlockSpec((br,), lambda i: (i,)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, d_pad), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, d_pad), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, d_pad), cd),
            jax.ShapeDtypeStruct((1, 1), cd),
        ],
        interpret=dispatch.interpret_mode(),
    )(idx_p, val_p, rows[0], shift_p, v_p)
    return hv[0, :d], usum[0, 0]


# -- Hessian diagonal --------------------------------------------------------


def _hdiag_kernel(
    idx_ref, val_ref, y_ref, off_ref, ew_ref, w_ref,
    dx2_acc, dx_acc, csum_acc, *, loss, compute_dtype,
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dx2_acc[...] = jnp.zeros_like(dx2_acc)
        dx_acc[...] = jnp.zeros_like(dx_acc)
        csum_acc[...] = jnp.zeros_like(csum_acc)

    ix = idx_ref[...]
    v = val_ref[...].astype(compute_dtype)
    z = jnp.sum(v * w_ref[0, :][ix], axis=-1) + off_ref[...]
    c = ew_ref[...] * loss.d2(z, y_ref[...])
    csum_acc[0, 0] += jnp.sum(c)
    _scatter_rows(ix, _group_totals(ix, v * v * c[:, None]), dx2_acc)
    _scatter_rows(ix, _group_totals(ix, v * c[:, None]), dx_acc)


def fused_hessian_diagonal(
    indices, values, labels, offsets, ew, w_eff, d: int, loss
):
    """One design read -> (colsum(x^2, c), colsum(x, c), sum(c)) with
    c = ew * l''(z) computed from in-sweep margins — the whole variance
    pass, which the XLA path spends matvec + 2 colsums (3 reads) on."""
    n, k, cd, br, n_pad, d_pad, idx_p, val_p, rows, w_p = _prep(
        indices, values, (labels, offsets, ew), w_eff, d
    )
    dispatch.record_kernel_cost(
        "fused_hdiag", n, k, d, jnp.dtype(values.dtype).itemsize,
        flops_per_slot=5.0,
        extra_bytes=3 * d_pad * jnp.dtype(cd).itemsize,
    )
    dx2, dx, csum = pl.pallas_call(
        functools.partial(_hdiag_kernel, loss=loss, compute_dtype=cd),
        grid=(n_pad // br,),
        in_specs=[
            pl.BlockSpec((br, k), lambda i: (i, 0)),
            pl.BlockSpec((br, k), lambda i: (i, 0)),
            pl.BlockSpec((br,), lambda i: (i,)),
            pl.BlockSpec((br,), lambda i: (i,)),
            pl.BlockSpec((br,), lambda i: (i,)),
            pl.BlockSpec((1, d_pad), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, d_pad), lambda i: (0, 0)),
            pl.BlockSpec((1, d_pad), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, d_pad), cd),
            jax.ShapeDtypeStruct((1, d_pad), cd),
            jax.ShapeDtypeStruct((1, 1), cd),
        ],
        interpret=dispatch.interpret_mode(),
    )(idx_p, val_p, *rows, w_p)
    return dx2[0, :d], dx[0, :d], csum[0, 0]
