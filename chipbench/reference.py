"""Plain references: float32 ``jax.numpy`` under matmul precision "highest",
written from the model equations and importing nothing of ``photon_ml_tpu``.

L2 logistic GLM over padded sparse rows
    f(w) = sum_i softplus(-s_i z_i) + l2/2 |w|^2,  z_i = sum_k v_ik w[c_ik],
    s_i = 2 y_i - 1;  grad = sum_i (sigmoid(z_i) - y_i) v_ik e[c_ik] + l2 w.
GAME logistic, fixed effect + one per-user random effect
    z_i = xg_i . w + xu_i . T[user_i];
    f = sum_i softplus(-s_i z_i) + lf/2 |w|^2 + lr/2 |T|^2.

``dtype`` is the precision of the control: the same equations with every
array, product and sum held in that type (bfloat16 for these float32
configurations).  Everything runs in blocks of rows so that it fits beside
the inputs.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_BLOCK = 1 << 18


def _blocks(n: int):
    return [(lo, min(lo + _BLOCK, n)) for lo in range(0, n, _BLOCK)]


def _loss_terms(z, y):
    s = 2.0 * y - 1.0
    return jax.nn.softplus(-s * z), jax.nn.sigmoid(z) - y


@partial(jax.jit, static_argnames=("dtype",))
def _glm_block(indices, values, labels, w, dtype):
    with jax.default_matmul_precision("highest"):
        v = values.astype(dtype)
        z = jnp.sum(v * w.astype(dtype)[indices], axis=1, dtype=dtype)
        loss, d1 = _loss_terms(z, labels.astype(dtype))
        upd = (v * d1[:, None]).reshape(-1)
        grad = jnp.zeros(w.shape, dtype).at[indices.reshape(-1)].add(upd)
        return jnp.sum(loss, dtype=dtype), grad, z


def glm_value_grad(indices, values, labels, w, l2: float,
                   dtype=jnp.float32):
    """(value, gradient (d,), margins (n,)) of the L2 logistic objective."""
    w = jnp.asarray(w, jnp.float32)
    value = jnp.zeros((), dtype)
    grad = jnp.zeros(w.shape, dtype)
    margins = []
    for lo, hi in _blocks(indices.shape[0]):
        v, g, z = _glm_block(
            indices[lo:hi], values[lo:hi], labels[lo:hi], w, dtype
        )
        value, grad = value + v, grad + g
        margins.append(z)
    wd = w.astype(dtype)
    value = value + jnp.asarray(0.5 * l2, dtype) * jnp.sum(wd * wd, dtype=dtype)
    grad = grad + jnp.asarray(l2, dtype) * wd
    return (
        value.astype(jnp.float32),
        grad.astype(jnp.float32),
        jnp.concatenate(margins).astype(jnp.float32),
    )


@partial(jax.jit, static_argnames=("dtype",))
def _game_block(xg, xu, user, labels, w_f, table, dtype):
    with jax.default_matmul_precision("highest"):
        xg, xu = xg.astype(dtype), xu.astype(dtype)
        z = jnp.sum(xg * w_f.astype(dtype), axis=1, dtype=dtype) + jnp.sum(
            xu * table.astype(dtype)[user], axis=1, dtype=dtype
        )
        loss, d1 = _loss_terms(z, labels.astype(dtype))
        g_f = jnp.sum(xg * d1[:, None], axis=0, dtype=dtype)
        g_t = jnp.zeros(table.shape, dtype).at[user].add(xu * d1[:, None])
        return jnp.sum(loss, dtype=dtype), g_f, g_t, z


def game_value_grads(xg, xu, user, labels, w_f, table, l2_fixed: float,
                     l2_user: float, dtype=jnp.float32):
    """(value, grad wrt fixed (d,), grad wrt table (E, d_u), margins (n,))."""
    w_f = jnp.asarray(w_f, jnp.float32)
    table = jnp.asarray(table, jnp.float32)
    value = jnp.zeros((), dtype)
    g_f = jnp.zeros(w_f.shape, dtype)
    g_t = jnp.zeros(table.shape, dtype)
    margins = []
    for lo, hi in _blocks(xg.shape[0]):
        v, a, b, z = _game_block(
            xg[lo:hi], xu[lo:hi], user[lo:hi], labels[lo:hi], w_f, table,
            dtype,
        )
        value, g_f, g_t = value + v, g_f + a, g_t + b
        margins.append(z)
    wf, tb = w_f.astype(dtype), table.astype(dtype)
    value = (
        value
        + jnp.asarray(0.5 * l2_fixed, dtype) * jnp.sum(wf * wf, dtype=dtype)
        + jnp.asarray(0.5 * l2_user, dtype) * jnp.sum(tb * tb, dtype=dtype)
    )
    g_f = g_f + jnp.asarray(l2_fixed, dtype) * wf
    g_t = g_t + jnp.asarray(l2_user, dtype) * tb
    return (
        value.astype(jnp.float32),
        g_f.astype(jnp.float32),
        g_t.astype(jnp.float32),
        jnp.concatenate(margins).astype(jnp.float32),
    )


def game_scores(xg, xu, rows, w_f, dtype=jnp.float32):
    """Served margin of each request: xg . w + xu . (its user's table row).
    ``rows`` are the table rows of the requests' users, (n, d_u)."""
    with jax.default_matmul_precision("highest"):
        xg, xu = jnp.asarray(xg, dtype), jnp.asarray(xu, dtype)
        z = jnp.sum(xg * jnp.asarray(w_f, dtype), axis=1, dtype=dtype)
        z = z + jnp.sum(xu * jnp.asarray(rows, dtype), axis=1, dtype=dtype)
        return np.asarray(z.astype(jnp.float32))


def auc(labels, scores) -> float:
    """Area under the ROC curve by ranks (ties share their mean rank)."""
    y = np.asarray(labels) > 0.5
    s = np.asarray(scores, np.float64)
    order = np.argsort(s, kind="stable")
    ranks = np.empty(s.size, np.float64)
    sorted_s = s[order]
    # mean rank of each run of ties
    edges = np.flatnonzero(np.diff(sorted_s)) + 1
    starts = np.concatenate([[0], edges])
    ends = np.concatenate([edges, [s.size]])
    mean_rank = (starts + ends + 1) / 2.0
    ranks[order] = np.repeat(mean_rank, ends - starts)
    pos = int(y.sum())
    neg = y.size - pos
    if pos == 0 or neg == 0:
        return float("nan")
    return float((ranks[y].sum() - pos * (pos + 1) / 2.0) / (pos * neg))


def rel_gap(a, b) -> float:
    """|a - b| over |b|, as a float."""
    a, b = float(a), float(b)
    return abs(a - b) / max(abs(b), 1e-30)


def rel_l2(a, b) -> float:
    """|a - b|_2 over |b|_2, on the host in float64."""
    a = np.asarray(a, np.float64).ravel()
    b = np.asarray(b, np.float64).ravel()
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))
