"""Plain reference for logistic GAME with a fixed effect, plain random
effects and FACTORED random effects (w_e = B gamma_e), the benchmark's copy
(``tests/reference_game.py`` is the repo's, with the materialised Kronecker
design and the Newton loops): float32 ``jax.numpy`` under matmul precision
"highest", written from the model equations, importing nothing of
``photon_ml_tpu``, in blocks of rows so that it fits beside the inputs.

    z_i = sum_c score_c(i),  score_fixed(i)    = x_i . w,
                             score_random(i)   = x_i . T[id_i],
                             score_factored(i) = (B^T x_i) . G[id_i]
    F   = sum_i softplus(-s_i z_i) + sum_c l2_c / 2 |params_c|^2,  s = 2y - 1
          (a factored coordinate pays l2 / 2 |G|^2 + l2_projection / 2 |B|^2)

``F`` sums over all rows, active and passive: what coordinate descent
reports.  The gradient of a coordinate is that of the objective the
coordinate is *trained* on, as in ``reference_multi.py``: every row with
weight 1 for a fixed effect, each row with its ``train_weight`` for a random
effect, plain or factored.  Both leaves of a factored coordinate are trained
on the same weighted active sample:

    dF/dG[e] = sum_{i: id_i = e} t_i r_i (B^T x_i) + l2 G[e]
    dF/dB    = sum_i t_i r_i x_i (x) G[id_i]       + l2_projection B,
    r_i = sigmoid(z_i) - y_i

A coordinate is a dict ``{"kind": "fixed" | "random" | "factored", "x": (n,
d), "params": (d,) | (E, d) | {"gamma": (E, k), "projection": (d, k)},
"l2": float}`` plus, when not fixed, ``"ids": (n,)`` and ``"train_weight":
(n,)``, and when factored ``"l2_projection"``.  ``dtype`` is the precision
of the control: the same equations with every array, product and sum held in
that type.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from chipbench.reference import _blocks, _loss_terms


def _score(kind, x, ids, p, dtype):
    if kind == "fixed":
        return jnp.sum(x * p, axis=1, dtype=dtype)
    if kind == "random":
        return jnp.sum(x * p[ids], axis=1, dtype=dtype)
    latent = jnp.matmul(x, p["projection"]).astype(dtype)
    return jnp.sum(latent * p["gamma"][ids], axis=1, dtype=dtype)


@partial(jax.jit, static_argnames=("kinds", "dtype"))
def _block(kinds, xs, ids, train_weights, params, labels, dtype):
    with jax.default_matmul_precision("highest"):
        cast = lambda a: a.astype(dtype)
        xs = [cast(x) for x in xs]
        params = jax.tree_util.tree_map(cast, params)
        z = jnp.zeros(labels.shape, dtype)
        for kind, x, i, p in zip(kinds, xs, ids, params):
            z = z + _score(kind, x, i, p, dtype)
        loss, d1 = _loss_terms(z, cast(labels))
        grads = []
        for kind, x, i, tw, p in zip(kinds, xs, ids, train_weights, params):
            if kind == "fixed":
                grads.append(jnp.sum(x * d1[:, None], axis=0, dtype=dtype))
                continue
            r = (d1 * cast(tw))[:, None]
            if kind == "random":
                grads.append(jnp.zeros(p.shape, dtype).at[i].add(x * r))
                continue
            latent = cast(jnp.matmul(x, p["projection"]))
            grads.append({
                "gamma": jnp.zeros(p["gamma"].shape, dtype).at[i].add(
                    latent * r),
                "projection": cast(jnp.matmul(x.T, p["gamma"][i] * r)),
            })
        return jnp.sum(loss, dtype=dtype), tuple(grads), z


def _penalty(coordinate, p, dtype):
    """(l2 / 2 |p|^2, l2 p) leaf by leaf; a factored coordinate's
    projection under its own weight."""
    def leaf(weight, a):
        weight = jnp.asarray(weight, dtype)
        return 0.5 * weight * jnp.sum(a * a, dtype=dtype), weight * a

    if coordinate["kind"] != "factored":
        return leaf(coordinate["l2"], p)
    vg, gg = leaf(coordinate["l2"], p["gamma"])
    vb, gb = leaf(coordinate["l2_projection"], p["projection"])
    return vg + vb, {"gamma": gg, "projection": gb}


def value_grads(coordinates, labels, dtype=jnp.float32):
    """(F, [gradient of each coordinate's trained objective], margins (n,))
    at the coordinates' ``params``; a factored coordinate's gradient is a
    dict of its two leaves."""
    add = lambda a, b: jax.tree_util.tree_map(jnp.add, a, b)
    kinds = tuple(c["kind"] for c in coordinates)
    params = [
        jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                               c["params"])
        for c in coordinates
    ]
    value = jnp.zeros((), dtype)
    grads = [
        jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, dtype), p)
        for p in params
    ]
    margins = []
    for lo, hi in _blocks(labels.shape[0]):
        v, g, z = _block(
            kinds,
            [c["x"][lo:hi] for c in coordinates],
            [c["ids"][lo:hi] if c["kind"] != "fixed" else None
             for c in coordinates],
            [c["train_weight"][lo:hi] if c["kind"] != "fixed" else None
             for c in coordinates],
            params, labels[lo:hi], dtype,
        )
        value = value + v
        grads = [add(a, b) for a, b in zip(grads, g)]
        margins.append(z)
    for k, (c, p) in enumerate(zip(coordinates, params)):
        v, g = _penalty(
            c, jax.tree_util.tree_map(lambda a: a.astype(dtype), p), dtype)
        value = value + v
        grads[k] = add(grads[k], g)
    to32 = lambda a: a.astype(jnp.float32)
    return (
        to32(value),
        [jax.tree_util.tree_map(to32, g) for g in grads],
        to32(jnp.concatenate(margins)),
    )
