"""Plain reference for logistic GAME with a fixed effect and K random
effects, the benchmark's copy (``tests/reference_game.py`` is the repo's,
with the per-entity Newton loop): float32 ``jax.numpy`` under matmul
precision "highest", written from the model equations, importing nothing of
``photon_ml_tpu``, in blocks of rows so that it fits beside the inputs.

    z_i = sum_c score_c(i),  score_fixed(i) = x_i . w,
                             score_random(i) = x_i . T[id_i]
    F   = sum_i softplus(-s_i z_i) + sum_c l2_c / 2 |params_c|^2,  s = 2y - 1

``F`` sums over all rows, active and passive: what coordinate descent
reports.  The gradient of a coordinate is that of the objective the
coordinate is *trained* on: every row with weight 1 for a fixed effect; for
a random effect each row with its ``train_weight`` (1 for a row of an entity
under the active cap, count / cap for a sampled row of an entity over it, 0
for a passive row), which the task reads from the design once at set-up.

A coordinate is a dict ``{"kind": "fixed" | "random", "x": (n, d),
"params": (d,) | (E, d), "l2": float}`` plus, when random, ``"ids": (n,)``
and ``"train_weight": (n,)``.  ``dtype`` is the precision of the control:
the same equations with every array, product and sum held in that type.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from chipbench.reference import _blocks, _loss_terms


@partial(jax.jit, static_argnames=("kinds", "dtype"))
def _block(kinds, xs, ids, train_weights, params, labels, dtype):
    with jax.default_matmul_precision("highest"):
        xs = [x.astype(dtype) for x in xs]
        params = [p.astype(dtype) for p in params]
        z = jnp.zeros(labels.shape, dtype)
        for kind, x, i, p in zip(kinds, xs, ids, params):
            rows = p if kind == "fixed" else p[i]
            z = z + jnp.sum(x * rows, axis=1, dtype=dtype)
        loss, d1 = _loss_terms(z, labels.astype(dtype))
        grads = []
        for kind, x, i, tw, p in zip(kinds, xs, ids, train_weights, params):
            if kind == "fixed":
                grads.append(jnp.sum(x * d1[:, None], axis=0, dtype=dtype))
            else:
                r = (d1 * tw.astype(dtype))[:, None]
                grads.append(jnp.zeros(p.shape, dtype).at[i].add(x * r))
        return jnp.sum(loss, dtype=dtype), tuple(grads), z


def value_grads(coordinates, labels, dtype=jnp.float32):
    """(F, [gradient of each coordinate's trained objective], margins (n,))
    at the coordinates' ``params``."""
    kinds = tuple(c["kind"] for c in coordinates)
    params = [jnp.asarray(c["params"], jnp.float32) for c in coordinates]
    value = jnp.zeros((), dtype)
    grads = [jnp.zeros(p.shape, dtype) for p in params]
    margins = []
    for lo, hi in _blocks(labels.shape[0]):
        v, g, z = _block(
            kinds,
            [c["x"][lo:hi] for c in coordinates],
            [c["ids"][lo:hi] if c["kind"] == "random" else None
             for c in coordinates],
            [c["train_weight"][lo:hi] if c["kind"] == "random" else None
             for c in coordinates],
            params, labels[lo:hi], dtype,
        )
        value = value + v
        grads = [a + b for a, b in zip(grads, g)]
        margins.append(z)
    for k, (c, p) in enumerate(zip(coordinates, params)):
        p = p.astype(dtype)
        value = value + jnp.asarray(0.5 * c["l2"], dtype) * jnp.sum(
            p * p, dtype=dtype)
        grads[k] = grads[k] + jnp.asarray(c["l2"], dtype) * p
    return (
        value.astype(jnp.float32),
        [g.astype(jnp.float32) for g in grads],
        jnp.concatenate(margins).astype(jnp.float32),
    )
