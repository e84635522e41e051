"""Plain reference of the ``game_music_2re_x4`` configuration: logistic GAME
with a fixed effect and K random effects, ``reference_multi.py``'s
mathematics over rows that lie on the HOST, a block at a time, so that a row
count no one chip holds still fits: float32 ``jax.numpy`` under matmul
precision "highest", written from the model equations, importing nothing of
``photon_ml_tpu``.  The tables come in GLOBAL entity order, as the task
fetches them: a program sharded over four chips and this unsharded sum have
to agree.

    z_i = sum_c score_c(i),  score_fixed(i) = x_i . w,
                             score_random(i) = x_i . T[id_i]
    F   = sum_i softplus(-s_i z_i) + sum_c l2_c / 2 |params_c|^2,  s = 2y - 1

``F`` sums over all rows, active and passive.  The gradient of a coordinate
is that of the objective the coordinate is *trained* on: every row with
weight 1 for a fixed effect; for a random effect each row with its
``train_weight`` (1 under the active cap, count / cap for a sampled row of an
entity over it, 0 for a passive row).

A coordinate is a dict ``{"kind": "fixed" | "random", "x": (n, d),
"params": (d,) | (E, d), "l2": float}`` plus, when random, ``"ids": (n,)``
and ``"train_weight": (n,)``; ``x``, ``ids``, ``train_weight`` and
``labels`` are numpy arrays.  ``dtype`` is the precision of the control: the
same equations with every array, product and sum held in that type.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

BLOCK = 1 << 18


@partial(jax.jit, static_argnames=("kinds", "dtype"))
def _block(kinds, xs, ids, train_weights, params, labels, dtype):
    with jax.default_matmul_precision("highest"):
        y = labels.astype(dtype)
        z = jnp.zeros(y.shape, dtype)
        for kind, x, i, p in zip(kinds, xs, ids, params):
            rows = p if kind == "fixed" else p[i]
            z = z + jnp.sum(x.astype(dtype) * rows, axis=1, dtype=dtype)
        loss = jax.nn.softplus(-(2.0 * y - 1.0) * z)
        d1 = jax.nn.sigmoid(z) - y
        grads = []
        for kind, x, i, tw, p in zip(kinds, xs, ids, train_weights, params):
            x = x.astype(dtype)
            if kind == "fixed":
                grads.append(jnp.sum(x * d1[:, None], axis=0, dtype=dtype))
            else:
                r = (d1 * tw.astype(dtype))[:, None]
                grads.append(jnp.zeros(p.shape, dtype).at[i].add(x * r))
        return jnp.sum(loss, dtype=dtype), tuple(grads)


def value_grads(coordinates, labels, dtype=jnp.float32, block=BLOCK):
    """(F, [gradient of each coordinate's trained objective]) at the
    coordinates' ``params``, summed over blocks of ``block`` rows."""
    kinds = tuple(c["kind"] for c in coordinates)
    params = [jnp.asarray(c["params"], jnp.float32).astype(dtype)
              for c in coordinates]
    value = jnp.zeros((), dtype)
    grads = [jnp.zeros(p.shape, dtype) for p in params]
    n = labels.shape[0]
    for lo in range(0, n, block):
        hi = min(lo + block, n)
        v, g = _block(
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
    for k, (c, p) in enumerate(zip(coordinates, params)):
        value = value + jnp.asarray(0.5 * c["l2"], dtype) * jnp.sum(
            p * p, dtype=dtype)
        grads[k] = grads[k] + jnp.asarray(c["l2"], dtype) * p
    return value.astype(jnp.float32), [g.astype(jnp.float32) for g in grads]
