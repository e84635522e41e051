"""Plain reference for logistic GAME with a fixed effect, dense random
effects and per-entity SPARSE random effects over a bag of original columns
(``game_music_sparse_user``): float32 ``jax.numpy`` under matmul precision
"highest", written from the model equations, importing nothing of
``photon_ml_tpu``, in blocks of rows.

    z_i = x_i . w  +  xs_i . S[song_i]  +  sum_j v_ij U(user_i, c_ij)
    F   = sum_i softplus(-s_i z_i) + sum_c l2_c / 2 |params_c|^2

``U`` is what the program fetched: per-user (user, column, value) lists.
:func:`join` finds every stored entry (i, j) of the rows in them by a sort
of the (user, column) keys on the host (int64), an entry of a pair the
lists lack reading 0 (:func:`coefficients`); every gradient is then a segment sum over the pairs
(the sparse coefficients) or over the entities (a dense table).  The pairs
are the union of the fetched ones and the reference's own ACTIVE union (the
(user, column) pairs of the rows with a train weight): a gradient over it
misses no coefficient the trained objective has.

A coordinate is a dict ``{"kind": "fixed" | "random" | "sparse", "params",
"l2"}`` plus ``"x"`` (fixed, random), ``"ids"`` and ``"train_weight"``
(random, sparse) and, sparse, ``"entry_pair"`` (s, n) int32 (the pair of
each entry; the number of pairs where the entry has none) and ``"values"``
(s, n); a sparse coordinate's ``params`` are the (P,) pair coefficients.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import _blocks, _loss_terms


def join(ids, columns, values, train_weight, list_entities, list_columns,
         width: int):
    """Host side of the sort-join.  ``ids`` (n,) the rows' entities,
    ``columns`` / ``values`` (s, n) their stored entries (a column of
    ``width`` is a pad), ``train_weight`` (n,), and the (entity, column) of
    every entry of the fetched lists.  Returns ``{"entry_pair" (s, n)
    int32, "pairs" (P,) int64 keys entity * width + column, "list_at" (L,)
    each list entry's pair, "union_missing": pairs of the reference's
    active union the lists lack}``."""
    ids = np.asarray(ids).astype(np.int64)
    columns = np.asarray(columns).astype(np.int64)
    held = (columns < width) & (np.asarray(values) != 0)
    keys = ids[None, :] * width + columns
    active = held & (np.asarray(train_weight) > 0)[None, :]
    union = np.unique(keys[active])
    fetched = (np.asarray(list_entities).astype(np.int64) * width
               + np.asarray(list_columns).astype(np.int64))
    pairs = np.union1d(union, fetched)
    at = np.minimum(np.searchsorted(pairs, keys), max(pairs.size - 1, 0))
    hit = held & (pairs[at] == keys) if pairs.size else held & False
    return {
        "entry_pair": np.where(hit, at, pairs.size).astype(np.int32),
        "pairs": pairs,
        "list_at": np.searchsorted(pairs, fetched),
        "union_missing": int(union.size - np.count_nonzero(
            np.isin(union, fetched))),
    }


def coefficients(joined, list_values) -> np.ndarray:
    """(P,) float32: the fetched lists' values at their pairs, 0 at a pair
    of the reference's union the lists lack."""
    out = np.zeros(joined["pairs"].size, np.float32)
    out[joined["list_at"]] = list_values
    return out


@partial(jax.jit, static_argnames=("kinds", "dtype"))
def _block(kinds, xs, ids, train_weights, pair_index, pair_values, params,
           labels, dtype):
    with jax.default_matmul_precision("highest"):
        params = [p.astype(dtype) for p in params]
        z = jnp.zeros(labels.shape, dtype)
        for kind, x, i, e, v, p in zip(kinds, xs, ids, pair_index,
                                        pair_values, params):
            if kind == "fixed":
                z = z + jnp.sum(x.astype(dtype) * p, axis=1, dtype=dtype)
            elif kind == "random":
                z = z + jnp.sum(x.astype(dtype) * p[i], axis=1, dtype=dtype)
            else:
                padded = jnp.concatenate([p, jnp.zeros((1,), dtype)])
                z = z + jnp.sum(v.astype(dtype) * padded[e], axis=0,
                                dtype=dtype)
        loss, d1 = _loss_terms(z, labels.astype(dtype))
        grads = []
        for kind, x, i, tw, e, v, p in zip(kinds, xs, ids, train_weights,
                                            pair_index, pair_values, params):
            if kind == "fixed":
                grads.append(jnp.sum(x.astype(dtype) * d1[:, None], axis=0,
                                     dtype=dtype))
                continue
            r = d1 * tw.astype(dtype)
            if kind == "random":
                grads.append(jnp.zeros(p.shape, dtype).at[i].add(
                    x.astype(dtype) * r[:, None]))
            else:
                grads.append(jnp.zeros((p.shape[0] + 1,), dtype).at[
                    e.reshape(-1)].add((v.astype(dtype) * r[None, :])
                                       .reshape(-1))[:-1])
        return jnp.sum(loss, dtype=dtype), tuple(grads), z


def value_grads(coordinates, labels, dtype=jnp.float32):
    """(F, [gradient of each coordinate's trained objective], margins (n,))
    at the coordinates' ``params``."""
    kinds = tuple(c["kind"] for c in coordinates)
    params = [jnp.asarray(c["params"], jnp.float32) for c in coordinates]
    value = jnp.zeros((), dtype)
    grads = [jnp.zeros(p.shape, dtype) for p in params]
    margins = []

    def part(c, key, lo, hi, rows_axis=0):
        if key not in c:
            return None
        a = c[key]
        return a[lo:hi] if rows_axis == 0 else a[:, lo:hi]

    for lo, hi in _blocks(labels.shape[0]):
        v, g, z = _block(
            kinds,
            [part(c, "x", lo, hi) for c in coordinates],
            [part(c, "ids", lo, hi) if c["kind"] == "random" else None
             for c in coordinates],
            [part(c, "train_weight", lo, hi) for c in coordinates],
            [part(c, "entry_pair", lo, hi, 1) for c in coordinates],
            [part(c, "values", lo, hi, 1) for c in coordinates],
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


def sparse_scores(ids, columns, values, lists, width: int) -> np.ndarray:
    """(n,) float64 sum_j v_ij U(id_i, c_ij) on the host, from the lists
    (held-out rows: an entry of a pair the lists lack reads 0)."""
    ents, cols, vals = (np.asarray(a) for a in lists)
    fetched = ents.astype(np.int64) * width + cols.astype(np.int64)
    order = np.argsort(fetched, kind="stable")
    fetched, vals = fetched[order], vals[order].astype(np.float64)
    keys = (np.asarray(ids).astype(np.int64)[None, :] * width
            + np.asarray(columns).astype(np.int64))
    at = np.minimum(np.searchsorted(fetched, keys),
                    max(fetched.size - 1, 0))
    hit = ((np.asarray(columns) < width) & (fetched[at] == keys)
           if fetched.size else np.zeros(keys.shape, bool))
    coef = np.where(hit, vals[at] if fetched.size else 0.0, 0.0)
    return np.sum(np.asarray(values, np.float64) * coef, axis=0)
