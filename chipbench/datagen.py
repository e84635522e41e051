"""Inputs of every cell, made on the device from the seed in one jitted call.

A cell's *work* must not change with ``--seed`` (the driver's runs of one set
use different seeds, and their spread is held against the bounds), but its
inputs must.  So the statistical problem is drawn from the configuration's own
``data_seed`` and ``--seed`` draws an isomorphic copy of it: rows in another
order, hashed columns / user ids relabelled by a seed-drawn bijection, dense
features under a seed-drawn signed permutation.  Every copy has the same
solver trajectory up to float summation order, the same held-out AUC and the
same bytes to move; no two seeds give the same arrays.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def key_of(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


def odd_multiplier(seed: int) -> int:
    """A seed-drawn odd 32-bit multiplier: x -> (x * m) mod 2**k is a
    bijection of [0, 2**k) for every k <= 32."""
    rng = np.random.default_rng([int(seed), 0x0DD])
    return int(rng.integers(1 << 20, 1 << 31)) * 2 + 1


def _zipf_rank(u, n_items: int, exponent: float):
    """Inverse-CDF draw of a rank in [0, n_items) under p(r) ~ (r+1)^-s
    (continuous approximation; s != 1)."""
    a = 1.0 - exponent
    r = ((float(n_items) ** a - 1.0) * u + 1.0) ** (1.0 / a)
    return jnp.clip(r.astype(jnp.int32) - 1, 0, n_items - 1)


def _relabel(x, mult, n_items: int):
    """Bijection of [0, n_items), n_items a power of two; ``mult`` an odd
    uint32 scalar (traced: one compiled program serves every seed)."""
    return ((x.astype(jnp.uint32) * mult) & jnp.uint32(n_items - 1)).astype(
        jnp.int32
    )


# ---------------------------------------------------------------------------
# Criteo-shaped hashed sparse rows
# ---------------------------------------------------------------------------


@partial(
    jax.jit,
    static_argnames=("n", "d", "numeric", "categorical", "zipf",
                     "margin_scale"),
)
def _glm_rows(data_key, perm_key, mult, *, n, d, numeric, categorical, zipf,
              margin_scale):
    k_u, k_v, k_y = jax.random.split(data_key, 3)
    # the hidden model lives on RANKS (seed-free); a column id is its rank
    # relabelled by the seed's bijection, so every seed learns the same model
    # at other addresses
    rank = _zipf_rank(
        jax.random.uniform(k_u, (n, categorical)), d - numeric, zipf
    ) + numeric
    num_rank = jnp.broadcast_to(jnp.arange(numeric, dtype=jnp.int32),
                                (n, numeric))
    ranks = jnp.concatenate([num_rank, rank], axis=1)
    values = jnp.concatenate(
        [
            jnp.log1p(jax.random.exponential(k_v, (n, numeric))),
            jnp.ones((n, categorical), jnp.float32),
        ],
        axis=1,
    )
    # hidden coefficient of a rank: a hash of the rank to (-1, 1)
    h = (ranks.astype(jnp.uint32) * jnp.uint32(2654435761)) >> 8
    w_true = h.astype(jnp.float32) / float(1 << 23) - 1.0
    margin = margin_scale * jnp.sum(values * w_true, axis=1)
    labels = (
        jax.random.uniform(k_y, (n,)) < jax.nn.sigmoid(margin)
    ).astype(jnp.float32)
    order = jax.random.permutation(perm_key, n)
    indices = _relabel(ranks, mult, d)
    return indices[order], values[order], labels[order]


def glm_rows(config: dict, seed: int, n: int, part: str):
    """(indices (n, slots) int32, values (n, slots) f32, labels (n,) f32) of
    the ``train`` or ``heldout`` part."""
    data_key = jax.random.fold_in(
        key_of(config["data_seed"]), {"train": 0, "heldout": 1}[part]
    )
    perm_key = jax.random.fold_in(key_of(seed), 17)
    return _glm_rows(
        data_key,
        perm_key,
        jnp.uint32(odd_multiplier(seed)),
        n=int(n),
        d=int(config["num_coefficients"]),
        numeric=int(config["numeric_slots"]),
        categorical=int(config["categorical_slots"]),
        zipf=float(config["column_zipf_exponent"]),
        margin_scale=float(config["margin_scale"]),
    )


# ---------------------------------------------------------------------------
# GAME rows: fixed effect + one per-user random effect
# ---------------------------------------------------------------------------


def signed_permutation(seed: int, d: int, tag: int):
    rng = np.random.default_rng([int(seed), tag])
    return rng.permutation(d).astype(np.int32), rng.choice(
        np.array([-1.0, 1.0], np.float32), size=d
    )


@partial(
    jax.jit,
    static_argnames=("n", "d_fixed", "d_user", "users", "zipf", "cap",
                     "margin_scale"),
)
def _game_rows(model_key, data_key, perm_key, mult, pf, sf, pu, su, *, n,
               d_fixed, d_user, users, zipf, cap, margin_scale):
    k_e, k_g, k_u, k_y = jax.random.split(data_key, 4)
    # rows per user: a count k in 1..cap with p(k) ~ k^-zipf for every user,
    # users laid end to end and the rows spread over them in proportion
    a = 1.0 - zipf
    u = jax.random.uniform(k_e, (users,))
    counts = jnp.clip(
        (((cap + 1.0) ** a - 1.0) * u + 1.0) ** (1.0 / a), 1, cap
    ).astype(jnp.int32)
    ends = jnp.cumsum(counts)
    pos = jnp.arange(n, dtype=jnp.float32) * (
        ends[-1].astype(jnp.float32) / n
    )
    rank = jnp.clip(
        jnp.searchsorted(ends, pos.astype(jnp.int32), side="right"),
        0, users - 1,
    ).astype(jnp.int32)
    xg = jax.random.normal(k_g, (n, d_fixed), jnp.float32)
    xu = jax.random.normal(k_u, (n, d_user), jnp.float32)
    w_f = jax.random.normal(model_key, (d_fixed,), jnp.float32)
    # hidden per-user effect: a hash of (rank, column) to (-1, 1)
    cell = rank[:, None].astype(jnp.uint32) * jnp.uint32(d_user) + jnp.arange(
        d_user, dtype=jnp.uint32
    )
    w_u = ((cell * jnp.uint32(2246822519)) >> 8).astype(
        jnp.float32
    ) / float(1 << 23) - 1.0
    margin = margin_scale * (
        xg @ w_f / np.sqrt(d_fixed) + jnp.sum(xu * w_u, axis=1) / np.sqrt(d_user)
    )
    labels = (
        jax.random.uniform(k_y, (n,)) < jax.nn.sigmoid(margin)
    ).astype(jnp.float32)
    order = jax.random.permutation(perm_key, n)
    user = _relabel(rank, mult, users)
    return (xg[:, pf] * sf)[order], (xu[:, pu] * su)[order], user[order], \
        labels[order]


def game_rows(config: dict, seed: int, n: int, users: int, part: str):
    """(fixed features (n, 64), user features (n, 16), user ids (n,) int32,
    labels (n,)) of the ``train`` or ``heldout`` part."""
    model_key = key_of(config["data_seed"])
    data_key = jax.random.fold_in(model_key, {"train": 1, "heldout": 2}[part])
    perm_key = jax.random.fold_in(key_of(seed), 29)
    pf, sf = signed_permutation(seed, int(config["fixed_dim"]), 1)
    pu, su = signed_permutation(seed, int(config["user_dim"]), 2)
    return _game_rows(
        model_key, data_key, perm_key, jnp.uint32(odd_multiplier(seed)),
        pf, sf, pu, su,
        n=int(n),
        d_fixed=int(config["fixed_dim"]),
        d_user=int(config["user_dim"]),
        users=int(users),
        zipf=float(config["rows_per_user_zipf_exponent"]),
        cap=int(config["rows_per_user_cap"]),
        margin_scale=float(config["margin_scale"]),
    )
