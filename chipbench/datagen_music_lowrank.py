"""Inputs of the ``game_music_factored`` cell: ``datagen_music``'s ratings
(the same laws, keys and isomorphic seeds: rows a user, song of a row,
features, the fixed and the per-user hidden effects) with a per-song hidden
vector of LOW RANK,

    w_song = B* gamma*_song,   B* (d_song, k) drawn from ``data_seed``,
                               gamma*_song the hashed vector of the song's
                               rank in k dimensions,

so that a factored random effect of latent dimension k can reach it and a
rank-k table is what held-out AUC certifies.  ``datagen_music`` hides a
full-rank hashed vector a song, which no (d_song, k) projection holds.  The
song term is scaled by 1 / sqrt(d_song k): the variance a unit-normal B* and
hashed gammas in (-1, 1) give it is the 1/3 the hashed song vector has in
``game_music_2re``, so the three terms keep their shares of the margin.

``initial_projection`` is the factored coordinate's starting B0: drawn once
from ``data_seed`` in the problem's own coordinates and carried into the
seed's (the signed permutation of the song features), so that every seed
starts its solves from the SAME point of the same problem.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.datagen import (
    _relabel,
    _zipf_rank,
    key_of,
    signed_permutation,
)
from chipbench.datagen_music import _hashed_effect, odd_multiplier

_SONG_TAG = 3  # the song shard's signed permutation (datagen_music's order)


@partial(
    jax.jit,
    static_argnames=("n", "d_fixed", "d_user", "d_song", "latent", "users",
                     "songs", "song_ids", "user_exponent", "user_least",
                     "user_most", "song_zipf", "margin_scale"),
)
def _music_rows(model_key, data_key, perm_key, mult_u, mult_s, pf, sf, pu, su,
                ps, ss, *, n, d_fixed, d_user, d_song, latent, users, songs,
                song_ids, user_exponent, user_least, user_most, song_zipf,
                margin_scale):
    k_c, k_w = jax.random.split(model_key)
    k_b = jax.random.fold_in(model_key, 0xB)
    k_s, k_g, k_u, k_v, k_y = jax.random.split(data_key, 5)
    # rows per user and song of a row: datagen_music's, draw for draw
    a = 1.0 - user_exponent
    lo, hi = float(user_least) ** a, float(user_most + 1) ** a
    counts = jnp.clip(
        ((hi - lo) * jax.random.uniform(k_c, (users,)) + lo) ** (1.0 / a),
        user_least, user_most,
    ).astype(jnp.int32)
    ends = jnp.cumsum(counts)
    pos = jnp.arange(n, dtype=jnp.float32) * (
        ends[-1].astype(jnp.float32) / n
    )
    user_rank = jnp.clip(
        jnp.searchsorted(ends, pos.astype(jnp.int32), side="right"),
        0, users - 1,
    ).astype(jnp.int32)
    song_rank = _zipf_rank(jax.random.uniform(k_s, (n,)), songs, song_zipf)
    xg = jax.random.normal(k_g, (n, d_fixed), jnp.float32)
    xu = jax.random.normal(k_u, (n, d_user), jnp.float32)
    xs = jax.random.normal(k_v, (n, d_song), jnp.float32)
    w_f = jax.random.normal(k_w, (d_fixed,), jnp.float32)
    b_star = jax.random.normal(k_b, (d_song, latent), jnp.float32)
    margin = margin_scale * (
        xg @ w_f / np.sqrt(d_fixed)
        + jnp.sum(xu * _hashed_effect(user_rank, d_user, 2246822519), axis=1)
        / np.sqrt(d_user)
        + jnp.sum(
            (xs @ b_star) * _hashed_effect(song_rank, latent, 3266489917),
            axis=1,
        )
        / np.sqrt(d_song * latent)
    )
    labels = (
        jax.random.uniform(k_y, (n,)) < jax.nn.sigmoid(margin)
    ).astype(jnp.float32)
    order = jax.random.permutation(perm_key, n)
    user = _relabel(user_rank, mult_u, users)
    song = _relabel(song_rank, mult_s, song_ids)
    return {
        "features": {
            "global": (xg[:, pf] * sf)[order],
            "per_user": (xu[:, pu] * su)[order],
            "per_song": (xs[:, ps] * ss)[order],
        },
        "entities": {"userId": user[order], "songId": song[order]},
        "labels": labels[order],
    }


def music_rows(config: dict, param, seed: int, n: int, part: str):
    """``datagen_music.music_rows`` with the low-rank song effect: the same
    dict of ``features``, ``entities`` and ``labels``."""
    users, song_ids = int(param("num_users")), int(param("song_id_space"))
    model_key = key_of(config["data_seed"])
    data_key = jax.random.fold_in(model_key, {"train": 1, "heldout": 2}[part])
    perm_key = jax.random.fold_in(key_of(seed), 31)
    dims = [int(config[k]) for k in ("fixed_dim", "user_dim", "song_dim")]
    (pf, sf), (pu, su), (ps, ss) = (
        signed_permutation(seed, d, tag) for tag, d in enumerate(dims, 1)
    )
    return _music_rows(
        model_key, data_key, perm_key,
        jnp.uint32(odd_multiplier(seed, 1)),
        jnp.uint32(odd_multiplier(seed, 2)),
        pf, sf, pu, su, ps, ss,
        n=int(n),
        d_fixed=dims[0], d_user=dims[1], d_song=dims[2],
        latent=int(config["latent_dim"]),
        users=users,
        songs=int(param("num_songs")), song_ids=song_ids,
        user_exponent=float(config["rows_per_user_exponent"]),
        user_least=int(config["rows_per_user_least"]),
        user_most=int(config["rows_per_user_most"]),
        song_zipf=float(config["song_zipf_exponent"]),
        margin_scale=float(config["margin_scale"]),
    )


def initial_projection(config: dict, seed: int) -> np.ndarray:
    """(song_dim, latent_dim) float32 B0 ~ N(0, 1 / song_dim), one draw from
    ``data_seed``, in this seed's coordinates: row j is the draw's row
    ``perm[j]`` under the sign the seed gives song feature j, so that
    ``x_seed @ B0_seed == x @ B0`` row for row."""
    d, k = int(config["song_dim"]), int(config["latent_dim"])
    rng = np.random.default_rng([int(config["data_seed"]), 0xB0])
    b0 = rng.normal(0.0, 1.0 / np.sqrt(d), size=(d, k)).astype(np.float32)
    perm, sign = (np.asarray(a) for a in signed_permutation(seed, d,
                                                            _SONG_TAG))
    return b0[perm] * sign[:, None].astype(np.float32)
