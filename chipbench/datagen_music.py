"""Inputs of the ``game_music_2re`` cells: ratings of songs by users, made on
the device from the seed in one jitted call (the Yahoo! Music shape of
Photon-ML's own GAME fixture: a fixed effect, a per-user and a per-song
random effect).

As in ``datagen.py`` the statistical problem comes from the configuration's
``data_seed`` and ``--seed`` draws an isomorphic copy of it: rows in another
order, user and song ids relabelled by two seed-drawn bijections, each
feature shard under its own signed permutation.  A user's and a song's row
COUNT is the same under every seed, so the bucketed designs keep their
shapes and the work does not move; which rows of an entity over the active
cap are sampled does move with the row order.

Two tables of opposite skew over the same rows:

* rows per user: a count k in ``[least, most]`` with p(k) ~ k^-s for every
  user, users laid end to end and the rows spread over them in proportion
  (few fat entities: hundreds of rows each, a tail of thousands);
* song of a row: a rank under p(r) ~ (r+1)^-z over the source's item count,
  independent of the user (many thin entities with a fat head).

The hidden model is a dense fixed vector plus one hashed vector per user
rank and one per song rank, so held-out AUC falls when either table is
zeroed.
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


def odd_multiplier(seed: int, tag: int) -> int:
    """``datagen.odd_multiplier`` with a tag: one bijection per id space."""
    rng = np.random.default_rng([int(seed), 0x0DD, int(tag)])
    return int(rng.integers(1 << 20, 1 << 31)) * 2 + 1


def _hashed_effect(rank, d: int, salt: int):
    """Hidden per-entity vector: a hash of (rank, column) to (-1, 1)."""
    cell = rank[:, None].astype(jnp.uint32) * jnp.uint32(d) + jnp.arange(
        d, dtype=jnp.uint32
    )
    return ((cell * jnp.uint32(salt)) >> 8).astype(jnp.float32) / float(
        1 << 23
    ) - 1.0


@partial(
    jax.jit,
    static_argnames=("n", "d_fixed", "d_user", "d_song", "users", "songs",
                     "song_ids", "user_exponent", "user_least", "user_most",
                     "song_zipf", "margin_scale"),
)
def _music_rows(model_key, data_key, perm_key, mult_u, mult_s, pf, sf, pu, su,
                ps, ss, *, n, d_fixed, d_user, d_song, users, songs, song_ids,
                user_exponent, user_least, user_most, song_zipf,
                margin_scale):
    k_c, k_w = jax.random.split(model_key)
    k_s, k_g, k_u, k_v, k_y = jax.random.split(data_key, 5)
    # rows per user: the same counts in the train and the held-out part (a
    # heavy user is heavy in both), scaled to the part's row count
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
    margin = margin_scale * (
        xg @ w_f / np.sqrt(d_fixed)
        + jnp.sum(xu * _hashed_effect(user_rank, d_user, 2246822519), axis=1)
        / np.sqrt(d_user)
        + jnp.sum(xs * _hashed_effect(song_rank, d_song, 3266489917), axis=1)
        / np.sqrt(d_song)
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
    """``{"features": {shard: (n, d) f32}, "entities": {name: (n,) int32},
    "labels": (n,) f32}`` of the ``train`` or ``heldout`` part.  ``param``
    reads a size of the configuration (``Run.param``: a rehearsal shrinks
    rows and entities).  ``num_users`` is both the number of users and
    their id space (a power of two); songs are drawn over ``num_songs``
    ranks and labelled in ``song_id_space`` (a power of two)."""
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
        users=users,
        songs=int(param("num_songs")), song_ids=song_ids,
        user_exponent=float(config["rows_per_user_exponent"]),
        user_least=int(config["rows_per_user_least"]),
        user_most=int(config["rows_per_user_most"]),
        song_zipf=float(config["song_zipf_exponent"]),
        margin_scale=float(config["margin_scale"]),
    )
