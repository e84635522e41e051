"""Inputs of the ``game_music_2re_x4`` cell: ``datagen_music``'s ratings, made
in BLOCKS of rows and gathered on the host, for a row count that no one chip
holds (one call of ``datagen_music.music_rows`` at 2^24 rows would need more
than a chip has, and what a generator leaves on chip 0 would read as the
cell's memory peak).  The device never holds more than one block; the task
shards the host arrays over the chips itself.

The laws are ``datagen_music``'s, letter for letter: a row count k in
``[least, most]`` with p(k) ~ k^-s for every user, the users laid end to end
and the n rows spread over them in proportion; a song rank under p(r) ~
(r+1)^-z, independent of the user; a hidden dense fixed vector plus one
hashed vector a user rank and a song rank.  Row i of the statistical problem
(before the seed's reordering) belongs to block i // block_rows and draws
its song, features and label from the configuration's ``data_seed`` folded
with the part and the block, so the problem does not depend on ``--seed``.
``--seed`` draws an isomorphic copy: the blocks in another order, the rows of
a block in another order, user and song ids relabelled by two bijections,
each feature shard under its own signed permutation.  A user's and a song's
row COUNT is the same under every seed.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.datagen import _relabel, _zipf_rank, key_of, signed_permutation
from chipbench.datagen_music import _hashed_effect, odd_multiplier


@partial(
    jax.jit,
    static_argnames=("m", "n", "d_fixed", "d_user", "d_song", "users",
                     "songs", "song_ids", "user_exponent", "user_least",
                     "user_most", "song_zipf", "margin_scale"),
)
def _music_block(model_key, part_key, perm_key, block, mult_u, mult_s, pf, sf,
                 pu, su, ps, ss, *, m, n, d_fixed, d_user, d_song, users,
                 songs, song_ids, user_exponent, user_least, user_most,
                 song_zipf, margin_scale):
    k_c, k_w = jax.random.split(model_key)
    k_s, k_g, k_u, k_v, k_y = jax.random.split(
        jax.random.fold_in(part_key, block), 5)
    # rows per user: the same counts in every block and part
    a = 1.0 - user_exponent
    lo, hi = float(user_least) ** a, float(user_most + 1) ** a
    counts = jnp.clip(
        ((hi - lo) * jax.random.uniform(k_c, (users,)) + lo) ** (1.0 / a),
        user_least, user_most,
    ).astype(jnp.int32)
    ends = jnp.cumsum(counts)
    row = block * m + jnp.arange(m, dtype=jnp.int32)
    pos = row.astype(jnp.float32) * (ends[-1].astype(jnp.float32) / n)
    user_rank = jnp.clip(
        jnp.searchsorted(ends, pos.astype(jnp.int32), side="right"),
        0, users - 1,
    ).astype(jnp.int32)
    song_rank = _zipf_rank(jax.random.uniform(k_s, (m,)), songs, song_zipf)
    xg = jax.random.normal(k_g, (m, d_fixed), jnp.float32)
    xu = jax.random.normal(k_u, (m, d_user), jnp.float32)
    xs = jax.random.normal(k_v, (m, d_song), jnp.float32)
    w_f = jax.random.normal(k_w, (d_fixed,), jnp.float32)
    margin = margin_scale * (
        xg @ w_f / np.sqrt(d_fixed)
        + jnp.sum(xu * _hashed_effect(user_rank, d_user, 2246822519), axis=1)
        / np.sqrt(d_user)
        + jnp.sum(xs * _hashed_effect(song_rank, d_song, 3266489917), axis=1)
        / np.sqrt(d_song)
    )
    labels = (
        jax.random.uniform(k_y, (m,)) < jax.nn.sigmoid(margin)
    ).astype(jnp.float32)
    order = jax.random.permutation(jax.random.fold_in(perm_key, block), m)
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


def music_rows_host(config: dict, param, seed: int, n: int, part: str):
    """``{"features": {shard: (n, d) f32}, "entities": {name: (n,) int32},
    "labels": (n,) f32}`` of the ``train`` or ``heldout`` part, as numpy
    arrays on the host, made a block of ``generator_block_rows`` rows at a
    time on the device.  ``param`` reads a size of the configuration
    (``Run.param``)."""
    n, m = int(n), min(int(param("generator_block_rows")), int(n))
    if n % m:
        raise ValueError(f"{n} rows are not whole blocks of {m}")
    users, song_ids = int(param("num_users")), int(param("song_id_space"))
    model_key = key_of(config["data_seed"])
    part_key = jax.random.fold_in(model_key, {"train": 1, "heldout": 2}[part])
    perm_key = jax.random.fold_in(key_of(seed), 31)
    dims = [int(config[k]) for k in ("fixed_dim", "user_dim", "song_dim")]
    (pf, sf), (pu, su), (ps, ss) = (
        signed_permutation(seed, d, tag) for tag, d in enumerate(dims, 1)
    )
    blocks = np.random.default_rng([int(seed), 31]).permutation(n // m)
    out = None
    for k, block in enumerate(blocks):
        got = jax.device_get(_music_block(
            model_key, part_key, perm_key, jnp.int32(block),
            jnp.uint32(odd_multiplier(seed, 1)),
            jnp.uint32(odd_multiplier(seed, 2)),
            pf, sf, pu, su, ps, ss,
            m=m, n=n,
            d_fixed=dims[0], d_user=dims[1], d_song=dims[2],
            users=users,
            songs=int(param("num_songs")), song_ids=song_ids,
            user_exponent=float(config["rows_per_user_exponent"]),
            user_least=int(config["rows_per_user_least"]),
            user_most=int(config["rows_per_user_most"]),
            song_zipf=float(config["song_zipf_exponent"]),
            margin_scale=float(config["margin_scale"]),
        ))
        if out is None:
            out = jax.tree_util.tree_map(
                lambda a: np.empty((n,) + a.shape[1:], a.dtype), got)
        jax.tree_util.tree_map(
            lambda whole, a: whole.__setitem__(slice(k * m, (k + 1) * m), a),
            out, got)
    return out
