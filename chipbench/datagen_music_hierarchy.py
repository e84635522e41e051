"""Inputs of the ``game_music_sparse_user`` cell: ``datagen_music``'s ratings
(the same laws, keys and isomorphic seeds: rows a user, song of a row, the
fixed and per-song features and hidden effects) where the per-user
coordinate reads the rated item's place in the source's item HIERARCHY as a
sparse bag, not dense features.

KDD Cup 2011 Yahoo! Music Track 1 keeps 624,961 items in one id space:
507,172 tracks, 88,909 albums, 27,888 artists and 992 genres
(``trackData.txt``: a track's album, artist and genres; ``albumData.txt``:
an album's artist and genres).  Here the item at popularity rank r has a
kind and parents drawn once from ``data_seed`` (``hierarchy``): the kinds
shuffled over the ranks in the source's counts; an album's artist Zipf over
the artists and its genres, a track's album uniform over the albums (its
artist the album's) and its own genres, 1 + Poisson(1) genres (at most 5)
Zipf over the genres.  A rating's bag is the rated item and its ancestors:
a track's holds itself, its album, its artist and its genres (at most 8),
an album's itself, its artist and its genres, an artist's or a genre's
itself; every entry 1.0, its column the item's id in the song id space
(the seed's bijection of ranks, as the song ids).

The hidden per-user effect is a sparse affinity over artists and genres: a
hashed value in (-1, 1) on a ``affinity_density`` share of the (user,
artist) and (user, genre) pairs, summed over the artists and genres of a
rating's bag; the fixed and song terms are ``datagen_music``'s.  The bag
comes back slot-major, (8, n) columns and values, the rows' long axis minor
on the chip (an (n, 8) array there lies padded to 128 lanes).
"""

from __future__ import annotations

from functools import lru_cache, partial

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

SLOTS = 8  # a track: itself, album, artist and up to 5 genres
KINDS = ("tracks", "albums", "artists", "genres")
ARTIST, GENRE = 2, 3


def kind_counts(config: dict, songs: int):
    """The source's counts of each kind scaled to ``songs`` items (the
    source's own at full size), each at least one."""
    source = np.asarray([config["hierarchy"][k] for k in KINDS], np.float64)
    counts = np.maximum(np.floor(source * songs / source.sum()), 1).astype(
        np.int64)
    counts[0] += songs - counts.sum()
    return counts


def _zipf_index(rng, n: int, size, exponent: float):
    """Indices in [0, n) under p(i) ~ (i+1)^-exponent."""
    p = np.arange(1, n + 1, dtype=np.float64) ** -exponent
    return rng.choice(n, size=size, p=p / p.sum())


def _genres(rng, items: int, genres: int, exponent: float, most: int):
    """(items, most) genre indices, 1 + Poisson(1) of them an item (at most
    ``most``), each Zipf over the genres, repeats dropped; -1 pads."""
    count = np.minimum(1 + rng.poisson(1.0, items), most)
    draw = _zipf_index(rng, genres, (items, most), exponent)
    draw = np.where(np.arange(most) < count[:, None], draw, -1)
    draw = np.sort(draw, axis=1)[:, ::-1]
    repeat = np.zeros_like(draw, bool)
    repeat[:, 1:] = (draw[:, 1:] == draw[:, :-1]) & (draw[:, 1:] >= 0)
    return np.where(repeat, -1, draw)


@lru_cache(maxsize=4)
def _hierarchy(data_seed: int, counts: tuple, artist_zipf: float,
               genre_zipf: float):
    rng = np.random.default_rng([data_seed, 0x41E])
    n_tracks, n_albums, n_artists, n_genres = counts
    kind = rng.permutation(np.repeat(np.arange(4, dtype=np.int8), counts))
    rank_of = [np.flatnonzero(kind == k) for k in range(4)]
    album_artist = _zipf_index(rng, n_artists, n_albums, artist_zipf)
    album_genres = _genres(rng, n_albums, n_genres, genre_zipf, 5)
    track_album = rng.integers(0, n_albums, n_tracks)
    track_genres = _genres(rng, n_tracks, n_genres, genre_zipf, 5)

    def ranks(kind_index, idx):
        return np.where(idx >= 0, rank_of[kind_index][np.maximum(idx, 0)],
                        -1)

    bag = np.full((kind.size, SLOTS), -1, np.int64)
    bag[:, 0] = np.arange(kind.size)
    t = rank_of[0]
    bag[t, 1] = ranks(1, track_album)
    bag[t, 2] = ranks(2, album_artist[track_album])
    bag[t, 3:8] = ranks(3, track_genres)
    a = rank_of[1]
    bag[a, 1] = ranks(2, album_artist)
    bag[a, 2:7] = ranks(3, album_genres)
    return bag.astype(np.int32), kind


def hierarchy(config: dict, songs: int):
    """((songs, 8) int32 the ranks of every item's bag, -1 pads; (songs,)
    int8 its kind: 0 track, 1 album, 2 artist, 3 genre), drawn from
    ``data_seed`` alone."""
    return _hierarchy(
        int(config["data_seed"]), tuple(int(c) for c in kind_counts(
            config, songs)),
        float(config["artist_zipf_exponent"]),
        float(config["genre_zipf_exponent"]))


def _pair_uniform(a, b, salt: int):
    """A hash of (a, b) to [0, 1)."""
    x = (a.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
         + b.astype(jnp.uint32) * jnp.uint32(0x85EBCA77))
    x = x ^ (x >> 15)
    x = x * jnp.uint32(salt)
    x = x ^ (x >> 13)
    return (x >> 8).astype(jnp.float32) / float(1 << 24)


@partial(
    jax.jit,
    static_argnames=("n", "d_fixed", "d_song", "users", "songs", "song_ids",
                     "user_exponent", "user_least", "user_most", "song_zipf",
                     "density", "affinity_scale", "margin_scale"),
)
def _music_rows(model_key, data_key, perm_key, mult_u, mult_s, pf, sf, ps, ss,
                bag_ranks, kind, *, n, d_fixed, d_song, users, songs,
                song_ids, user_exponent, user_least, user_most, song_zipf,
                density, affinity_scale, margin_scale):
    k_c, k_w = jax.random.split(model_key)
    k_s, k_g, _, k_v, k_y = jax.random.split(data_key, 5)
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
    xs = jax.random.normal(k_v, (n, d_song), jnp.float32)
    w_f = jax.random.normal(k_w, (d_fixed,), jnp.float32)
    # the rated item's bag, slot-major
    bag = jnp.take(bag_ranks, song_rank, axis=1)  # (8, n)
    held = bag >= 0
    safe = jnp.maximum(bag, 0)
    liked = held & ((kind[safe] == ARTIST) | (kind[safe] == GENRE))
    liked = liked & (_pair_uniform(user_rank[None, :], safe, 2654435761)
                     < density)
    affinity = 2.0 * _pair_uniform(user_rank[None, :], safe, 2246822519) - 1.0
    user_term = affinity_scale * jnp.sum(
        jnp.where(liked, affinity, 0.0), axis=0)
    margin = margin_scale * (
        xg @ w_f / np.sqrt(d_fixed)
        + user_term
        + jnp.sum(xs * _hashed_effect(song_rank, d_song, 3266489917), axis=1)
        / np.sqrt(d_song)
    )
    labels = (
        jax.random.uniform(k_y, (n,)) < jax.nn.sigmoid(margin)
    ).astype(jnp.float32)
    order = jax.random.permutation(perm_key, n)
    user = _relabel(user_rank, mult_u, users)
    song = _relabel(song_rank, mult_s, song_ids)
    columns = jnp.where(held, _relabel(safe, mult_s, song_ids), song_ids)
    return {
        "features": {
            "global": (xg[:, pf] * sf)[order],
            "per_song": (xs[:, ps] * ss)[order],
        },
        "bag": {
            "columns": columns[:, order],
            "values": held.astype(jnp.float32)[:, order],
        },
        "entities": {"userId": user[order], "songId": song[order]},
        "labels": labels[order],
    }


def music_rows(config: dict, param, seed: int, n: int, part: str):
    """``{"features": {"global": (n, 64), "per_song": (n, 16)}, "bag":
    {"columns": (8, n) int32 ids in the song id space (its width, the pad),
    "values": (8, n) f32}, "entities": {"userId", "songId"}, "labels"}`` of
    the ``train`` or ``heldout`` part; ``param`` as in
    ``datagen_music.music_rows``."""
    users, song_ids = int(param("num_users")), int(param("song_id_space"))
    songs = int(param("num_songs"))
    model_key = key_of(config["data_seed"])
    data_key = jax.random.fold_in(model_key, {"train": 1, "heldout": 2}[part])
    perm_key = jax.random.fold_in(key_of(seed), 31)
    d_fixed, d_song = int(config["fixed_dim"]), int(config["song_dim"])
    # the tags of datagen_music's global and song shards
    pf, sf = signed_permutation(seed, d_fixed, 1)
    ps, ss = signed_permutation(seed, d_song, 3)
    bag, kind = hierarchy(config, songs)
    return _music_rows(
        model_key, data_key, perm_key,
        jnp.uint32(odd_multiplier(seed, 1)),
        jnp.uint32(odd_multiplier(seed, 2)),
        pf, sf, ps, ss, jnp.asarray(bag.T), jnp.asarray(kind),
        n=int(n), d_fixed=d_fixed, d_song=d_song, users=users,
        songs=songs, song_ids=song_ids,
        user_exponent=float(config["rows_per_user_exponent"]),
        user_least=int(config["rows_per_user_least"]),
        user_most=int(config["rows_per_user_most"]),
        song_zipf=float(config["song_zipf_exponent"]),
        density=float(config["affinity_density"]),
        affinity_scale=float(config["affinity_scale"]),
        margin_scale=float(config["margin_scale"]),
    )
