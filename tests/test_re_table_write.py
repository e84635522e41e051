"""``RandomEffectCoordinate.update_all`` writes the solved lanes into the
coefficient table with one gather through a static entity -> lane map.
The values are those of the scatter-a-bucket chain it replaced (written
out below as the reference), bit for bit: sentinel lanes never land,
an entity in no lane keeps the value it came in with, a row of unknown
entity scores 0."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.core.tasks import TaskType
from photon_ml_tpu.core.types import LabeledBatch
from photon_ml_tpu.game import (
    CoordinateConfig,
    CoordinateDescent,
    FixedEffectCoordinate,
    GameData,
    RandomEffectCoordinate,
    build_bucketed_random_effect_design,
)
from photon_ml_tpu.game import coordinates as coordinates_mod
from photon_ml_tpu.game.data import BucketedRandomEffectDesign
from photon_ml_tpu.models.training import OptimizerType
from photon_ml_tpu.solvers.common import final_grad_norm

N_USERS, N_SONGS = 48, 30
NO_ROWS = {"userId": (3, 20, 47), "songId": (0, 11)}  # entities in no lane
DIMS = {"global": 5, "per_user": 4, "per_song": 3}
TABLES = {  # coordinate -> (entity column, feature shard, table rows)
    "per-user": ("userId", "per_user", N_USERS),
    "per-song": ("songId", "per_song", N_SONGS),
}
LANE_MULTIPLE = 8  # pads every bucket's lanes: the sentinel lanes
SOLVER = dict(
    task=TaskType.LOGISTIC_REGRESSION, optimizer=OptimizerType.NEWTON,
    max_iters=2, tolerance=0.0,
)


@functools.lru_cache(maxsize=None)
def ratings():
    """Seeded rows over users of 1 to 40 rows each and songs under a Zipf
    law; some entities of either table have no row, and one row in eleven
    has a user the table does not know (-1)."""
    rng = np.random.default_rng(20261003)
    present = np.setdiff1d(np.arange(N_USERS), NO_ROWS["userId"])
    counts = np.clip((40 * rng.uniform(size=present.size) ** 3).astype(int),
                     1, 40)
    user = np.repeat(present, counts)
    n = user.size
    songs = np.setdiff1d(np.arange(N_SONGS), NO_ROWS["songId"])
    p = (np.arange(songs.size) + 1.0) ** -1.0
    song = rng.choice(songs, size=n, p=p / p.sum())
    order = rng.permutation(n)
    user, song = user[order], song[order]
    user[::11] = -1
    x = {k: rng.normal(size=(n, d)) for k, d in DIMS.items()}
    y = (rng.uniform(size=n) < 0.5).astype(float)
    return x, {"userId": user, "songId": song}, y


def random_effect(name, num_buckets, dtype, reg_weight=1.0):
    x, ids, y = ratings()
    column, shard, entities = TABLES[name]
    data = GameData.create(features=x, labels=y, entity_ids=ids)
    design = build_bucketed_random_effect_design(
        data, column, shard, entities, num_buckets=num_buckets,
        entity_multiple=LANE_MULTIPLE, dtype=dtype,
    )
    return RandomEffectCoordinate(
        design=design,
        row_features=jnp.asarray(x[shard], dtype),
        row_entities=jnp.asarray(ids[column], jnp.int32),
        full_offsets_base=jnp.zeros((y.size,), dtype),
        config=CoordinateConfig(shard=shard, reg_weight=reg_weight,
                                random_effect=column, **SOLVER),
    )


def warm_table_and_scores(coord, dtype):
    """A table with no zero in it and the other coordinates' scores."""
    rng = np.random.default_rng(7)
    table = 0.1 + rng.uniform(size=(coord.num_entities, coord.dim))
    scores = rng.normal(size=coord.row_entities.shape)
    return jnp.asarray(table, dtype), jnp.asarray(scores, dtype)


def scatter_chain(config):
    """``update_all`` as it was before the inverse map: every bucket's
    solutions scattered into the table in turn, sentinels dropped."""
    solve = coordinates_mod._make_solve(config, batched=True)

    @jax.jit
    def update_all(table, reg_weights, full_offsets, entity_indices,
                   buckets, row_features, row_entities):
        trackers = []
        for eidx, bucket in zip(entity_indices, buckets):
            offsets = bucket.gather_offsets(full_offsets)
            w0 = jnp.take(table, eidx, axis=0, mode="clip")
            lam = jnp.take(reg_weights, eidx, mode="clip")
            result = solve(w0, lam, bucket.features, bucket.labels, offsets,
                           bucket.weights, bucket.mask)
            table = table.at[eidx].set(result.w, mode="drop")
            trackers.append(
                (result.reason, result.iterations, final_grad_norm(result)))
        scores = coordinates_mod._score_rows_by_entity(
            table, row_features, row_entities)
        return table, tuple(trackers), scores

    return update_all


def chain_arguments(coord, table, partial_scores):
    return (
        table, coord.reg_weights, coord.full_offsets_base + partial_scores,
        coord._entity_indices, tuple(coord.design.buckets),
        coord.row_features, coord.row_entities,
    )


@pytest.mark.parametrize("dtype_name", ["float32", "float64"])
@pytest.mark.parametrize("num_buckets", [1, 4])
def test_update_is_bit_equal_to_the_scatter_chain(num_buckets, dtype_name):
    dtype = jnp.dtype(dtype_name)
    coord = random_effect("per-user", num_buckets, dtype)
    table, partial = warm_table_and_scores(coord, dtype)
    lanes = [np.asarray(ei) for ei in coord.design.entity_index]
    assert len(lanes) == num_buckets
    assert sum(int(np.sum(ei == N_USERS)) for ei in lanes) > 0  # (a)
    ids = np.asarray(coord.row_entities)
    assert np.sum(ids < 0) > 0  # (c)

    got_table, got_trackers, got_scores = coord.update_step(table, partial)
    want_table, want_trackers, want_scores = scatter_chain(coord.config)(
        *chain_arguments(coord, table, partial))

    assert got_table.dtype == dtype and got_scores.dtype == dtype
    np.testing.assert_array_equal(
        np.asarray(got_table), np.asarray(want_table))
    np.testing.assert_array_equal(
        np.asarray(got_scores), np.asarray(want_scores))
    # a sentinel lane solves an all-masked problem whose warm start is the
    # clipped last row, which the chain may have rewritten by then: its
    # tracker is no entity's and ``wrap_tracker`` cuts it
    for got, want, ei in zip(got_trackers, want_trackers, lanes):
        for g, w in zip(got, want):
            np.testing.assert_array_equal(
                np.asarray(g)[ei < N_USERS], np.asarray(w)[ei < N_USERS])
    # (b) an entity in no lane keeps its warm start, every other row moved
    stayed = np.all(np.asarray(got_table) == np.asarray(table), axis=1)
    assert sorted(np.flatnonzero(stayed)) == list(NO_ROWS["userId"])
    # (c) a row of unknown entity scores 0, and only such a row
    assert np.all(np.asarray(got_scores)[ids < 0] == 0.0)
    assert np.all(np.asarray(got_scores)[ids >= 0] != 0.0)


@pytest.mark.parametrize("num_buckets", [1, 4])
def test_lane_map_inverts_the_designs_entity_index(num_buckets):
    coord = random_effect("per-song", num_buckets, jnp.float32)
    lane_of = np.asarray(coord._lane_of_entity)
    assert lane_of.shape == (N_SONGS,) and lane_of.dtype == np.int32
    lanes = np.concatenate(
        [np.asarray(ei) for ei in coord.design.entity_index])
    held = lane_of >= 0
    assert sorted(np.flatnonzero(~held)) == list(NO_ROWS["songId"])
    np.testing.assert_array_equal(lanes[lane_of[held]], np.flatnonzero(held))
    # every real lane is some entity's, no sentinel lane is
    assert np.sum(held) == np.sum(lanes < N_SONGS)


@pytest.mark.parametrize("where", ["one_bucket", "two_buckets"])
def test_entity_in_two_lanes_raises_at_construction(where):
    coord = random_effect("per-user", 4, jnp.float32)
    index = [np.array(ei) for ei in coord.design.entity_index]
    if where == "one_bucket":
        index[0][1] = index[0][0]
    else:
        index[2][0] = index[0][0]
    design = BucketedRandomEffectDesign(
        buckets=list(coord.design.buckets), entity_index=index,
        num_entities=N_USERS,
    )
    with pytest.raises(ValueError, match="more than one lane"):
        RandomEffectCoordinate(
            design=design, row_features=coord.row_features,
            row_entities=coord.row_entities,
            full_offsets_base=coord.full_offsets_base, config=coord.config,
        )


def scatter_results(lowered_text):
    """The result type of every scatter of a lowered program."""
    return re.findall(
        r"stablehlo\.scatter.*?-> (tensor<[^>]+>)", lowered_text, re.DOTALL)


@pytest.mark.parametrize("num_buckets", [1, 4])
def test_lowered_update_holds_no_scatter_into_the_table(num_buckets):
    coord = random_effect("per-user", num_buckets, jnp.float32)
    table, partial = warm_table_and_scores(coord, jnp.float32)
    text = coord._update_all.lower(
        table, coord.reg_weights, coord.full_offsets_base + partial,
        coord._entity_indices, coord._lane_of_entity, coord._offsets_maps,
        tuple(coord.design.buckets), coord.row_features, coord.row_entities,
    ).as_text()
    table_type = f"tensor<{N_USERS}x{DIMS['per_user']}xf32>"
    assert table_type in text and "stablehlo.gather" in text
    # what still scatters is a solve's tracker slot, (lanes, 1[, d])
    assert table_type not in scatter_results(text)
    # the same search finds the chain's: one a bucket
    chain = scatter_chain(coord.config).lower(
        *chain_arguments(coord, table, partial)).as_text()
    assert scatter_results(chain).count(table_type) == num_buckets


def test_lane_map_is_one_object_in_every_fused_state():
    """``run_grid`` broadcasts the leaves that are the same object in two
    probes and stacks the rest a combo (``descent.py``)."""
    coord = random_effect("per-user", 4, jnp.float32)
    states = [coord.fused_state(), coord.fused_state_for_reg(0.5),
              coord.fused_state_for_reg(0.25)]
    position = [
        i for i, leaf in enumerate(states[0])
        if leaf is coord._lane_of_entity
    ]
    assert len(position) == 1
    assert all(s[position[0]] is coord._lane_of_entity for s in states)
    broadcast = jax.tree_util.tree_map(
        lambda a, b: a is b, states[1], states[2])
    assert broadcast[position[0]] is True and broadcast[0] is False

    other = random_effect("per-user", 1, jnp.float32)
    restored = other.with_fused_state(states[1])
    assert restored._lane_of_entity is coord._lane_of_entity
    assert restored._entity_indices is coord._entity_indices
    assert other._lane_of_entity is not coord._lane_of_entity
    table, partial = warm_table_and_scores(coord, jnp.float32)
    got = restored.update_step(table, partial)
    want = coord.with_fused_state(states[1]).update_step(table, partial)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def descent(names, fuse):
    x, _, y = ratings()
    dtype = jnp.float64
    labels = jnp.asarray(y, dtype)
    zeros, ones = jnp.zeros_like(labels), jnp.ones_like(labels)
    coords = {}
    for name in names:
        if name == "fixed":
            coords[name] = FixedEffectCoordinate(
                LabeledBatch(features=jnp.asarray(x["global"], dtype),
                             labels=labels, offsets=zeros, weights=ones,
                             mask=ones),
                CoordinateConfig(shard="global", reg_weight=1.0, **SOLVER),
            )
        else:
            coords[name] = random_effect(name, 4, dtype, reg_weight=3.0)
    return CoordinateDescent(
        coordinates=coords, labels=labels, base_offsets=zeros, weights=ones,
        task=TaskType.LOGISTIC_REGRESSION, fuse_passes=fuse,
    )


@pytest.mark.parametrize("names", [
    ("fixed", "per-user"), ("per-song", "fixed", "per-user"),
], ids=["two_coordinates", "three_coordinates"])
def test_fused_pass_and_unfused_loop_agree(names):
    fused_model, fused_history = descent(names, True).run(num_iterations=3)
    model, history = descent(names, False).run(num_iterations=3)
    assert [h.coordinate for h in fused_history] == list(names) * 3
    for name in names:
        np.testing.assert_allclose(
            np.asarray(fused_model.params[name]),
            np.asarray(model.params[name]), atol=1e-12)
    for name in set(names) & set(TABLES):
        no_lane = list(NO_ROWS[TABLES[name][0]])
        # the zero it started from
        assert np.all(np.asarray(fused_model.params[name])[no_lane] == 0.0)
    for fused, plain in zip(fused_history, history):
        assert fused.coordinate == plain.coordinate
        np.testing.assert_allclose(
            fused.objective, plain.objective, rtol=1e-12)
        assert fused.convergence_histogram == plain.convergence_histogram


def test_counter_is_booked_once_a_traced_program():
    name = "game.table_write.inverse_gather"
    assert obs.taxonomy.matches(name)
    coordinates_mod._make_multi_bucket_update_cached.cache_clear()
    counter = obs.registry().counter(name)
    before = counter.value
    coord = random_effect("per-user", 4, jnp.float32)
    table, partial = warm_table_and_scores(coord, jnp.float32)
    coord.update_step(table, partial)
    coord.update_step(table + 1.0, partial)
    assert counter.value - before == 1
    # another coordinate's shapes are another program
    other = random_effect("per-song", 4, jnp.float32)
    other.update_step(*warm_table_and_scores(other, jnp.float32))
    assert counter.value - before == 2
