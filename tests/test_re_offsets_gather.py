"""A random-effect coordinate routes the (n,) residual offsets into its
padded buckets by ``game.data.gather_offsets_compact``: one gathered index a
held row, then every slot of every bucket filled by one contiguous run of
what was gathered.  The values are those of the plain definition
``RandomEffectDesign.gather_offsets`` (an index a padded slot), bit for
bit; the static maps are derived once, on the host, from each bucket's
``row_index`` and ``mask``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.core.tasks import TaskType
from photon_ml_tpu.game import (
    CoordinateConfig,
    GameData,
    RandomEffectCoordinate,
    build_bucketed_random_effect_design,
    build_random_effect_design,
)
from photon_ml_tpu.game import coordinates as coordinates_mod
from photon_ml_tpu.game.data import (
    RandomEffectDesign,
    gather_offsets_compact,
    offsets_gather_maps,
)
from photon_ml_tpu.models.training import OptimizerType

N_ENTITIES = 60
DIM = 3


def skewed_rows(seed=20261005, entities=N_ENTITIES, most=50, unknown=True):
    """Rows over entities of 0 to ``most`` rows each, shuffled; some rows of
    an entity the table does not know."""
    rng = np.random.default_rng(seed)
    counts = np.clip(
        (most * rng.uniform(size=entities) ** 3).astype(int), 0, most)
    counts[5] = most  # the deepest lane of the deepest bucket
    ids = rng.permutation(np.repeat(np.arange(entities), counts))
    if unknown:
        ids[::13] = -1
    n = ids.size
    return GameData.create(
        features={"s": rng.normal(size=(n, DIM))},
        labels=(rng.uniform(size=n) < 0.5).astype(float),
        entity_ids={"e": ids.astype(np.int32)},
    )


def by_hand(counts, depth):
    """One bucket of ``len(counts)`` lanes, lane e holding rows in its
    first ``counts[e]`` slots, the rows numbered in lane order."""
    counts = np.asarray(counts)
    held = np.arange(depth)[None, :] < counts[:, None]
    row_index = np.full(held.shape, -1, np.int32)
    row_index[held] = np.arange(int(held.sum()))
    mask = held.astype(np.float32)
    return RandomEffectDesign(
        features=jnp.zeros(held.shape + (1,), jnp.float32),
        labels=jnp.zeros(held.shape, jnp.float32),
        weights=jnp.asarray(mask), mask=jnp.asarray(mask),
        row_index=jnp.asarray(row_index),
    )


def buckets_of(case):
    """(buckets, number of rows the offsets cover, whether the lanes of
    every bucket are in an order monotone in their row count)."""
    if case == "skewed_bucketed_under_a_cap":
        data = skewed_rows()
        design = build_bucketed_random_effect_design(
            data, "e", "s", N_ENTITIES, num_buckets=4, active_cap=20)
        assert max(b.rows_per_entity for b in design.buckets) == 20
        return design.buckets, data.num_rows, True
    if case == "plain_one_bucket":
        data = skewed_rows()
        return [build_random_effect_design(
            data, "e", "s", N_ENTITIES, active_cap=20)], data.num_rows, False
    if case == "all_masked_empty_bucket":
        data = skewed_rows()
        data.entity_ids["e"][:] = -1
        design = build_bucketed_random_effect_design(
            data, "e", "s", N_ENTITIES, num_buckets=4, entity_multiple=2)
        assert not np.asarray(design.buckets[0].mask).any()
        return design.buckets, data.num_rows, True
    if case == "sentinel_lanes":
        data = skewed_rows()
        design = build_bucketed_random_effect_design(
            data, "e", "s", N_ENTITIES, num_buckets=4, entity_multiple=3)
        assert any(
            np.any(np.asarray(ei) == N_ENTITIES) for ei in design.entity_index)
        return design.buckets, data.num_rows, True
    if case == "lane_at_its_buckets_depth":
        data = skewed_rows()
        design = build_bucketed_random_effect_design(
            data, "e", "s", N_ENTITIES, num_buckets=3)
        for b in design.buckets:  # every bucket's last slot is held
            assert np.asarray(b.mask)[:, -1].any()
        return design.buckets, data.num_rows, True
    if case == "last_run_reads_past_the_gathered_rows":
        # the last slot's run is one lane in the middle: read as 4 lanes
        # it starts before and ends after what was gathered for the bucket
        return [by_hand([1, 2, 5, 0], 5)], 8, True
    if case == "first_run_starts_before_the_gathered_rows":
        # slot 0's run starts at lane 3: read from lane 0 it starts
        # before the first gathered row
        return [by_hand([0, 0, 0, 2, 3], 3), by_hand([1, 4], 4)], 10, True
    if case == "lanes_in_no_order":
        return [by_hand([3, 0, 1, 4, 0, 2], 4)], 10, False
    raise AssertionError(case)


CASES = [
    "skewed_bucketed_under_a_cap", "plain_one_bucket",
    "all_masked_empty_bucket", "sentinel_lanes", "lane_at_its_buckets_depth",
    "last_run_reads_past_the_gathered_rows",
    "first_run_starts_before_the_gathered_rows", "lanes_in_no_order",
]


@pytest.mark.parametrize("jit", [True, False], ids=["jit", "eager"])
@pytest.mark.parametrize("case", CASES)
def test_compact_gather_is_the_plain_definition_bit_for_bit(case, jit):
    buckets, n, ordered = buckets_of(case)
    rng = np.random.default_rng(3)
    full = jnp.asarray(rng.normal(size=n), buckets[0].mask.dtype)
    maps = offsets_gather_maps([(b.row_index, b.mask) for b in buckets])
    perm, starts = maps
    assert perm.dtype == np.int32 and all(s.dtype == np.int32 for s in starts)
    assert [s.shape for s in starts] == [
        (b.rows_per_entity,) for b in buckets]
    held = sum(int((np.asarray(b.mask) > 0).sum()) for b in buckets)
    slots = sum(int(np.asarray(b.mask).size) for b in buckets)
    # an index a held row where the lanes are in the builder's order, and
    # never more than the plain definition's index a padded slot
    assert perm.size == held if ordered else held <= perm.size <= slots
    fn = jax.jit(gather_offsets_compact) if jit else gather_offsets_compact
    got = fn(full, maps, [b.mask for b in buckets])
    assert len(got) == len(buckets)
    for g, b in zip(got, buckets):
        want = b.gather_offsets(full)
        assert g.shape == want.shape and g.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(want))


@pytest.mark.parametrize("fault", ["a_hole_in_a_lane", "a_held_slot_of_no_row"])
def test_maps_refuse_a_design_the_builder_would_not_make(fault):
    bucket = by_hand([2, 4, 1], 4)
    row_index, mask = np.array(bucket.row_index), np.array(bucket.mask)
    if fault == "a_hole_in_a_lane":
        mask[1, 1] = 0.0  # lane 1 holds slots 0, 2, 3
        match = "lane 1 are not a prefix"
    else:
        row_index[1, 3] = -1
        match = "has no row"
    with pytest.raises(ValueError, match=match):
        offsets_gather_maps([(row_index, mask)])
    design = dataclasses.replace(
        bucket, row_index=jnp.asarray(row_index), mask=jnp.asarray(mask))
    with pytest.raises(ValueError, match=match):
        RandomEffectCoordinate(
            design=design, row_features=jnp.zeros((7, 1), jnp.float32),
            row_entities=jnp.zeros((7,), jnp.int32),
            full_offsets_base=jnp.zeros((7,), jnp.float32),
            config=config(),
        )


def config():
    return CoordinateConfig(
        shard="s", reg_weight=1.0, random_effect="e",
        task=TaskType.LOGISTIC_REGRESSION, optimizer=OptimizerType.NEWTON,
        max_iters=2, tolerance=0.0,
    )


def coordinate(num_buckets=4, dtype=jnp.float32, seed=20261005):
    data = skewed_rows(seed)
    design = build_bucketed_random_effect_design(
        data, "e", "s", N_ENTITIES, num_buckets=num_buckets, active_cap=20,
        entity_multiple=4, dtype=dtype)
    return RandomEffectCoordinate(
        design=design,
        row_features=jnp.asarray(data.features["s"], dtype),
        row_entities=jnp.asarray(data.entity_ids["e"]),
        full_offsets_base=jnp.zeros((data.num_rows,), dtype),
        config=config(),
    )


def gathers(jaxpr, scopes=""):
    """(name stack, number of indices) of every gather of a jaxpr, the
    gathers of its inner jaxprs under the name stack of their call."""
    out = []
    for eqn in jaxpr.eqns:
        here = scopes + "/" + str(eqn.source_info.name_stack)
        if eqn.primitive.name == "gather":
            out.append((here, int(np.prod(eqn.invars[1].aval.shape[:-1]))))
        for param in eqn.params.values():
            for inner in param if isinstance(param, (list, tuple)) else [param]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    out.extend(gathers(inner, here))
    return out


def test_traced_update_gathers_an_index_a_held_row_and_a_run():
    name = "game.offsets_gather.compact"
    assert obs.taxonomy.matches(name)
    counter = obs.registry().counter(name)
    before = counter.value
    coord = coordinate()
    buckets = coord.design.buckets
    rng = np.random.default_rng(1)
    table = jnp.asarray(
        rng.normal(size=(N_ENTITIES, DIM)), jnp.float32)
    args = (table, jnp.zeros_like(coord.full_offsets_base))
    body = coordinates_mod._multi_bucket_update_body(coord.config)
    state = coord.fused_state()
    jaxpr = jax.make_jaxpr(body)(
        table, state[0], state[1], *state[2:])
    assert counter.value - before == 1  # once a trace
    under = [
        count for scopes, count in gathers(jaxpr.jaxpr)
        if "re_gather/offsets" in scopes
    ]
    held = sum(int((np.asarray(b.mask) > 0).sum()) for b in buckets)
    runs = sum(b.rows_per_entity for b in buckets)
    slots = [b.num_entities * b.rows_per_entity for b in buckets]
    assert held < sum(slots) // 2 + held // 2  # the design has padding
    assert sum(under) == held + runs
    # one gather of the held rows and one a bucket of its depth in runs:
    # none of a bucket's lanes x depth padded slots
    assert all(b.num_entities > 1 for b in buckets)
    assert sorted(under) == sorted(
        [held] + [b.rows_per_entity for b in buckets])
    # the gauges of the construction say the same
    reg = obs.registry()
    assert reg.gauge("game.offsets_gather.gather_indices").value == (
        held + runs)
    assert reg.gauge("game.offsets_gather.padded_slots").value == sum(slots)
    # and the jitted coordinate books the counter once a traced program
    coordinates_mod._make_multi_bucket_update_cached.cache_clear()
    before = counter.value
    coord.update_step(*args)
    coord.update_step(table + 1.0, args[1])
    assert counter.value - before == 1


def test_maps_are_one_object_in_every_fused_state():
    """``run_grid`` broadcasts the leaves that are the same object in two
    probes and stacks the rest a combo (``descent.py``)."""
    coord = coordinate()
    leaves = jax.tree_util.tree_leaves(coord._offsets_maps)
    assert len(leaves) == 1 + coord.design.num_buckets
    states = [coord.fused_state(), coord.fused_state_for_reg(0.5),
              coord.fused_state_for_reg(0.25)]
    for state in states:
        got = [
            leaf for leaf in jax.tree_util.tree_leaves(state)
            if any(leaf is m for m in leaves)
        ]
        assert len(got) == len(leaves)
    broadcast = jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: a is b, states[1], states[2]))
    assert broadcast.count(False) == 1  # the reg weights alone are stacked

    other = coordinate(num_buckets=2, seed=7)
    restored = other.with_fused_state(states[1])
    assert restored._offsets_maps is coord._offsets_maps
    assert other._offsets_maps is not coord._offsets_maps
    rng = np.random.default_rng(2)
    table = jnp.asarray(rng.normal(size=(N_ENTITIES, DIM)), jnp.float32)
    partial = jnp.asarray(
        rng.normal(size=coord.full_offsets_base.shape), jnp.float32)
    got = restored.update_step(table, partial)
    want = coord.with_fused_state(states[1]).update_step(table, partial)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
