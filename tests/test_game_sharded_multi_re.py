"""Entity-sharded GAME coordinate descent over MORE THAN ONE random effect:
a fixed effect, a per-user and a per-song random effect with both tables
entity-sharded, each in its own row partition, the rows moved between the
two by the on-device exchange — against the unsharded descent given the same
active samples, against the plain reference (``tests/reference_game.py``),
the exchange plan against numpy, the compiled programs' collectives, the one
update body, the driver and sharded checkpoints."""

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np
import pytest

import reference_game as ref
from photon_ml_tpu import obs
from photon_ml_tpu.core.tasks import TaskType
from photon_ml_tpu.game import (
    CoordinateConfig,
    CoordinateDescent,
    EntityShardedRandomEffectCoordinate,
    FixedEffectCoordinate,
    GameData,
    RandomEffectCoordinate,
    build_bucketed_random_effect_design,
    entity_partition_game_data,
    entity_partition_rows,
    entity_shard_assignment,
    entity_shard_layouts,
    row_exchange_plan,
)
from photon_ml_tpu.models.training import OptimizerType
from photon_ml_tpu.obs.xla_cost import count_collectives
from photon_ml_tpu.parallel.mesh import batch_sharding, make_entity_mesh

CAP = 32
N_USERS, N_SONGS = 17, 13  # remainders at 2 and at 4 shards
DIMS = {"global": 5, "per_user": 4, "per_song": 3}
L2 = {"fixed": 1.0, "per-user": 2.0, "per-song": 3.0}
TABLES = {  # coordinate -> (entity column, feature shard, table rows)
    "per-user": ("userId", "per_user", N_USERS),
    "per-song": ("songId", "per_song", N_SONGS),
}
ORDER = ("fixed", "per-user", "per-song")
CD_ITERATIONS = 2
SOLVERS = {
    "NEWTON": dict(optimizer=OptimizerType.NEWTON, max_iters=2,
                   tolerance=0.0),
    "LBFGS": dict(optimizer=OptimizerType.LBFGS, max_iters=25,
                  tolerance=1e-10),
}


@functools.lru_cache(maxsize=None)
def ratings():
    """Seeded ratings of 13 songs by 17 users: a user and a song past the
    active cap, a planted model on all three coordinates."""
    rng = np.random.default_rng(20261004)
    counts = rng.integers(6, 24, size=N_USERS)
    counts[3] = CAP + 9  # an entity over the cap
    user = np.repeat(np.arange(N_USERS), counts)
    n = user.size
    p = (np.arange(N_SONGS) + 1.0) ** -1.2
    song = rng.choice(N_SONGS, size=n, p=p / p.sum())
    order = rng.permutation(n)
    user, song = user[order], song[order]
    x = {k: rng.normal(size=(n, d)) for k, d in DIMS.items()}
    margin = (
        x["global"] @ rng.normal(size=DIMS["global"])
        + np.sum(x["per_user"] * rng.normal(
            size=(N_USERS, DIMS["per_user"]))[user], axis=1)
        + np.sum(x["per_song"] * rng.normal(
            size=(N_SONGS, DIMS["per_song"]))[song], axis=1)
    )
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(float)
    assert np.bincount(user).max() > CAP and np.bincount(song).max() > CAP
    ids = {"userId": user.astype(np.int32), "songId": song.astype(np.int32)}
    return x, ids, y


def game_data():
    x, ids, y = ratings()
    return GameData.create(features=x, labels=y, entity_ids=ids)


def config(name, solver):
    column, shard = (
        (None, "global") if name == "fixed" else TABLES[name][:2])
    return CoordinateConfig(
        shard=shard, reg_weight=L2[name], random_effect=column,
        task=TaskType.LOGISTIC_REGRESSION, **SOLVERS[solver],
    )


def layouts(data, n_shards):
    """{coordinate: (own-order GameData, assignment, partition)}: the
    per-user partition is the canonical one, the per-song partition
    carries the exchange plan to and from it."""
    by_entity = entity_shard_layouts(
        data,
        {column: entities for column, _, entities in TABLES.values()},
        n_shards,
        {column: {shard} for column, shard, _ in TABLES.values()},
    )
    return {name: by_entity[TABLES[name][0]] for name in TABLES}


def build_sharded(n_shards, solver="NEWTON", dtype=jnp.float64, fuse=True,
                  cap=CAP):
    """(CoordinateDescent, coordinates, layouts, designs) with both random
    effects entity-sharded over ``n_shards`` devices."""
    data = game_data()
    mesh = make_entity_mesh(n_shards, devices=jax.devices()[:n_shards])
    lay = layouts(data, n_shards)
    canonical = lay["per-user"][0]
    put = lambda v: jax.device_put(
        jnp.asarray(v, dtype), batch_sharding(mesh, np.ndim(v)))
    coords, designs = {}, {}
    for name in ORDER:
        if name == "fixed":
            batch = jtu.tree_map(
                lambda v: jax.device_put(v, batch_sharding(mesh, v.ndim)),
                canonical.fixed_effect_batch("global", dtype),
            )
            coords[name] = FixedEffectCoordinate(batch, config(name, solver))
            continue
        column, shard, entities = TABLES[name]
        own, assignment, part = lay[name]
        designs[name] = lay[name].bucketed_design(
            column, shard, num_buckets=2, active_cap=cap, dtype=dtype)
        coords[name] = EntityShardedRandomEffectCoordinate(
            design=designs[name],
            row_features=np.asarray(own.features[shard], dtype),
            row_entities=np.asarray(own.entity_ids[column]),
            full_offsets_base=np.asarray(canonical.offsets, dtype),
            config=config(name, solver),
            mesh=mesh,
            assignment=assignment,
            partition=part,
        )
    cd = CoordinateDescent(
        coords, labels=put(canonical.labels),
        base_offsets=put(canonical.offsets), weights=put(canonical.weights),
        task=TaskType.LOGISTIC_REGRESSION, fuse_passes=fuse,
    )
    return cd, coords, lay, designs


def in_original_rows(design, part):
    """The same design (the same active samples) over the ORIGINAL row
    order: every slot's row index taken back through the partition."""
    buckets = []
    for bucket in design.buckets:
        rows = np.asarray(bucket.row_index)
        back = np.where(rows >= 0, part.row_perm[np.maximum(rows, 0)], -1)
        buckets.append(dataclasses.replace(
            bucket, row_index=jnp.asarray(back, jnp.int32)))
    return dataclasses.replace(design, buckets=buckets)


def build_unsharded(lay, designs, solver="NEWTON", dtype=jnp.float64):
    """The three-coordinate descent on one device over the original rows,
    given the sharded designs' own active samples."""
    x, ids, _ = ratings()
    data = game_data()
    n = data.num_rows
    zeros = jnp.zeros((n,), dtype)
    coords = {}
    for name in ORDER:
        if name == "fixed":
            coords[name] = FixedEffectCoordinate(
                data.fixed_effect_batch("global", dtype),
                config(name, solver))
            continue
        column, shard, _ = TABLES[name]
        coords[name] = RandomEffectCoordinate(
            design=in_original_rows(designs[name], lay[name][2]),
            row_features=jnp.asarray(x[shard], dtype),
            row_entities=jnp.asarray(ids[column]),
            full_offsets_base=zeros,
            config=config(name, solver),
        )
    return CoordinateDescent(
        coords, labels=jnp.asarray(data.labels, dtype), base_offsets=zeros,
        weights=jnp.ones((n,), dtype), task=TaskType.LOGISTIC_REGRESSION,
    )


def global_params(model, coords):
    return {
        name: (
            coords[name].global_table(p)
            if isinstance(coords[name], EntityShardedRandomEffectCoordinate)
            else np.asarray(p)
        )
        for name, p in model.params.items()
    }


# -- (a) sharded == unsharded ------------------------------------------------


@pytest.mark.parametrize("solver", sorted(SOLVERS))
@pytest.mark.parametrize("n_shards", [2, 4])
def test_both_tables_sharded_match_the_unsharded_descent(n_shards, solver):
    cd, coords, lay, designs = build_sharded(n_shards, solver)
    model, history = cd.run(num_iterations=CD_ITERATIONS)
    local, local_history = build_unsharded(lay, designs, solver).run(
        num_iterations=CD_ITERATIONS)
    got = global_params(model, coords)
    for name in ORDER:
        np.testing.assert_allclose(
            got[name], np.asarray(local.params[name]), atol=1e-10,
            err_msg=name)
    np.testing.assert_allclose(
        [h.objective for h in history],
        [h.objective for h in local_history], rtol=1e-10)
    assert [h.coordinate for h in history] == list(ORDER) * CD_ITERATIONS
    # the entity over the cap trained on its sample, on both sides
    assert any(
        np.asarray(b.weights).max() > 1.0
        for b in designs["per-user"].buckets)


@pytest.mark.parametrize("fuse", ["coordinate", False])
def test_dispatch_modes_agree(fuse):
    cd, coords, _, _ = build_sharded(2, fuse=fuse)
    model, _ = cd.run(num_iterations=CD_ITERATIONS)
    cd_fused, coords_fused, _, _ = build_sharded(2)
    fused, _ = cd_fused.run(num_iterations=CD_ITERATIONS)
    got, want = global_params(model, coords), global_params(
        fused, coords_fused)
    for name in ORDER:
        np.testing.assert_allclose(got[name], want[name], atol=1e-12)


# -- (b) sharded == the plain reference --------------------------------------


def reference_problem(lay, designs):
    x, ids, y = ratings()
    problem = []
    for name in ORDER:
        if name == "fixed":
            problem.append({"name": name, "kind": "fixed",
                            "x": x["global"], "l2": L2[name]})
            continue
        column, shard, entities = TABLES[name]
        design = in_original_rows(designs[name], lay[name][2])
        counts = np.bincount(ids[column], minlength=entities)
        sample = {}
        for bucket, lanes in zip(design.buckets, design.entity_index):
            rows = np.asarray(bucket.row_index)
            weights = np.asarray(bucket.weights, np.float64)
            for lane, entity in enumerate(np.asarray(lanes)):
                if entity < entities and counts[entity] > CAP:
                    held = rows[lane] >= 0
                    sample[int(entity)] = (
                        rows[lane][held], weights[lane][held])
        problem.append({
            "name": name, "kind": "random", "x": x[shard],
            "ids": ids[column], "entities": entities, "l2": L2[name],
            "sample": sample,
        })
    return {"labels": y, "coordinates": problem}


@pytest.mark.parametrize("dtype_name, tol_values, tol_params", [
    # the tolerances of tests/test_game_multi_re.py, where they are derived
    ("float64", 1e-8, 1e-8),
    ("float32", 2e-5, 6e-4),
])
def test_both_tables_sharded_match_the_plain_reference(
        dtype_name, tol_values, tol_params):
    cd, coords, lay, designs = build_sharded(4, dtype=jnp.dtype(dtype_name))
    model, history = cd.run(num_iterations=CD_ITERATIONS)
    want, want_values = ref.block_coordinate_descent(
        reference_problem(lay, designs), CD_ITERATIONS,
        SOLVERS["NEWTON"]["max_iters"])
    got = global_params(model, coords)
    values = max(
        abs(h.objective - w) / abs(w) for h, w in zip(history, want_values))
    params = max(
        float(np.linalg.norm(got[k] - np.asarray(want[k], np.float64))
              / np.linalg.norm(np.asarray(want[k], np.float64)))
        for k in want
    )
    assert values <= tol_values and params <= tol_params, (values, params)


# -- (c) the exchange plan alone ---------------------------------------------


@pytest.mark.parametrize("n_shards", [2, 4])
def test_exchange_plan_against_numpy(n_shards):
    data = game_data()
    lay = layouts(data, n_shards)
    can, own = lay["per-user"][2], lay["per-song"][2]
    plan = own.exchange
    assert can.exchange is None and plan is not None
    n = data.num_rows
    assert plan.real_rows == n
    # every (source, destination) block's count against a numpy count
    shard_in_can = np.empty(n, np.int64)
    real = can.row_perm >= 0
    shard_in_can[can.row_perm[real]] = (
        np.flatnonzero(real) // can.rows_per_shard)
    shard_in_own = np.empty(n, np.int64)
    real = own.row_perm >= 0
    shard_in_own[own.row_perm[real]] = (
        np.flatnonzero(real) // own.rows_per_shard)
    want = np.zeros((n_shards, n_shards), np.int64)
    np.add.at(want, (shard_in_can, shard_in_own), 1)
    assert plan.block_rows == want.max()
    sent = (plan.send_to_owner >= 0).reshape(
        n_shards, n_shards, plan.block_rows).sum(axis=2)
    np.testing.assert_array_equal(sent, want)
    back = (plan.send_to_canonical >= 0).reshape(
        n_shards, n_shards, plan.block_rows).sum(axis=2)
    np.testing.assert_array_equal(back, want.T)
    assert (plan.recv_at_owner >= 0).sum() == n
    assert (plan.recv_at_canonical >= 0).sum() == n


def _exchange_on_the_mesh(mesh, direction):
    """One direction of the plan as the update runs it: the program's
    ``_exchange_rows`` a shard, under the test's own ``shard_map``."""
    from jax.sharding import PartitionSpec as P

    from photon_ml_tpu.game.coordinates import _exchange_rows
    from photon_ml_tpu.parallel.mesh import ENTITY_AXIS

    n_shards = mesh.shape[ENTITY_AXIS]
    return jax.jit(jax.shard_map(
        lambda x, send, recv: _exchange_rows(
            x, send, recv, n_shards, direction),
        mesh=mesh, in_specs=P(ENTITY_AXIS), out_specs=P(ENTITY_AXIS),
    ))


@pytest.mark.parametrize("n_shards", [2, 4])
def test_exchange_round_trip_on_the_device(n_shards):
    _, coords, lay, _ = build_sharded(n_shards)
    can, own = lay["per-user"][2], lay["per-song"][2]
    plan = own.exchange
    mesh = coords["per-song"].mesh
    n = game_data().num_rows
    column = np.arange(1.0, n + 1.0)  # no real row carries 0
    mesh_put = lambda v: jax.device_put(
        jnp.asarray(v), batch_sharding(mesh, 1))
    to_owner = lambda v: np.asarray(_exchange_on_the_mesh(mesh, "to_owner")(
        mesh_put(v), mesh_put(plan.send_to_owner),
        mesh_put(plan.recv_at_owner)))
    in_can = can.apply(column)
    at_owner = to_owner(in_can)
    # every row where the owner's partition puts it; pads give 0
    np.testing.assert_array_equal(at_owner, own.apply(column))
    assert np.all(at_owner[own.row_perm < 0] == 0.0)
    round_trip = np.asarray(_exchange_on_the_mesh(mesh, "to_canonical")(
        mesh_put(at_owner), mesh_put(plan.send_to_canonical),
        mesh_put(plan.recv_at_canonical)))
    np.testing.assert_array_equal(round_trip, in_can)
    assert np.all(round_trip[can.row_perm < 0] == 0.0)
    # a pad row's garbage never crosses: the way in drops it
    dirty = in_can.copy()
    dirty[can.row_perm < 0] = 7.0
    np.testing.assert_array_equal(to_owner(dirty), at_owner)
    # what the coordinate itself holds is the plan, a shard its segment;
    # the canonical coordinate holds none
    for held, planned in zip(
            coords["per-song"]._exchange,
            (plan.send_to_owner, plan.recv_at_owner,
             plan.send_to_canonical, plan.recv_at_canonical)):
        np.testing.assert_array_equal(np.asarray(held), planned)
    assert coords["per-user"]._exchange == ()


@pytest.mark.parametrize("n_shards", [2, 4])
def test_scores_come_back_in_the_canonical_order(n_shards):
    """The way back through the coordinate's own program: the per-song
    scores of a known table equal the rows' scores in the original order,
    put where the canonical partition puts them (pad rows 0)."""
    data = game_data()
    _, coords, lay, _ = build_sharded(n_shards)
    can = lay["per-user"][2]
    song = coords["per-song"]
    table = np.random.default_rng(5).normal(size=(N_SONGS, DIMS["per_song"]))
    stored = jax.device_put(
        jnp.asarray(song.assignment.table_from_global(table)),
        batch_sharding(song.mesh, 2))
    want = np.sum(
        np.asarray(data.features["per_song"])
        * table[np.asarray(data.entity_ids["songId"])], axis=1)
    np.testing.assert_allclose(
        np.asarray(song.score(stored)), can.apply(want),
        rtol=1e-13, atol=1e-13)


def test_a_sharded_design_is_the_plain_builder_s_on_the_host():
    lay = layouts(game_data(), 4)
    column, shard, entities = TABLES["per-song"]
    options = dict(num_buckets=2, active_cap=CAP, dtype=jnp.float64)
    got = lay["per-song"].bucketed_design(column, shard, **options)
    want = build_bucketed_random_effect_design(
        lay["per-song"].data, column, shard, entities, **options)
    host = jax.local_devices(backend="cpu")[0]
    for g, w in zip(
            jtu.tree_leaves(got.buckets), jtu.tree_leaves(want.buckets)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        assert g.devices() == {host}
    for g, w in zip(got.entity_index, want.entity_index):
        np.testing.assert_array_equal(g, w)


def test_a_partition_equal_to_the_canonical_one_yields_no_plan():
    data = game_data()
    assignment = entity_shard_assignment(N_USERS, 4)
    _, can = entity_partition_game_data(data, "userId", assignment)
    _, again = entity_partition_game_data(
        data, "userId", assignment, canonical=can)
    assert again.exchange is None
    assert row_exchange_plan(can, again) is None
    two = entity_partition_rows(
        data.entity_ids["userId"], entity_shard_assignment(N_USERS, 2))
    with pytest.raises(ValueError, match="shards"):
        row_exchange_plan(can, two)


def test_layout_spans_carry_the_counts():
    data = game_data()
    lay = layouts(data, 4)
    spans = [
        s[6] for s in obs.recent_spans()
        if s[0] == "partition.entity_layout"
    ][-2:]
    user, song = spans
    assert user["random_effect"] == "userId"
    assert "exchange_block_rows" not in user
    for attrs, name in ((user, "per-user"), (song, "per-song")):
        part = lay[name][2]
        assert attrs["rows"] == data.num_rows
        assert attrs["rows_per_shard"] == part.rows_per_shard
        assert attrs["padded_rows"] == part.padded_rows
    plan = lay["per-song"][2].exchange
    assert song["exchange_block_rows"] == plan.block_rows
    assert song["exchange_real_rows"] == data.num_rows


def test_coordinate_spans_carry_the_bytes_placed():
    """One partition.coordinate span a sharded coordinate, after the
    layouts: its entity, width, partition rows and every byte it put on
    the mesh."""
    _, coords, lay, _ = build_sharded(4)
    records = obs.recent_spans()
    spans = [s for s in records if s[0] == "partition.coordinate"]
    assert [s[6]["random_effect"] for s in spans] == ["userId", "songId"]
    layouts_end = max(
        s[2] for s in records if s[0] == "partition.entity_layout")
    for span, name in zip(spans, ("per-user", "per-song")):
        coord = coords[name]
        placed = (
            coord.reg_weights, coord._buckets, coord._entity_indices,
            coord.row_features, coord.row_entities_local,
            coord.full_offsets_base, coord._exchange,
        )
        assert layouts_end <= span[1]
        assert span[6]["shards"] == 4
        assert span[6]["rows"] == lay[name][2].padded_rows
        assert span[6]["bytes_placed"] == sum(
            leaf.nbytes for leaf in jtu.tree_leaves(placed))


# -- (d) the compiled programs' collectives ----------------------------------


def test_the_fused_pass_is_traced_once_a_descent():
    """The key and the fixed effect's zeros start replicated over the
    data's mesh, as every pass gives them back: the second pass finds the
    first one's program."""
    cd, _, _, _ = build_sharded(4)
    cd.run(num_iterations=CD_ITERATIONS)
    assert cd._fused_pass._cache_size() == 1


def _update_program(coord):
    table = coord.initial_params()
    state = coord.fused_state()
    offsets = state[1]
    return coord._update_all.lower(
        table, state[0], offsets, *state[2:]).compile().as_text()


def test_collectives_of_the_compiled_programs():
    cd, coords, _, _ = build_sharded(4)
    # the canonical coordinate's update crosses no shard
    assert count_collectives(_update_program(coords["per-user"])) == {}
    # the other's only collectives are the exchange's: one all_to_all in,
    # one out
    song = count_collectives(_update_program(coords["per-song"]))
    assert song == {"all-to-all": 2}, song
    # the fused pass: those two, plus the reductions over sharded rows
    # (the fixed effect's solve, the objective after every update)
    model_params = {n: c.initial_params() for n, c in coords.items()}
    scores = {n: c.score(model_params[n]) for n, c in coords.items()}
    cd._fused_pass_fn()
    states = {n: c.fused_state() for n, c in coords.items()}
    text = cd._fused_pass.lower(
        states, cd.labels, cd.base_offsets, cd.weights, model_params,
        scores, jax.random.PRNGKey(0),
    ).compile().as_text()
    fused = count_collectives(text)
    assert fused.pop("all-to-all") == 2, fused
    assert set(fused) <= {"all-reduce", "all-gather", "reduce-scatter"}
    assert fused.get("all-reduce", 0) >= 1, fused


def test_exchange_counters():
    reg = obs.registry()
    before = reg.snapshot()["counters"].get("game.exchange.programs", 0)
    cd, coords, lay, _ = build_sharded(2)
    cd.run(num_iterations=1)
    snap = reg.snapshot()
    # traced once, in the fused pass; the canonical coordinate counts none
    assert snap["counters"]["game.exchange.programs"] - before >= 1
    plan = lay["per-song"][2].exchange
    assert snap["gauges"]["game.exchange.bytes_per_pass"] == (
        2 * plan.exchanged_rows * 8)


# -- (d2) the offsets-gather maps a shard -------------------------------------


@pytest.mark.parametrize("n_shards", [2, 4])
def test_offsets_gather_maps_a_shard(n_shards):
    """A shard gathers the residual offsets of ITS held rows through its
    block of the maps; the shards hold unequal numbers of rows, so the
    shorter ones' blocks are padded to the longest's. (That the descent
    over these coordinates equals the unsharded one at both widths is
    ``test_both_tables_sharded_match_the_unsharded_descent``.)"""
    from photon_ml_tpu.game.data import gather_offsets_compact

    _, coords, _, _ = build_sharded(n_shards)
    for name in TABLES:
        coord = coords[name]
        perm, starts = coord._entity_indices[2]
        blocks = lambda a: np.split(np.asarray(a), n_shards)
        held = [
            sum(int((blocks(b.mask)[p] > 0).sum()) for b in coord._buckets)
            for p in range(n_shards)
        ]
        assert len(set(held)) > 1, held
        assert perm.shape == (n_shards * max(held),)
        assert [s.shape for s in starts] == [
            (n_shards * b.rows_per_entity,) for b in coord._buckets]
        rows = coord.partition.rows_per_shard
        full = np.random.default_rng(5).normal(size=n_shards * rows)
        for p in range(n_shards):
            local = jnp.asarray(blocks(full)[p])
            got = gather_offsets_compact(
                local,
                (blocks(perm)[p], tuple(blocks(s)[p] for s in starts)),
                [jnp.asarray(blocks(b.mask)[p]) for b in coord._buckets],
            )
            for g, b in zip(got, coord._buckets):
                shard = dataclasses.replace(
                    b, **{f.name: jnp.asarray(blocks(getattr(b, f.name))[p])
                          for f in dataclasses.fields(b)})
                np.testing.assert_array_equal(
                    np.asarray(g), np.asarray(shard.gather_offsets(local)))
        # the maps ride the fused state beside the lanes, as one object
        state = coord.fused_state()
        assert state[2] is coord._entity_indices
        restored = coord.with_fused_state(state)
        assert restored._entity_indices[2][0] is perm
        table = coord.initial_params() + 0.25
        partial = jax.device_put(
            jnp.asarray(full[:coord.full_offsets_base.shape[0]]),
            coord.full_offsets_base.sharding)
        for g, w in zip(jtu.tree_leaves(restored.update_step(table, partial)),
                        jtu.tree_leaves(coord.update_step(table, partial))):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# -- (e) one update body -----------------------------------------------------


@pytest.mark.parametrize("name", ["per-user", "per-song"])
def test_one_shard_is_bit_equal_to_the_unsharded_coordinate(name):
    reg = obs.registry()
    _, coords, lay, designs = build_sharded(1)
    sharded = coords[name]
    column, shard, _ = TABLES[name]
    x, ids, _ = ratings()
    n = game_data().num_rows
    plain = RandomEffectCoordinate(
        design=in_original_rows(designs[name], lay[name][2]),
        row_features=jnp.asarray(x[shard]),
        row_entities=jnp.asarray(ids[column]),
        full_offsets_base=jnp.zeros((n,)),
        config=config(name, "NEWTON"),
    )
    partial = np.random.default_rng(7).normal(size=n)
    can = lay["per-user"][2]
    written = reg.snapshot()["counters"].get(
        "game.table_write.inverse_gather", 0)
    table_s, _, scores_s = sharded.update_step(
        sharded.initial_params(), jnp.asarray(can.apply(partial)))
    # the sharded path writes its table by PR 33's inverse gather
    assert reg.snapshot()["counters"][
        "game.table_write.inverse_gather"] == written + 1
    table_p, _, scores_p = plain.update_step(
        plain.initial_params(), jnp.asarray(partial))
    np.testing.assert_array_equal(
        sharded.global_table(table_s), np.asarray(table_p))
    np.testing.assert_array_equal(
        can.restore(np.asarray(scores_s)), np.asarray(scores_p))


# -- (f) the driver ----------------------------------------------------------


def _driver_params(tmp_path, out, **over):
    """A toy fixture for ``cli.game_train``: ratings with a userId and a
    songId in the metadata map, written by the repo's own Avro codec."""
    from photon_ml_tpu.io.avro import write_avro_file
    from photon_ml_tpu.io.schemas import TRAINING_EXAMPLE_SCHEMA
    from photon_ml_tpu.io.vocab import FeatureVocabulary, feature_key

    x, ids, y = ratings()
    prefixes = {"global": "gf", "per_user": "uf", "per_song": "sf"}
    train = str(tmp_path / "ratings.avro")
    if not os.path.exists(train):
        records = [
            {
                "uid": f"row{i}",
                "label": float(y[i]),
                "features": [
                    {"name": f"{prefixes[k]}{j}", "term": "",
                     "value": float(x[k][i, j])}
                    for k in prefixes for j in range(DIMS[k])
                ],
                "metadataMap": {"userId": f"user{ids['userId'][i]:02d}",
                                "songId": f"song{ids['songId'][i]:02d}"},
                "weight": None,
                "offset": None,
            }
            for i in range(y.size)
        ]
        write_avro_file(train, TRAINING_EXAMPLE_SCHEMA, records)
        for k, prefix in prefixes.items():
            FeatureVocabulary(
                [feature_key(f"{prefix}{j}", "") for j in range(DIMS[k])],
                add_intercept=True,
            ).save(str(tmp_path / f"{k}.features"))
    solver = {"optimizer": "TRON", "max_iters": 20, "tolerance": 1e-9}
    params = {
        "train_input": [train],
        "validate_input": [],
        "output_dir": str(tmp_path / out),
        "task": "LOGISTIC_REGRESSION",
        "num_iterations": 2,
        "updating_sequence": list(ORDER),
        "feature_shards": {
            k: str(tmp_path / f"{k}.features") for k in prefixes},
        "coordinates": {
            "fixed": {"shard": "global", "reg_weights": [L2["fixed"]],
                      **solver},
            "per-user": {"shard": "per_user", "random_effect": "userId",
                         "reg_weights": [L2["per-user"]], "num_buckets": 2,
                         **solver},
            "per-song": {"shard": "per_song", "random_effect": "songId",
                         "reg_weights": [L2["per-song"]], "num_buckets": 2,
                         **solver},
        },
    }
    params.update(over)
    return params


def test_config_admits_any_number_of_plain_random_effects(tmp_path):
    from photon_ml_tpu.cli.config import GameDriverParams, load_params

    params = _driver_params(tmp_path, "unused", entity_shards=4)
    loaded = load_params(params, GameDriverParams)
    loaded.validate()
    assert loaded.entity_shards == 4
    for bad in ({"latent_dim": 2}, {"projector": "RANDOM=2"}):
        refused = _driver_params(tmp_path, "unused", entity_shards=4)
        refused["coordinates"]["per-song"].update(bad)
        with pytest.raises(ValueError, match="cannot be entity-sharded"):
            load_params(refused, GameDriverParams).validate()
    none = _driver_params(tmp_path, "unused", entity_shards=4)
    none["updating_sequence"] = ["fixed"]
    none["coordinates"] = {"fixed": none["coordinates"]["fixed"]}
    with pytest.raises(ValueError, match="at least one"):
        load_params(none, GameDriverParams).validate()


def test_driver_trains_two_sharded_random_effects(tmp_path):
    from photon_ml_tpu.cli.game_train import run_game_training

    plain = run_game_training(_driver_params(tmp_path, "plain"))
    sharded = run_game_training(
        _driver_params(tmp_path, "sharded", entity_shards=4))
    want, got = plain.sweep[0]["model"], sharded.sweep[0]["model"]
    # exported tables are back in GLOBAL entity order, both of them
    for name in ORDER:
        assert np.shape(got.params[name]) == np.shape(want.params[name])
        np.testing.assert_allclose(
            np.asarray(got.params[name]), np.asarray(want.params[name]),
            atol=1e-6, err_msg=name)
    np.testing.assert_allclose(
        sharded.sweep[0]["history"][-1].objective,
        plain.sweep[0]["history"][-1].objective, rtol=1e-8)
    spans = [
        s[6]["random_effect"] for s in obs.recent_spans()
        if s[0] == "partition.entity_layout"
    ]
    assert spans[-2:] == ["userId", "songId"]


# -- (g) sharded checkpoints -------------------------------------------------


def test_resume_two_sharded_tables_at_another_width(tmp_path):
    """Two passes at width 2 with sharded checkpoints, resumed at width 4:
    the continued run equals the uninterrupted one (the entity-keyed
    restore re-keys BOTH stored tables)."""
    ckpt = str(tmp_path / "ckpt")
    keys = {
        name: [f"{column}:{i}" for i in range(entities)]
        for name, (column, _, entities) in TABLES.items()
    }

    def run(n_shards, iterations, **checkpoints):
        # no cap: which rows of an entity over it are sampled moves with
        # the row order, and so with the width
        cd, coords, lay, _ = build_sharded(n_shards, cap=None)
        if checkpoints:
            checkpoints.update(
                checkpoint_dir=ckpt, checkpoint_every=1,
                sharded_checkpoints=n_shards,
                entity_keys={
                    name: lay[name][1].stored_entity_keys(keys[name])
                    for name in TABLES
                },
            )
        model, _ = cd.run(num_iterations=iterations, **checkpoints)
        return global_params(model, coords)

    run(2, 2, resume=False)
    resumed = run(4, 4, resume=True)
    whole = run(2, 4)
    for name in ORDER:
        np.testing.assert_allclose(
            resumed[name], whole[name], atol=1e-10, err_msg=name)
