"""GAME coordinate descent over a fixed effect and TWO random effects of
opposite skew, both under an active-row cap, against the plain reference
(``tests/reference_game.py``: a loop over entities, no buckets) given the
same active samples; the capped design against the rule; the ``game.design``
span and counters against numpy; the device scopes of the compiled pass."""

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_game as ref
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
from photon_ml_tpu.models.training import OptimizerType

CAP = 32
N_USERS, N_SONGS = 16, 96
DIMS = {"global": 6, "per_user": 4, "per_song": 3}
L2 = {"fixed": 1.0, "per-user": 10.0, "per-song": 10.0}
TABLES = {  # coordinate -> (entity column, feature shard, table rows)
    "per-user": ("userId", "per_user", N_USERS),
    "per-song": ("songId", "per_song", N_SONGS),
}
CD_ITERATIONS, NEWTON_ITERATIONS = 3, 2
ORDERS = {
    "fixed-user-song": ("fixed", "per-user", "per-song"),
    "song-fixed-user": ("per-song", "fixed", "per-user"),
}


@functools.lru_cache(maxsize=None)
def music():
    """Seeded ratings: few fat users (20 to ~6x the cap rows each), many
    thin songs with a fat head (most of one to three rows, the head past
    the cap); a planted model on all three coordinates."""
    rng = np.random.default_rng(20261002)
    counts = np.clip(
        (20.0 * (1.0 - rng.uniform(size=N_USERS)) ** (-1 / 0.7)).astype(int),
        20, 6 * CAP,
    )
    user = np.repeat(np.arange(N_USERS), counts)
    n = user.size
    p = (np.arange(N_SONGS) + 1.0) ** -1.1
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
    assert np.bincount(song, minlength=N_SONGS).min() <= 1
    return x, {"userId": user, "songId": song}, y


def game_data(labels=None):
    x, ids, y = music()
    return GameData.create(
        features=x, labels=y if labels is None else labels, entity_ids=ids
    )


def active_sample(design, ids):
    """What the design trains each entity on, as the reference takes it:
    entity -> (row ids, weights) for every entity over the cap, and the
    (n,) train weight of every row (0 = passive)."""
    counts = np.bincount(ids, minlength=design.num_entities)
    sample, weight_of_row = {}, np.zeros(ids.size)
    for bucket, lanes in zip(design.buckets, design.entity_index):
        rows, weights = np.asarray(bucket.row_index), np.asarray(
            bucket.weights, np.float64)
        for lane, entity in enumerate(np.asarray(lanes)):
            if entity >= design.num_entities:
                continue
            held = rows[lane] >= 0
            weight_of_row[rows[lane][held]] = weights[lane][held]
            if counts[entity] > CAP:
                sample[int(entity)] = (rows[lane][held], weights[lane][held])
    return sample, weight_of_row


def build(order, dtype, fuse, labels=None):
    """(CoordinateDescent, reference problem) over the same rows, the
    reference given the designs' own active samples."""
    x, ids, _ = music()
    data = game_data(labels)
    n = data.num_rows
    y = jnp.asarray(data.labels, dtype)
    zeros, ones = jnp.zeros((n,), dtype), jnp.ones((n,), dtype)
    common = dict(
        task=TaskType.LOGISTIC_REGRESSION, optimizer=OptimizerType.NEWTON,
        max_iters=NEWTON_ITERATIONS, tolerance=0.0,
    )
    coords, problem = {}, []
    for name in order:
        if name == "fixed":
            coords[name] = FixedEffectCoordinate(
                LabeledBatch(features=jnp.asarray(x["global"], dtype),
                             labels=y, offsets=zeros, weights=ones, mask=ones),
                CoordinateConfig(shard="global", reg_weight=L2[name],
                                 **common),
            )
            problem.append({"name": name, "kind": "fixed", "x": x["global"],
                            "l2": L2[name]})
            continue
        column, shard, entities = TABLES[name]
        design = build_bucketed_random_effect_design(
            data, column, shard, entities, num_buckets=3, active_cap=CAP,
            dtype=dtype,
        )
        coords[name] = RandomEffectCoordinate(
            design=design,
            row_features=jnp.asarray(x[shard], dtype),
            row_entities=jnp.asarray(ids[column], jnp.int32),
            full_offsets_base=zeros,
            config=CoordinateConfig(shard=shard, reg_weight=L2[name],
                                    random_effect=column, **common),
        )
        problem.append({
            "name": name, "kind": "random", "x": x[shard],
            "ids": ids[column], "entities": entities, "l2": L2[name],
            "sample": active_sample(design, ids[column])[0],
        })
    cd = CoordinateDescent(
        coordinates=coords, labels=y, base_offsets=zeros, weights=ones,
        task=TaskType.LOGISTIC_REGRESSION, fuse_passes=fuse,
    )
    return cd, {"labels": np.asarray(data.labels), "coordinates": problem}


@functools.lru_cache(maxsize=None)
def reference_run(order_name, dtype_name):
    _, problem = build(ORDERS[order_name], jnp.float64, True)
    return ref.block_coordinate_descent(
        problem, CD_ITERATIONS, NEWTON_ITERATIONS, jnp.dtype(dtype_name))


def worst_gaps(got_params, got_values, want_params, want_values):
    """(largest relative objective gap over the updates, largest relative
    L2 gap over the parameter sets)."""
    values = max(
        abs(g - w) / abs(w) for g, w in zip(got_values, want_values))
    params = max(
        float(np.linalg.norm(np.asarray(got_params[k], np.float64)
                             - np.asarray(want_params[k], np.float64))
              / np.linalg.norm(np.asarray(want_params[k], np.float64)))
        for k in want_params
    )
    return values, params


# float64: both sides do the same arithmetic up to summation order.
# float32: over both orders and the three dispatch modes the program reads
# at most 9.3e-7 on an objective and 2.8e-5 on a parameter set against the
# float64 reference (the reference itself, run in float32: 1.0e-5 and
# 3.7e-4); bfloat16 in the program's place reads 6.6e-3 and 7.9e-2 at the
# least.  The float32 limits sit twenty times above the first and more than
# two decades below the last.
TOLERANCE = {"float64": (1e-8, 1e-8), "float32": (2e-5, 6e-4)}


@pytest.mark.parametrize("order_name", sorted(ORDERS))
@pytest.mark.parametrize("fuse", [True, "coordinate", False])
@pytest.mark.parametrize("dtype_name", ["float64", "float32"])
def test_descent_matches_plain_reference(order_name, fuse, dtype_name):
    cd, _ = build(ORDERS[order_name], jnp.dtype(dtype_name), fuse)
    model, history = cd.run(num_iterations=CD_ITERATIONS)
    want_params, want_values = reference_run(order_name, "float64")
    assert [h.coordinate for h in history] == list(
        ORDERS[order_name]) * CD_ITERATIONS
    assert model.params["per-user"].dtype == jnp.dtype(dtype_name)
    values, params = worst_gaps(
        model.params, [h.objective for h in history], want_params,
        want_values)
    tol_values, tol_params = TOLERANCE[dtype_name]
    assert values <= tol_values and params <= tol_params, (values, params)


@pytest.mark.parametrize("order_name", sorted(ORDERS))
def test_bfloat16_in_the_programs_place_fails_the_float32_tolerance(
        order_name):
    low_params, low_values = reference_run(order_name, "bfloat16")
    want_params, want_values = reference_run(order_name, "float64")
    values, params = worst_gaps(low_params, low_values, want_params,
                                want_values)
    tol_values, tol_params = TOLERANCE["float32"]
    assert values > 10 * tol_values and params > 10 * tol_params, (
        values, params)


@pytest.mark.parametrize("name", sorted(TABLES))
def test_capped_design_follows_the_rule(name):
    column, shard, entities = TABLES[name]
    ids = music()[1][column]
    design = build_bucketed_random_effect_design(
        game_data(), column, shard, entities, num_buckets=3, active_cap=CAP,
        dtype=jnp.float64,
    )
    counts = np.bincount(ids, minlength=entities)
    sample, weight_of_row = active_sample(design, ids)
    assert sorted(sample) == list(np.flatnonzero(counts > CAP))
    for entity, (rows, weights) in sample.items():
        assert rows.size == CAP == np.unique(rows).size
        assert np.all(ids[rows] == entity)  # all the entity's own
        np.testing.assert_allclose(weights, counts[entity] / CAP, rtol=1e-12)
    active = weight_of_row > 0
    # uncapped entities whole, weight 1; the rest of a capped one passive
    np.testing.assert_array_equal(
        np.bincount(ids[active], minlength=entities),
        np.minimum(counts, CAP))
    assert np.all(weight_of_row[active & (counts[ids] <= CAP)] == 1.0)
    assert max(b.rows_per_entity for b in design.buckets) == CAP
    # an entity sits in one bucket only, on a lane of its own
    lanes = np.concatenate([np.asarray(e) for e in design.entity_index])
    lanes = lanes[lanes < entities]
    assert np.unique(lanes).size == lanes.size == np.sum(counts > 0)


@pytest.mark.parametrize("name", sorted(TABLES))
def test_passive_row_is_scored_and_never_trained_on(name):
    """Flip the label of a row that is passive in this table: the table's
    update is unchanged to the bit, the reported objective is not."""
    column, shard, entities = TABLES[name]
    x, ids, y = music()
    design = build_bucketed_random_effect_design(
        game_data(), column, shard, entities, num_buckets=3, active_cap=CAP,
        dtype=jnp.float64,
    )
    _, weight_of_row = active_sample(design, ids[column])
    passive = int(np.flatnonzero(weight_of_row == 0)[0])
    flipped = y.copy()
    flipped[passive] = 1.0 - flipped[passive]

    def one_update(labels):
        cd, _ = build((name,), jnp.float64, False, labels=labels)
        model, history = cd.run(num_iterations=1)
        return np.asarray(model.params[name]), history[-1].objective

    table, value = one_update(y)
    table_flipped, value_flipped = one_update(flipped)
    np.testing.assert_array_equal(table, table_flipped)
    assert np.any(table[ids[column][passive]] != 0.0)
    assert abs(value - value_flipped) > 1e-3


@pytest.mark.parametrize("name", sorted(TABLES))
def test_design_span_and_counters_equal_numpys_counts(name):
    column, shard, entities = TABLES[name]
    ids = music()[1][column]
    reg = obs.registry()
    before = {k: reg.counter(k).value
              for k in ("game.re.capped_entities", "game.re.passive_rows")}
    design = build_bucketed_random_effect_design(
        game_data(), column, shard, entities, num_buckets=3, active_cap=CAP,
        dtype=jnp.float64,
    )
    counts = np.bincount(ids, minlength=entities)
    active = int(np.minimum(counts, CAP).sum())
    spans = [s for s in obs.recent_spans() if s[0] == "game.design"]
    assert len(spans) == 1
    attrs = spans[0][6]
    assert attrs == {
        "random_effect": column,
        "entities": int(np.sum(counts > 0)),
        "buckets": 3,
        "bucket_caps": [b.rows_per_entity for b in design.buckets],
        "active_rows": active,
        "active_slots": sum(
            len(e) * b.rows_per_entity
            for e, b in zip(design.entity_index, design.buckets)),
        "capped_entities": int(np.sum(counts > CAP)),
        "passive_rows": ids.size - active,
    }
    assert attrs["bucket_caps"][-1] == CAP
    assert attrs["active_slots"] >= active
    assert reg.counter("game.re.capped_entities").value - before[
        "game.re.capped_entities"] == attrs["capped_entities"]
    assert reg.counter("game.re.passive_rows").value - before[
        "game.re.passive_rows"] == attrs["passive_rows"]
    assert obs.taxonomy.matches("game.design")
    assert obs.taxonomy.matches("game.re.capped_entities")
    assert obs.taxonomy.matches("game.re.passive_rows")


def _fresh_programs():
    """Forget every traced program, so that the next build traces anew."""
    coordinates_mod._make_multi_bucket_update_cached.cache_clear()
    coordinates_mod._make_fixed_update_and_score_cached.cache_clear()
    coordinates_mod._make_solve_cached.cache_clear()
    jax.clear_caches()


def _fused_pass_and_arguments(order):
    cd, _ = build(order, jnp.float32, True)
    cd._fused_pass_fn()
    names = list(cd.coordinates)
    params = {n: cd.coordinates[n].initial_params() for n in names}
    scores = {n: jnp.zeros_like(cd.labels) for n in names}
    return cd, cd._fused_pass, (
        {n: cd.coordinates[n].fused_state() for n in names},
        cd.labels, cd.base_offsets, cd.weights, params, scores,
        jax.random.PRNGKey(0),
    )


def _op_names(fused, args):
    """The ``op_name`` of every instruction of the compiled pass."""
    import re

    return set(re.findall(
        r'op_name="([^"]*)"', fused.lower(*args).compile().as_text()))


@functools.lru_cache(maxsize=None)
def _op_names_of_the_pass():
    _, fused, args = _fused_pass_and_arguments(ORDERS["fixed-user-song"])
    return _op_names(fused, args)


@pytest.mark.parametrize("scope", [
    "fixed/jit(run)/fe_solve/",
    "per-user/jit(update_all)/re_gather/",
    "per-user/jit(update_all)/re_newton_solve/",
    "per-user/jit(update_all)/re_scatter/",
    "per-user/jit(update_all)/re_score/",
    "per-song/jit(update_all)/re_gather/",
    "per-song/jit(update_all)/re_newton_solve/",
    "per-song/jit(update_all)/re_scatter/",
    "per-song/jit(update_all)/re_score/",
    "per-song/jit(update_all)/re_gather/offsets/",
    "per-song/jit(update_all)/re_gather/warm_start/",
    "per-song/jit(update_all)/re_gather/reg_weight/",
])
def test_scope_is_in_the_compiled_pass(scope):
    names = _op_names_of_the_pass()
    assert any(scope in name for name in names)
    # a table's gathers, scatters and rescoring never fall outside its
    # coordinate's scope
    assert not any(
        "re_" in name and "per-user/" not in name and "per-song/" not in name
        for name in names
    )


def test_scopes_change_no_bit_of_the_result(monkeypatch):
    def run(scoped):
        _fresh_programs()
        with monkeypatch.context() as m:
            if not scoped:
                m.setattr(jax, "named_scope",
                          lambda name: contextlib.nullcontext())
            cd, fused, args = _fused_pass_and_arguments(
                ORDERS["fixed-user-song"])
            has = any("re_scatter" in name for name in _op_names(fused, args))
            model, history = cd.run(num_iterations=2)
        return has, model, [h.objective for h in history]

    try:
        with_scopes, model, values = run(True)
        without, model_bare, values_bare = run(False)
    finally:
        _fresh_programs()
    assert with_scopes and not without
    assert values == values_bare
    for name in model.params:
        np.testing.assert_array_equal(
            np.asarray(model.params[name]), np.asarray(model_bare.params[name])
        )


def test_the_benchmarks_reference_agrees_with_the_repos():
    """``chipbench/reference_multi.py`` (float32, row blocks, train weights
    as an (n,) vector) against ``reference_game`` (float64, samples as row
    lists) at a model part of the way down."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        from chipbench import reference_multi
    finally:
        sys.path.pop(0)
    order = ORDERS["fixed-user-song"]
    _, problem = build(order, jnp.float64, True)
    params, _ = reference_run("fixed-user-song", "float64")
    n = problem["labels"].size
    parts = []
    for c in problem["coordinates"]:
        part = {"kind": c["kind"], "x": jnp.asarray(c["x"], jnp.float32),
                "params": np.asarray(params[c["name"]], np.float32),
                "l2": c["l2"]}
        if c["kind"] == "random":
            part["ids"] = jnp.asarray(c["ids"], jnp.int32)
            part["train_weight"] = jnp.asarray(
                ref.train_weights(c, n), jnp.float32)
        parts.append(part)
    value, grads, _ = reference_multi.value_grads(
        parts, jnp.asarray(problem["labels"], jnp.float32))
    assert abs(float(value) - float(ref.objective(problem, params))) <= (
        1e-5 * float(value))
    want = ref.gradients(problem, params)
    at_zero = ref.gradients(
        problem, {k: np.zeros_like(np.asarray(v)) for k, v in params.items()})
    for c, g in zip(problem["coordinates"], grads):
        scale = float(jnp.linalg.norm(jnp.asarray(at_zero[c["name"]])))
        gap = float(jnp.linalg.norm(
            jnp.asarray(g, jnp.float64) - want[c["name"]]))
        assert gap <= 1e-5 * scale, (c["name"], gap, scale)
