"""The factored random effect (w_e = B gamma_e, ``game/factored.py``) against
the plain reference (``tests/reference_game.py``: per-entity Newton on
explicitly projected rows, then Newton on the MATERIALISED Kronecker design
x (x) gamma with vec(B) as its coefficients), on a bucketed design with an
entity past the active cap and sentinel lanes; its value, gradients and
Hessian-vector product at a random point; a three-coordinate descent fused
against unfused; and what its tracker says of every lane and of the B solve.
"""

import copy
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_game as ref
import test_game_multi_re as multi
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
from photon_ml_tpu.game import factored as factored_mod
from photon_ml_tpu.game.coordinates import _make_solve
from photon_ml_tpu.game.data import (
    fill_offsets,
    gather_held_offsets,
    held_slot_values,
    offsets_gather_maps,
    spread_lanes,
)
from photon_ml_tpu.game.factored import (
    FactoredConfig,
    FactoredParams,
    FactoredRandomEffectCoordinate,
)
from photon_ml_tpu.models.training import OptimizerType
from photon_ml_tpu.ops import metrics as metrics_mod
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.solvers.common import ConvergenceReason, final_grad_norm

CAP = multi.CAP
N_USERS, N_SONGS = 8, 40
DIMS = {"global": 4, "per_user": 3, "per_song": 6}
LATENT = 2
L2 = {"fixed": 1.0, "per-user": 10.0, "per-song": 10.0}
L2_PROJECTION = 1.0
ENTITY_MULTIPLE = 4  # pads every bucket's lanes: sentinel lanes


@functools.lru_cache(maxsize=None)
def ratings():
    """Seeded ratings with a planted rank-LATENT song effect: few users of
    30 to 90 rows, songs Zipf so that the head passes the cap, most of the
    tail holds one to three rows and a few songs hold none."""
    rng = np.random.default_rng(20261005)
    user = np.repeat(np.arange(N_USERS), rng.integers(30, 90, size=N_USERS))
    n = user.size
    p = (np.arange(N_SONGS) + 1.0) ** -1.3
    song = rng.choice(N_SONGS, size=n, p=p / p.sum())
    order = rng.permutation(n)
    user, song = user[order], song[order]
    x = {k: rng.normal(size=(n, d)) for k, d in DIMS.items()}
    w_song = rng.normal(size=(N_SONGS, LATENT)) @ rng.normal(
        size=(LATENT, DIMS["per_song"]))
    margin = (
        x["global"] @ rng.normal(size=DIMS["global"])
        + np.sum(x["per_user"] * rng.normal(
            size=(N_USERS, DIMS["per_user"]))[user], axis=1)
        + np.sum(x["per_song"] * w_song[song], axis=1)
    )
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(float)
    counts = np.bincount(song, minlength=N_SONGS)
    assert counts.max() > CAP and counts.min() == 0
    return x, {"userId": user, "songId": song}, y


def initial_projection():
    return np.random.default_rng(5).normal(
        0.0, 1.0 / np.sqrt(DIMS["per_song"]),
        size=(DIMS["per_song"], LATENT))


def song_design(dtype):
    x, ids, y = ratings()
    data = GameData.create(features=x, labels=y, entity_ids=ids)
    return build_bucketed_random_effect_design(
        data, "songId", "per_song", N_SONGS, num_buckets=3, active_cap=CAP,
        entity_multiple=ENTITY_MULTIPLE, dtype=dtype,
    )


def song_coordinate(dtype, inner, lane_iters, b_iters, tolerance,
                    b_optimizer=OptimizerType.TRON, design=None,
                    b_tolerance=None):
    """(the factored per-song coordinate, its reference description); the
    B solve's tolerance is ``tolerance`` unless ``b_tolerance`` is given."""
    x, ids, _ = ratings()
    design = song_design(dtype) if design is None else design
    n = ids["songId"].size
    common = dict(task=TaskType.LOGISTIC_REGRESSION, tolerance=tolerance)
    coord = FactoredRandomEffectCoordinate(
        design=design,
        row_features=jnp.asarray(x["per_song"], dtype),
        row_entities=jnp.asarray(ids["songId"], jnp.int32),
        full_offsets_base=jnp.zeros((n,), dtype),
        re_config=CoordinateConfig(
            shard="per_song", random_effect="songId",
            optimizer=OptimizerType.NEWTON, reg_weight=L2["per-song"],
            max_iters=lane_iters, **common),
        factored=FactoredConfig(
            latent_dim=LATENT, num_inner_iterations=inner,
            latent_factor_config=CoordinateConfig(
                shard="per_song", optimizer=b_optimizer,
                reg_weight=L2_PROJECTION, max_iters=b_iters,
                **dict(common, tolerance=(
                    tolerance if b_tolerance is None else b_tolerance))),
        ),
        initial_projection=initial_projection(),
    )
    described = {
        "name": "per-song", "kind": "factored", "x": x["per_song"],
        "ids": ids["songId"], "entities": N_SONGS, "l2": L2["per-song"],
        "l2_projection": L2_PROJECTION,
        "sample": multi.active_sample(design, ids["songId"])[0],
    }
    return coord, described


def other_scores():
    """What the other coordinates would hand the update: any (n,) vector."""
    n = ratings()[2].size
    return np.random.default_rng(11).normal(size=n) * 0.5


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@functools.lru_cache(maxsize=None)
def reference_update(dtype_name):
    _, described = song_coordinate(jnp.float64, 1, 1, 1, 0.0)
    start = {"gamma": np.zeros((N_SONGS, LATENT)),
             "projection": initial_projection()}
    return ref.factored_update(described, ratings()[2], other_scores(),
                               start, jnp.dtype(dtype_name))


# Both problems of an alternation are strictly convex under their L2, so
# each has one minimiser and two converged solvers meet there.  The lane
# solves stop on their relative function-value test (|df| <= tol f0: at
# 1e-15 in float64, 1e-7 in float32).  The B solve does not: in float32 a
# decrease of 1e-7 f0 is the objective's own rounding, so that test stopped
# B anywhere within about sqrt(1e-7) of the minimiser as the order of the
# sum fell (1.7e-5 over the padded buckets, 2.0e-4 over the held rows,
# PR 37).  It runs the cell's rule instead, tolerance 0 and a budget of
# B_ITERATIONS outer iterations, which reaches its minimiser to the
# gradient's rounding.  Read against the float64 reference: the float64
# program 3.1e-10 (gamma) and 4.4e-11 (B); the float32 program 1.1e-7 and
# 1.3e-7 (a budget of 20 or 40: 1.5e-7, 1.4e-7); the reference itself in
# bfloat16 1.9e-2 and 4.2e-2.  The float32 limit sits three decades above
# its reading and two below bfloat16's.
TOLERANCE = {"float64": 1e-8, "float32": 2e-4}
SOLVER_TOLERANCE = {"float64": 1e-15, "float32": 1e-7}
B_ITERATIONS = 10


@pytest.mark.parametrize("dtype_name", ["float64", "float32"])
def test_converged_update_matches_the_kronecker_reference(dtype_name):
    dtype = jnp.dtype(dtype_name)
    coord, _ = song_coordinate(dtype, 1, 60, B_ITERATIONS,
                               SOLVER_TOLERANCE[dtype_name], b_tolerance=0.0)
    params, summary = coord.update(
        coord.initial_params(), jnp.asarray(other_scores(), dtype))
    want = reference_update("float64")
    gaps = (rel(params.gamma, want["gamma"]),
            rel(params.projection, want["projection"]))
    assert max(gaps) <= TOLERANCE[dtype_name], gaps
    # a song without a row keeps its zero gamma; sentinel lanes wrote none
    no_rows = np.bincount(ratings()[1]["songId"], minlength=N_SONGS) == 0
    assert np.all(np.asarray(params.gamma)[no_rows] == 0.0)
    assert params.gamma.shape == (N_SONGS, LATENT)
    solve = summary.inner_iterations[0]["projection"]
    assert solve["reason"] == ConvergenceReason.MAX_ITERATIONS.name
    assert solve["iterations"] == B_ITERATIONS


def test_bfloat16_in_the_programs_place_fails_the_float32_tolerance():
    low, want = reference_update("bfloat16"), reference_update("float64")
    gaps = (rel(low["gamma"], want["gamma"]),
            rel(low["projection"], want["projection"]))
    assert min(gaps) > 10 * TOLERANCE["float32"], gaps


def random_point(seed=3):
    rng = np.random.default_rng(seed)
    return {"gamma": rng.normal(size=(N_SONGS, LATENT)),
            "projection": rng.normal(size=(DIMS["per_song"], LATENT))}


def test_value_and_gamma_gradient_at_a_random_point():
    """The coordinate's score and penalty give the reference's objective;
    its gradient by gamma, for every song under the cap (where the trained
    objective is the objective), is the reference's."""
    coord, described = song_coordinate(jnp.float64, 1, 1, 1, 0.0)
    point = random_point()
    problem = {"labels": ratings()[2], "coordinates": [described]}
    y, ones = jnp.asarray(ratings()[2]), jnp.ones(ratings()[2].shape)

    def value(params):
        return metrics_mod.total_logistic_loss(
            y, coord.score(params), ones) + coord.reg_term(params)

    params = FactoredParams(gamma=jnp.asarray(point["gamma"]),
                            projection=jnp.asarray(point["projection"]))
    want = ref.objective(problem, {"per-song": point})
    assert abs(float(value(params)) - float(want)) <= 1e-11 * float(want)
    np.testing.assert_allclose(
        np.asarray(coord.score(params)),
        np.asarray(ref.score(described, point, jnp.float64)), atol=1e-12)
    got = jax.grad(value)(params)
    want_grads = ref.gradients(problem, {"per-song": point})["per-song"]
    under_cap = np.bincount(described["ids"], minlength=N_SONGS) <= CAP
    assert under_cap.sum() < N_SONGS  # a capped song is left out here
    np.testing.assert_allclose(
        np.asarray(got.gamma)[under_cap],
        np.asarray(want_grads["gamma"])[under_cap], rtol=1e-10, atol=1e-11)


def test_projection_gradient_and_hvp_against_the_kronecker_design():
    """The B problem's value, gradient and Hessian-vector product, which
    contract the bucketed design lazily, against the materialised
    Kronecker design of the trained rows (the capped song by its sample
    and weights), at a random point and against other coordinates'
    scores."""
    coord, described = song_coordinate(jnp.float64, 1, 1, 1, 0.0)
    point = random_point()
    offsets = other_scores()
    value_and_grad, hvp = factored_mod._latent_objective(
        loss_for_task(TaskType.LOGISTIC_REGRESSION), L2_PROJECTION,
        point["projection"].shape, coord._held,
        *held_inputs(coord, point["gamma"], offsets))
    vec_b = jnp.asarray(point["projection"]).reshape(-1)
    direction = jnp.asarray(
        np.random.default_rng(4).normal(size=vec_b.shape))
    got_value, got_grad = value_and_grad(vec_b)
    got_hvp = hvp(vec_b, direction)

    x, ids = described["x"], described["ids"]
    weights = ref.train_weights(described, ids.size)
    kron = np.asarray(ref.kronecker_design(
        jnp.asarray(x), jnp.asarray(point["gamma"])[ids]))
    z = kron @ np.asarray(vec_b) + offsets
    y = ratings()[2]
    p = 1.0 / (1.0 + np.exp(-z))
    want_value = np.sum(weights * np.logaddexp(0.0, -(2 * y - 1) * z)) + (
        0.5 * L2_PROJECTION * float(vec_b @ vec_b))
    want_grad = kron.T @ (weights * (p - y)) + L2_PROJECTION * np.asarray(
        vec_b)
    hessian = (kron * (weights * p * (1 - p))[:, None]).T @ kron + (
        L2_PROJECTION * np.eye(kron.shape[1]))
    assert abs(float(got_value) - want_value) <= 1e-11 * want_value
    np.testing.assert_allclose(np.asarray(got_grad), want_grad,
                               rtol=1e-10, atol=1e-11)
    np.testing.assert_allclose(np.asarray(got_hvp),
                               hessian @ np.asarray(direction),
                               rtol=1e-10, atol=1e-11)
    # and the reference's own gradient by B is that of the same design
    problem = {"labels": y, "coordinates": [described]}
    shifted = dict(described, name="offsets", kind="fixed",
                   x=offsets[:, None], l2=0.0)
    problem["coordinates"].append(shifted)
    want_b = ref.gradients(
        problem, {"per-song": point, "offsets": np.ones(1)}
    )["per-song"]["projection"]
    np.testing.assert_allclose(np.asarray(got_grad).reshape(want_b.shape),
                               np.asarray(want_b), rtol=1e-10, atol=1e-11)


def held_entity(design, size):
    """(size,) int32, host: the gamma table row of every held-row entry's
    lane, in ``perm``'s order (a wasted entry of an unordered design names
    its lane's entity; a sharding pad lane names ``num_entities``), 0 past
    the entries."""
    masks = [np.asarray(b.mask) for b in design.buckets]
    entity = held_slot_values(
        [np.broadcast_to(np.asarray(ei)[:, None], m.shape)
         for ei, m in zip(design.entity_index, masks)], masks)
    return np.pad(entity, (0, size - entity.size)).astype(np.int32)


def held_inputs(coord, gamma, offsets):
    """(gamma a held row, residual offset a held row), in the coordinate's
    held-row shape: what its update hands the B solve, read here from the
    table by each entry's entity."""
    entity = held_entity(coord.design, coord._held.weights.shape[0])
    return (
        jnp.take(jnp.asarray(gamma), entity, axis=0, mode="clip"),
        factored_mod._held_vector(gather_held_offsets(
            jnp.asarray(offsets), coord._offsets_maps[0]), coord._held),
    )


def descent(fuse, dtype=jnp.float64):
    """fixed + plain per-user + factored per-song, budgeted as a job."""
    x, ids, y = ratings()
    n = y.size
    labels = jnp.asarray(y, dtype)
    zeros, ones = jnp.zeros((n,), dtype), jnp.ones((n,), dtype)
    common = dict(task=TaskType.LOGISTIC_REGRESSION,
                  optimizer=OptimizerType.NEWTON, max_iters=2, tolerance=0.0)
    data = GameData.create(features=x, labels=y, entity_ids=ids)
    coords = {
        "fixed": FixedEffectCoordinate(
            LabeledBatch(features=jnp.asarray(x["global"], dtype),
                         labels=labels, offsets=zeros, weights=ones,
                         mask=ones),
            CoordinateConfig(shard="global", reg_weight=L2["fixed"],
                             **common)),
        "per-user": RandomEffectCoordinate(
            design=build_bucketed_random_effect_design(
                data, "userId", "per_user", N_USERS, num_buckets=2,
                active_cap=CAP, dtype=dtype),
            row_features=jnp.asarray(x["per_user"], dtype),
            row_entities=jnp.asarray(ids["userId"], jnp.int32),
            full_offsets_base=zeros,
            config=CoordinateConfig(
                shard="per_user", reg_weight=L2["per-user"],
                random_effect="userId", **common)),
        "per-song": song_coordinate(dtype, 2, 2, 3, 0.0)[0],
    }
    return CoordinateDescent(
        coordinates=coords, labels=labels, base_offsets=zeros, weights=ones,
        task=TaskType.LOGISTIC_REGRESSION, fuse_passes=fuse,
    )


@functools.lru_cache(maxsize=None)
def descent_run(fuse):
    return descent(fuse).run(num_iterations=2)


@pytest.mark.parametrize("fuse", ["coordinate", False])
def test_three_coordinate_descent_fused_against_unfused(fuse):
    model, history = descent_run(True)
    other_model, other_history = descent_run(fuse)
    assert [h.coordinate for h in history] == [
        "fixed", "per-user", "per-song"] * 2
    for h, o in zip(history, other_history):
        assert h.coordinate == o.coordinate
        assert abs(h.objective - o.objective) <= 1e-10 * abs(o.objective)
        assert h.solver_iterations == o.solver_iterations
        assert h.convergence_histogram == o.convergence_histogram
        if h.coordinate != "per-song":
            assert h.inner_iterations is None
            continue
        for a, b in zip(h.inner_iterations, o.inner_iterations):
            assert a["lanes"] == b["lanes"]
            for key in ("iterations", "cg_iterations", "passes", "reason"):
                assert a["projection"][key] == b["projection"][key]
    for name in ("fixed", "per-user"):
        assert rel(model.params[name], other_model.params[name]) <= 1e-10
    for leaf in ("gamma", "projection"):
        assert rel(getattr(model.params["per-song"], leaf),
                   getattr(other_model.params["per-song"], leaf)) <= 1e-10
    objectives = [h.objective for h in history]
    assert objectives[-1] < objectives[0]


def test_tracker_counts_every_lane_and_the_projection_solve():
    """One inner iteration, so that the fetched gamma is what the B solve
    saw: the tracker's counts of that solve are ``minimize_tron``'s own,
    run alone on the same inputs; every real lane of every bucket is in
    the record, no sentinel lane is."""
    coord, _ = song_coordinate(jnp.float64, 1, 2, 3, 0.0)
    offsets = jnp.asarray(other_scores())
    start = coord.initial_params()
    params, summary = coord.update(start, offsets)
    design = coord.design
    lanes = sum(len(ei) for ei in design.entity_index)
    real = sum(int(np.count_nonzero(np.asarray(ei) < N_SONGS))
               for ei in design.entity_index)
    with_rows = int(np.count_nonzero(
        np.bincount(ratings()[1]["songId"], minlength=N_SONGS)))
    assert lanes > real == with_rows  # the sentinel lanes are not counted
    (inner,) = summary.inner_iterations
    assert inner["lanes"]["count"] == real == summary.iterations.size
    assert sum(inner["lanes"]["convergence_histogram"].values()) == real
    assert inner["lanes"]["solver_iterations"] == 2.0
    assert sorted(summary.entity_ids) == sorted(
        np.flatnonzero(np.bincount(ratings()[1]["songId"],
                                   minlength=N_SONGS)))
    assert np.all(np.isfinite(summary.grad_norms))

    alone = factored_mod._make_latent_solve(coord._latent_cfg)(
        start.projection, *held_inputs(coord, params.gamma, offsets),
        coord._held,
    )
    solve = inner["projection"]
    assert solve["iterations"] == int(alone.iterations) == 3
    assert solve["cg_iterations"] == int(alone.cg_iterations) > 0
    assert solve["passes"] == 3 + 1 + int(alone.cg_iterations)
    assert solve["reason"] == ConvergenceReason.MAX_ITERATIONS.name
    assert solve["grad_norm"] == pytest.approx(
        float(jnp.linalg.norm(alone.grad)), rel=1e-12)
    np.testing.assert_allclose(np.asarray(params.projection).reshape(-1),
                               np.asarray(alone.w), rtol=1e-12)


def padded_objective(lam, shape, buckets, gammas, bucket_offsets):
    """The B problem over the padded buckets, slot by slot, each lane's
    gamma broadcast over its slots: the form the held rows replaced (PR
    37), ``(value_and_grad(vecB), hvp(vecB, vecV))``."""
    loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
    d, k = shape

    def terms(B, V):
        out = []
        for bucket, gamma_b, offsets in zip(buckets, gammas, bucket_offsets):
            w = bucket.weights * bucket.mask
            xb = jnp.einsum("erd,dk,ek->er", bucket.features, B, gamma_b,
                            precision="highest")
            dz = jnp.einsum("erd,dk,ek->er", bucket.features, V, gamma_b,
                            precision="highest")
            out.append((bucket, gamma_b, w, xb + offsets, dz))
        return out

    def contract(bucket, gamma_b, c):
        return jnp.einsum("erd,er,ek->dk", bucket.features, c, gamma_b,
                          precision="highest")

    def value_and_grad(vecB):
        B = vecB.reshape(d, k)
        val, grad = 0.5 * lam * jnp.vdot(B, B), lam * B
        for bucket, gamma_b, w, z, _ in terms(B, B):
            val = val + jnp.sum(w * loss.value(z, bucket.labels))
            grad = grad + contract(
                bucket, gamma_b, w * loss.d1(z, bucket.labels))
        return val, grad.reshape(-1)

    def hvp(vecB, vecV):
        B, V = vecB.reshape(d, k), vecV.reshape(d, k)
        out = lam * V
        for bucket, gamma_b, w, z, dz in terms(B, V):
            out = out + contract(
                bucket, gamma_b, w * loss.d2(z, bucket.labels) * dz)
        return out.reshape(-1)

    return value_and_grad, hvp


def shuffled_lanes(design, seed):
    """The design with every bucket's lanes in a random order: a slot's
    range of lanes then holds lanes that do not reach it, so the offsets
    maps' ``perm`` carries wasted entries (an unordered design)."""
    rng = np.random.default_rng(seed)
    buckets, index = [], []
    for bucket, ei in zip(design.buckets, design.entity_index):
        order = rng.permutation(bucket.num_entities)
        buckets.append(jax.tree_util.tree_map(lambda a: a[order], bucket))
        index.append(np.asarray(ei)[order])
    return dataclasses.replace(design, buckets=buckets, entity_index=index)


@pytest.mark.parametrize("ordered", [True, False])
def test_held_rows_give_the_padded_buckets_objective(ordered):
    """The B problem over the held rows against the same problem over the
    padded buckets, float32: its value, gradient and Hessian-vector
    product at a random point, on the song design with a capped (rescaled)
    song, passive rows and lanes padded for sharding, its lanes as built
    and shuffled (wasted ``perm`` entries, weight 0)."""
    design = song_design(jnp.float32)
    if not ordered:
        design = shuffled_lanes(design, 7)
    coord, _ = song_coordinate(jnp.float32, 1, 1, 1, 0.0, design=design)
    held_slots = sum(int(np.count_nonzero(np.asarray(b.mask) > 0))
                     for b in design.buckets)
    perm = np.asarray(coord._offsets_maps[0])
    assert coord._held_rows == held_slots
    assert (perm.size > held_slots) == (not ordered)
    held = coord._held
    size = held.weights.shape[0]
    assert held.features.shape == (DIMS["per_song"], size)
    assert size % factored_mod.HELD_ROWS_ALIGN == 0
    assert size - perm.size < factored_mod.HELD_ROWS_ALIGN
    # the cap's rescale is kept: every slot's weight, once
    np.testing.assert_allclose(
        float(jnp.sum(held.weights)),
        sum(float(jnp.sum(b.weights * b.mask)) for b in design.buckets),
        rtol=1e-6)
    # a held entry is the row perm names, of its lane's entity
    real = np.asarray(held.weights)[:perm.size] > 0
    assert real.sum() == held_slots
    assert not np.any(np.asarray(held.weights)[perm.size:])
    # a held row's gamma is its song's
    songs = ratings()[1]["songId"]
    table = np.arange(N_SONGS * LATENT, dtype=np.float32).reshape(
        N_SONGS, LATENT)
    gamma_rows = np.asarray(
        held_inputs(coord, table, np.zeros(songs.size))[0])
    np.testing.assert_array_equal(
        gamma_rows[:perm.size][real], table[songs[perm[real]]])
    # and its features are that row's
    np.testing.assert_array_equal(
        np.asarray(held.features)[:, :perm.size][:, real],
        np.asarray(ratings()[0]["per_song"], np.float32)[perm[real]].T)

    point = random_point()
    offsets = jnp.asarray(other_scores(), jnp.float32)
    gamma = jnp.asarray(point["gamma"], jnp.float32)
    vec_b = jnp.asarray(point["projection"], jnp.float32).reshape(-1)
    direction = jnp.asarray(
        np.random.default_rng(4).normal(size=vec_b.shape), jnp.float32)
    got_vg, got_hvp = factored_mod._latent_objective(
        loss_for_task(TaskType.LOGISTIC_REGRESSION), L2_PROJECTION,
        point["projection"].shape, held,
        *held_inputs(coord, gamma, offsets))
    want_vg, want_hvp = padded_objective(
        L2_PROJECTION, point["projection"].shape, design.buckets,
        [jnp.take(gamma, jnp.asarray(ei), axis=0, mode="clip")
         for ei in design.entity_index],
        [b.gather_offsets(offsets) for b in design.buckets])
    (got_value, got_grad), (want_value, want_grad) = (
        got_vg(vec_b), want_vg(vec_b))
    assert abs(float(got_value) - float(want_value)) <= 1e-6 * abs(
        float(want_value))
    assert rel(got_grad, want_grad) <= 1e-6
    assert rel(got_hvp(vec_b, direction),
               want_hvp(vec_b, direction)) <= 1e-6


def prefix_held_design(shapes, shuffle, sentinels, pads=0, seed=17):
    """(rows, design) of buckets of prefix-held slots, each slot a row of
    its own, lanes in count order or shuffled: ``shapes`` the (lanes,
    depth) of each bucket before ``sentinels`` empty lanes and ``pads``
    sharding pad lanes (entity index == ``num_entities``) a bucket."""
    from photon_ml_tpu.game.data import (
        BucketedRandomEffectDesign,
        RandomEffectDesign,
    )

    rng = np.random.default_rng(seed)
    num_entities = 100
    n = sum(lanes * depth for lanes, depth in shapes)
    rows = jnp.asarray(rng.normal(size=(n, 5)), jnp.float32)
    free = iter(rng.permutation(n))
    entities = iter(rng.permutation(num_entities))  # one lane an entity
    buckets, index = [], []
    for lanes, depth in shapes:
        count = np.sort(rng.integers(0, depth + 1, size=lanes))
        count = np.concatenate([count, np.zeros(sentinels + pads, int)])
        entity = np.concatenate([
            [next(entities) for _ in range(lanes + sentinels)],
            np.full(pads, num_entities)]).astype(np.int32)
        if shuffle:
            order = rng.permutation(count.size)
            count, entity = count[order], entity[order]
        mask = (np.arange(depth)[None, :] < count[:, None]).astype(np.float32)
        row_index = np.full(mask.shape, -1, np.int32)
        for lane, slot in zip(*np.nonzero(mask)):
            row_index[lane, slot] = next(free)
        buckets.append(RandomEffectDesign(
            features=jnp.where(mask[..., None] > 0,
                               rows[np.maximum(row_index, 0)], 0.0),
            labels=jnp.asarray(rng.integers(0, 2, mask.shape), jnp.float32),
            weights=jnp.asarray(rng.uniform(0.5, 2.0, mask.shape),
                                jnp.float32),
            mask=jnp.asarray(mask),
            row_index=jnp.asarray(row_index)))
        index.append(entity)
    return rows, BucketedRandomEffectDesign(
        buckets=buckets, entity_index=index, num_entities=num_entities)


@pytest.mark.parametrize("shuffle", [False, True])
@pytest.mark.parametrize("sentinels", [0, 5])
def test_held_rows_are_the_perm_entries_in_order(shuffle, sentinels):
    """The held-row build against a plain loop on buckets of prefix-held
    slots, each a row of its own, lanes in count order or shuffled, with
    ``sentinels`` empty lanes a bucket: every ``perm`` entry (slot-major,
    slot j over the lanes from the first to the last that hold it) carries
    its row's features, weight x mask, label and, spread from the lanes,
    its lane's entity, in that order, then zeros."""
    rows, design = prefix_held_design(((23, 3), (9, 11), (4, 30)), shuffle,
                                      sentinels)
    buckets, index = design.buckets, design.entity_index
    perm, starts = offsets_gather_maps(
        [(np.asarray(b.row_index), b.mask) for b in buckets])
    held, held_slots = factored_mod._build_held_rows(
        design, jnp.asarray(perm), rows)

    want = {k: [] for k in ("row", "weights", "labels", "entity")}
    for bucket, eidx in zip(buckets, index):
        mask, ri = np.asarray(bucket.mask), np.asarray(bucket.row_index)
        for j in range(mask.shape[1]):
            holders = [e for e in range(mask.shape[0]) if mask[e, j] > 0]
            for e in range(holders[0], holders[-1] + 1) if holders else ():
                want["row"].append(max(ri[e, j], 0))
                want["weights"].append(
                    float(bucket.weights[e, j]) * mask[e, j])
                want["labels"].append(float(bucket.labels[e, j]))
                want["entity"].append(eidx[e])
    size = held.weights.shape[0]
    entries = len(want["row"])
    assert entries == perm.size
    assert size % factored_mod.HELD_ROWS_ALIGN == 0
    assert size - entries < factored_mod.HELD_ROWS_ALIGN
    assert held_slots == sum(int(np.count_nonzero(b.mask)) for b in buckets)
    assert (entries > held_slots) == shuffle

    def padded(values, dtype):
        return np.pad(np.asarray(values, dtype), (0, size - entries))

    np.testing.assert_array_equal(
        np.asarray(held.features),
        np.pad(np.asarray(rows)[want["row"]], ((0, size - entries), (0, 0))).T)
    np.testing.assert_array_equal(np.asarray(held.weights),
                                  padded(want["weights"], np.float32))
    np.testing.assert_array_equal(np.asarray(held.labels),
                                  padded(want["labels"], np.float32))
    spread = spread_lanes(
        [jnp.asarray(ei, jnp.float32)[:, None] for ei in index],
        tuple(jnp.asarray(s) for s in starts), [b.mask for b in buckets],
        size)
    assert spread.shape == (size, 1)
    np.testing.assert_array_equal(np.asarray(spread)[:, 0],
                                  padded(want["entity"], np.float32))


@pytest.mark.parametrize("shapes, shuffle, sentinels, pads", [
    (((23, 3), (9, 11), (4, 30)), False, 0, 0),
    (((23, 3), (9, 11), (4, 30)), True, 0, 0),
    (((23, 3), (9, 11), (4, 30)), True, 5, 3),
    (((40, 2), (7, 6), (3, 9), (2, 40)), False, 0, 4),
    (((40, 2), (7, 6), (3, 9), (2, 40)), True, 2, 4),
    (((1, 1), (60, 5)), True, 0, 0),
])
def test_spread_lanes_is_the_gather_of_the_scattered_table(
        shapes, shuffle, sentinels, pads):
    """The lanes' values spread over the held rows by runs, against the
    table round trip they replace: every bucket's lanes scattered into the
    (E, k) table, then gathered a held row by its lane's entity. Bit for
    bit at every entry whose lane holds an entity (the held rows, and the
    wasted entries of an unordered design), zeros past the entries, and
    finite on the wasted entries of sharding pad lanes, which weigh 0 and
    which the scatter drops; buckets in count order or shuffled, with
    empty lanes and pad lanes, of several shapes."""
    rows, design = prefix_held_design(shapes, shuffle, sentinels, pads)
    perm, starts = offsets_gather_maps(
        [(np.asarray(b.row_index), b.mask) for b in design.buckets])
    assert (perm.size > sum(int(np.count_nonzero(b.mask))
                            for b in design.buckets)) == shuffle
    size = -(-perm.size // 64) * 64 + 64
    rng = np.random.default_rng(3)
    lanes = [jnp.asarray(rng.normal(size=(b.num_entities, 3)), jnp.float32)
             for b in design.buckets]
    table = jnp.asarray(rng.normal(size=(design.num_entities, 3)),
                        jnp.float32)
    for eidx, w in zip(design.entity_index, lanes):
        table = table.at[jnp.asarray(eidx)].set(w, mode="drop")
    entity = held_entity(design, size)
    want = np.asarray(jnp.take(table, entity, axis=0, mode="clip"))

    got = np.asarray(spread_lanes(
        lanes, tuple(jnp.asarray(s) for s in starts),
        [b.mask for b in design.buckets], size))
    assert got.shape == (size, 3)
    real = np.arange(size) < perm.size
    of_entity = real & (entity < design.num_entities)
    assert (of_entity.sum() < perm.size) == (shuffle and pads > 0)
    np.testing.assert_array_equal(got[of_entity], want[of_entity])
    assert np.all(np.isfinite(got))
    assert not np.any(got[~real])


def round_trip_update(coord):
    """The factored update as it stood before gamma stayed in the lanes,
    jitted with the coordinate's update signature: every inner iteration
    gathers each bucket's warm start from the table, scatters the lanes'
    solutions back into it a bucket at a time, and gathers the B solve's
    gamma rows from it a held row at a time. The oracle of the update that
    replaced it."""
    re_solve = _make_solve(coord.config, batched=True)
    latent_solve = factored_mod._make_latent_solve(coord._latent_cfg)
    reg_weight = coord.config.reg_weight
    entity = held_entity(coord.design, coord._held.weights.shape[0])

    def update_all(params, full_offsets, entity_indices, lane_of_entity,
                   offsets_maps, buckets, held, row_features, row_entities):
        gamma, b = params.gamma, params.projection
        perm, starts = offsets_maps
        gathered = gather_held_offsets(full_offsets, perm)
        bucket_offsets = fill_offsets(gathered, starts,
                                      [bk.mask for bk in buckets])
        held_offsets = factored_mod._held_vector(gathered, held)
        lane_tapes = [[] for _ in buckets]
        projection_tape = []
        for _ in range(coord.factored.num_inner_iterations):
            for tape, eidx, bucket, offsets in zip(
                    lane_tapes, entity_indices, buckets, bucket_offsets):
                g0 = jnp.take(gamma, eidx, axis=0, mode="clip")
                result = re_solve(
                    g0, jnp.full((eidx.shape[0],), reg_weight, gamma.dtype),
                    factored_mod._einsum("erd,dk->erk", bucket.features, b),
                    bucket.labels, offsets, bucket.weights, bucket.mask)
                tape.append((result.reason, result.iterations,
                             final_grad_norm(result)))
                gamma = gamma.at[eidx].set(result.w, mode="drop")
            gamma_rows = jnp.take(gamma, entity, axis=0, mode="clip")
            latent_result = latent_solve(b, gamma_rows, held_offsets, held)
            b = latent_result.w.reshape(b.shape)
            projection_tape.append(
                factored_mod._projection_tracker(latent_result))
        new_params = FactoredParams(gamma=gamma, projection=b)
        scores = factored_mod._score_rows(new_params, row_features,
                                          row_entities)
        tracker = factored_mod.FactoredUpdateTracker(
            tuple(tuple(jnp.stack(f) for f in zip(*tape))
                  for tape in lane_tapes),
            *(jnp.stack(f) for f in zip(*projection_tape)))
        return new_params, tracker, scores

    return jax.jit(update_all)


def assert_updates_equal(coord, got, want):
    """Two (params, tracker, scores) of one update bit for bit: the table,
    B, every real lane's record of every inner iteration, the B solves'
    records and the rescored rows. A sharding pad lane's record is left
    out: no history reads it, and its warm start differs (the table's
    last row, clipped, against its own last solution)."""
    (params, tracker, scores), (want_params, want_tracker, want_scores) = (
        jax.device_get(got), jax.device_get(want))
    np.testing.assert_array_equal(params.gamma, want_params.gamma)
    np.testing.assert_array_equal(params.projection, want_params.projection)
    np.testing.assert_array_equal(scores, want_scores)
    valid = coord._valid_lanes
    assert not all(v.all() for v in valid)  # there are pad lanes to skip
    for lanes, want_lanes, v in zip(tracker.lanes, want_tracker.lanes,
                                    valid):
        for field, want_field in zip(lanes, want_lanes):
            np.testing.assert_array_equal(np.asarray(field)[:, v],
                                          np.asarray(want_field)[:, v])
    for name in ("projection_iterations", "projection_cg_iterations",
                 "projection_passes", "projection_reason",
                 "projection_grad_norm"):
        np.testing.assert_array_equal(getattr(tracker, name),
                                      getattr(want_tracker, name))


@pytest.mark.parametrize("shuffle", [False, True])
@pytest.mark.parametrize("fuse", [False, True])
def test_update_against_the_table_round_trip(fuse, shuffle):
    """Two updates of two inner iterations (the second from a table the
    first wrote), each once through the coordinate and once through the
    table round trip it replaced, unfused (the coordinate's own dispatch)
    and fused (inside an outer jit, its state as arguments, as the fused
    pass runs it), lanes as built and shuffled: bit-equal."""
    design = song_design(jnp.float64)
    if shuffle:
        design = shuffled_lanes(design, 7)
    coord, _ = song_coordinate(jnp.float64, 2, 2, 3, 0.0, design=design)
    oracle = copy.copy(coord)
    oracle._update_all = round_trip_update(coord)

    def update(c, params, offsets):
        if not fuse:
            return c.update_step(params, offsets)
        return jax.jit(
            lambda state, p, o: c.with_fused_state(state).update_step(p, o)
        )(c.fused_state(), params, offsets)

    offsets = jnp.asarray(other_scores())
    params = want_params = coord.initial_params()
    for _ in range(2):
        got, want = update(coord, params, offsets), update(
            oracle, want_params, offsets)
        assert_updates_equal(coord, got, want)
        params, want_params = got[0], want[0]
    assert np.any(np.asarray(params.gamma) != 0.0)


def test_fused_descent_against_the_table_round_trip():
    """The three-coordinate descent, fused, with the factored update as it
    is and as the table round trip: the same model and history, bit for
    bit."""
    cd, other = descent(True), descent(True)
    song = other.coordinates["per-song"]
    song._update_all = round_trip_update(song)
    model, history = cd.run(num_iterations=2)
    want_model, want_history = other.run(num_iterations=2)
    for name in ("fixed", "per-user"):
        np.testing.assert_array_equal(model.params[name],
                                      want_model.params[name])
    for leaf in ("gamma", "projection"):
        np.testing.assert_array_equal(
            getattr(model.params["per-song"], leaf),
            getattr(want_model.params["per-song"], leaf))
    for h, o in zip(history, want_history):
        assert (h.coordinate, h.objective, h.solver_iterations,
                h.convergence_histogram, h.inner_iterations) == (
            o.coordinate, o.objective, o.solver_iterations,
            o.convergence_histogram, o.inner_iterations)


def test_spread_and_table_write_counters_fire_once_a_traced_update():
    """``game.factored.gamma_spread_runs`` books the runs a traced update
    spreads (every slot of every bucket, every inner iteration) and
    ``game.factored.table_write.inverse_gather`` one a traced update; a
    second update of the same shapes traces nothing."""
    runs, writes = ("game.factored.gamma_spread_runs",
                    "game.factored.table_write.inverse_gather")
    assert obs.taxonomy.matches(runs) and obs.taxonomy.matches(writes)
    factored_mod._make_factored_update_cached.cache_clear()
    coord, _ = song_coordinate(jnp.float64, 2, 2, 3, 0.0)
    reg = obs.registry()
    before = reg.counter(runs).value, reg.counter(writes).value
    offsets = jnp.asarray(other_scores())
    params, _ = coord.update(coord.initial_params(), offsets)
    coord.update(params, offsets)
    slots = sum(b.mask.shape[1] for b in coord.design.buckets)
    assert reg.counter(runs).value - before[0] == 2 * slots
    assert reg.counter(writes).value - before[1] == 1


def test_counters_are_fed_once_an_update():
    names = ("game.factored.updates", "game.factored.inner_iterations",
             "game.factored.projection_passes",
             "game.factored.projection_rows",
             "game.factored.projection_cg_iterations")

    def read():
        counters = obs.registry().snapshot()["counters"]
        return {n: counters.get(n, 0) for n in names}

    before = read()
    _, history = descent(True).run(num_iterations=2)
    got = {n: v - before[n] for n, v in read().items()}
    solves = [it["projection"] for h in history
              if h.inner_iterations is not None
              for it in h.inner_iterations]
    assert got["game.factored.updates"] == 2
    assert got["game.factored.inner_iterations"] == 4 == len(solves)
    assert got["game.factored.projection_passes"] == sum(
        s["passes"] for s in solves)
    held = sum(int(np.count_nonzero(np.asarray(b.mask) > 0))
               for b in song_design(jnp.float64).buckets)
    assert {s["rows"] for s in solves} == {held}
    assert got["game.factored.projection_rows"] == held * sum(
        s["passes"] for s in solves)
    assert got["game.factored.projection_cg_iterations"] == sum(
        s["cg_iterations"] for s in solves) > 0


def test_projection_solve_refuses_an_optimizer_it_does_not_implement():
    with pytest.raises(ValueError, match="NEWTON"):
        song_coordinate(jnp.float64, 1, 2, 3, 0.0,
                        b_optimizer=OptimizerType.NEWTON)
    coord, _ = song_coordinate(jnp.float64, 1, 2, 3, 0.0,
                               b_optimizer=OptimizerType.LBFGS)
    _, summary = coord.update(coord.initial_params(),
                              jnp.asarray(other_scores()))
    solve = summary.inner_iterations[0]["projection"]
    assert solve["cg_iterations"] == 0 and solve["passes"] >= 3
