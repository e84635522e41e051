"""The factored random effect (w_e = B gamma_e, ``game/factored.py``) against
the plain reference (``tests/reference_game.py``: per-entity Newton on
explicitly projected rows, then Newton on the MATERIALISED Kronecker design
x (x) gamma with vec(B) as its coefficients), on a bucketed design with an
entity past the active cap and sentinel lanes; its value, gradients and
Hessian-vector product at a random point; a three-coordinate descent fused
against unfused; and what its tracker says of every lane and of the B solve.
"""

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
from photon_ml_tpu.game.data import gather_offsets_compact
from photon_ml_tpu.game.factored import (
    FactoredConfig,
    FactoredParams,
    FactoredRandomEffectCoordinate,
)
from photon_ml_tpu.models.training import OptimizerType
from photon_ml_tpu.ops import metrics as metrics_mod
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.solvers.common import ConvergenceReason

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
                    b_optimizer=OptimizerType.TRON):
    """(the factored per-song coordinate, its reference description)."""
    x, ids, _ = ratings()
    design = song_design(dtype)
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
                reg_weight=L2_PROJECTION, max_iters=b_iters, **common),
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
# each has one minimiser and two converged solvers meet there.  The program
# stops on its relative function-value test (|df| <= tol f0: at 1e-15 in
# float64, 1e-7 in float32), which leaves a B about sqrt(tol) from the
# minimiser.  Read against the float64 reference: the float64 program 3.1e-10
# (gamma) and 1.0e-9 (B); the float32 program 1.1e-7 and 1.7e-5; the
# reference itself in bfloat16 1.9e-2 and 4.2e-2.  The float32 limit sits
# twelve times above its reading and two decades below bfloat16's.
TOLERANCE = {"float64": 1e-8, "float32": 2e-4}
SOLVER_TOLERANCE = {"float64": 1e-15, "float32": 1e-7}


@pytest.mark.parametrize("dtype_name", ["float64", "float32"])
def test_converged_update_matches_the_kronecker_reference(dtype_name):
    dtype = jnp.dtype(dtype_name)
    coord, _ = song_coordinate(dtype, 1, 60, 200,
                               SOLVER_TOLERANCE[dtype_name])
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
    assert summary.inner_iterations[0]["projection"]["reason"] != (
        ConvergenceReason.MAX_ITERATIONS.name)


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
    design = coord.design
    gammas = tuple(
        jnp.take(jnp.asarray(point["gamma"]), jnp.asarray(ei), axis=0,
                 mode="clip")
        for ei in design.entity_index)
    bucket_offsets = gather_offsets_compact(
        jnp.asarray(offsets), coord._offsets_maps,
        [b.mask for b in design.buckets])
    value_and_grad, hvp = factored_mod._latent_objective(
        loss_for_task(TaskType.LOGISTIC_REGRESSION), L2_PROJECTION,
        point["projection"].shape, gammas, bucket_offsets, design.buckets)
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
        start.projection,
        tuple(jnp.take(params.gamma, jnp.asarray(ei), axis=0, mode="clip")
              for ei in design.entity_index),
        tuple(gather_offsets_compact(
            offsets, coord._offsets_maps, [b.mask for b in design.buckets])),
        tuple(design.buckets),
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


def test_counters_are_fed_once_an_update():
    names = ("game.factored.updates", "game.factored.inner_iterations",
             "game.factored.projection_passes",
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
