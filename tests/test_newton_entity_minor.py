"""The small-d algebra of a Newton solve batched over entities: the
unrolled Cholesky solve (``solvers.newton._small_cho_solve``) against
float64 numpy, alone and under ``vmap``; a lane whose matrix is not
positive definite, or whose rows are all padding, in the middle of a batch;
``GLMObjective.hessian_row_sum`` against ``hessian_full``; the batched
Newton coordinate against the plain reference (``tests/reference_game.py``)
on one bucket and on four; what ``game.solve_layout`` books; that the
compiled solve updates no slice in place; and how many operations the
unrolled solve lowers to at its bound."""

import contextlib
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_game as ref
from photon_ml_tpu import obs
from photon_ml_tpu.core.normalization import (
    NormalizationContext,
    no_normalization,
)
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
from photon_ml_tpu.game.coordinates import _make_solve
from photon_ml_tpu.models.training import OptimizerType
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.solvers.newton import (
    _UNROLLED_CHO_MAX_DIM,
    _newton_direction,
    _small_cho_solve,
    solves_elementwise,
)

BATCH = 131  # entities under vmap: not a multiple of the 128 lanes


def spd(rng, d, batch=None):
    """Well-conditioned SPD matrices and right-hand sides, float64."""
    lead = () if batch is None else (batch,)
    a = rng.normal(size=lead + (d, d))
    h = a @ np.swapaxes(a, -1, -2) + d * np.eye(d)
    return h, rng.normal(size=lead + (d,))


# float32 solves of these matrices (condition number under 10) against
# float64 numpy read at most 4e-6 of the answer's largest entry; a wrong
# entry of the factor reads 1e-2 and more
SOLVE_TOLERANCE = {"float32": 1e-4, "float64": 1e-11}


# d 3 and 5 end on a group of fewer than ``_CHO_GROUP`` entries. d 32
# compiles for half a minute a case on the CPU (11 k multiply-subtracts),
# so it runs op by op: the same arithmetic in the same order, in the
# precision the chip runs and once in float64
SOLVE_CASES = [
    (d, batched, dtype_name)
    for d in (1, 2, 3, 5, 16)
    for batched in (False, True)
    for dtype_name in ("float32", "float64")
] + [(32, False, "float32"), (32, True, "float32"), (32, True, "float64")]


@pytest.mark.parametrize(
    "d, batched, dtype_name", SOLVE_CASES,
    ids=[f"{d}-{'vmapped' if batched else 'alone'}-{dtype_name}"
         for d, batched, dtype_name in SOLVE_CASES])
def test_solve_matches_float64_numpy(rng, d, batched, dtype_name):
    h, b = spd(rng, d, BATCH if batched else None)
    want = np.linalg.solve(h, b[..., None])[..., 0]
    hj, bj = jnp.asarray(h, dtype_name), jnp.asarray(b, dtype_name)
    solve = jax.vmap(_small_cho_solve) if batched else _small_cho_solve
    eager = jax.disable_jit() if d > 16 else contextlib.nullcontext()
    with eager:
        got = jax.jit(solve)(hj, bj)
    assert got.dtype == jnp.dtype(dtype_name) and got.shape == b.shape
    gap = np.max(np.abs(np.asarray(got, np.float64) - want)) / np.max(
        np.abs(want))
    assert gap <= SOLVE_TOLERANCE[dtype_name], gap


@pytest.mark.parametrize("d", [2, 16])
def test_shift_is_added_to_the_diagonal(rng, d):
    h, b = spd(rng, d)
    got = jax.jit(_small_cho_solve)(jnp.asarray(h), jnp.asarray(b), 0.5)
    want = np.linalg.solve(h + 0.5 * np.eye(d), b)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-10)


def _bad_matrix(kind, rng, d):
    if kind == "singular":  # rank 1, l2 0: a pivot is exactly zero
        v = np.arange(1.0, d + 1.0)
        return np.outer(v, v)
    q, _ = np.linalg.qr(rng.normal(size=(d, d)))
    eig = np.linspace(1.0, 2.0, d)
    eig[1] = -1.0  # indefinite: the jitter cannot rescue it either
    return (q * eig) @ q.T


@pytest.mark.parametrize("d", [5, 16])
@pytest.mark.parametrize("kind", ["singular", "indefinite"])
def test_retry_fires_for_the_bad_lane_only(rng, kind, d):
    """A matrix that is not positive definite, in the middle of a batch:
    its lane alone takes the jittered solve; every neighbour's answer is,
    bit for bit, what it is in a batch of sound matrices."""
    bad = 65
    h, g = spd(rng, d, BATCH)
    sound_h = h.copy()
    h[bad] = _bad_matrix(kind, rng, d)
    direction = jax.jit(jax.vmap(_newton_direction))
    pack = jnp.asarray
    got = np.asarray(direction(pack(h), jnp.asarray(g)))
    sound = np.asarray(direction(pack(sound_h), jnp.asarray(g)))
    others = np.arange(BATCH) != bad
    np.testing.assert_array_equal(got[others], sound[others])
    np.testing.assert_allclose(
        got[others], np.linalg.solve(h[others], -g[others][..., None])[..., 0],
        rtol=1e-9)
    if kind == "singular":
        jitter = 1e-6 * (1.0 + np.trace(h[bad]) / d)
        want = np.linalg.solve(h[bad] + jitter * np.eye(d), -g[bad])
        assert np.all(np.isfinite(got[bad]))
        np.testing.assert_allclose(got[bad], want, rtol=1e-5)
    else:
        assert not np.all(np.isfinite(got[bad]))


def _newton_config(**over):
    kw = dict(
        shard="u", task=TaskType.LOGISTIC_REGRESSION,
        optimizer=OptimizerType.NEWTON, reg_weight=10.0, max_iters=2,
        tolerance=0.0, random_effect="userId",
    )
    kw.update(over)
    return CoordinateConfig(**kw)


def _bucket(rng, entities, depth, d, dtype):
    x = rng.normal(size=(entities, depth, d))
    y = (rng.uniform(size=(entities, depth)) < 0.5).astype(float)
    held = (rng.uniform(size=(entities, depth)) < 0.8).astype(float)
    held[:, 0] = 1.0
    cast = lambda a: jnp.asarray(a, dtype)
    return dict(
        w0=cast(0.1 * rng.normal(size=(entities, d))),
        lam=cast(np.full(entities, 10.0)),
        x=cast(x), y=cast(y), off=cast(np.zeros((entities, depth))),
        wt=cast(held), mask=cast(held),
    )


@pytest.mark.parametrize("dtype_name", ["float32", "float64"])
def test_all_padding_entity_leaves_its_neighbours_alone(rng, dtype_name):
    """An entity whose mask is all zero (H = l2 I, gradient l2 w0) in the
    middle of a bucket: its Newton step lands on zero, and no other lane's
    solution changes by a bit."""
    entities, depth, d, empty = BATCH, 5, 16, 64
    b = _bucket(rng, entities, depth, d, jnp.dtype(dtype_name))
    solve = _make_solve(_newton_config(), batched=True)
    args = lambda k: (k["w0"], k["lam"], k["x"], k["y"], k["off"], k["wt"],
                      k["mask"])
    full = solve(*args(b))
    hollow = dict(b)
    hollow["mask"] = b["mask"].at[empty].set(0.0)
    hollow["wt"] = b["wt"].at[empty].set(0.0)
    got = solve(*args(hollow))
    others = np.arange(entities) != empty
    np.testing.assert_array_equal(
        np.asarray(got.w)[others], np.asarray(full.w)[others])
    assert np.all(np.isfinite(np.asarray(got.w)))
    np.testing.assert_allclose(
        np.asarray(got.w)[empty], 0.0,
        atol=1e-6 if dtype_name == "float32" else 1e-14)
    assert int(np.asarray(got.iterations)[empty]) >= 1


@pytest.mark.parametrize("case", ["plain", "scaled", "no_l2"])
def test_hessian_row_sum_is_hessian_full(rng, case):
    n, d = 37, 5
    batch = LabeledBatch(
        features=jnp.asarray(rng.normal(size=(n, d))),
        labels=jnp.asarray((rng.uniform(size=n) < 0.5).astype(float)),
        offsets=jnp.asarray(0.1 * rng.normal(size=n)),
        weights=jnp.asarray(rng.uniform(0.5, 2.0, size=n)),
        mask=jnp.asarray((rng.uniform(size=n) < 0.9).astype(float)),
    )
    obj = GLMObjective(
        loss=loss_for_task(TaskType.LOGISTIC_REGRESSION),
        l2_weight=0.0 if case == "no_l2" else 3.0,
        normalization=NormalizationContext(
            factors=jnp.asarray(rng.uniform(0.5, 2.0, size=d)), shifts=None)
        if case == "scaled" else no_normalization(),
    )
    w = jnp.asarray(rng.normal(size=d))
    np.testing.assert_allclose(
        np.asarray(obj.hessian_row_sum(w, batch)),
        np.asarray(obj.hessian_full(w, batch)), rtol=1e-12, atol=1e-13)


# -- the batched coordinate against the plain reference -----------------

N_USERS, D_FIXED, D_USER = 48, 6, 16
CD_ITERATIONS, NEWTON_ITERATIONS = 2, 2
L2 = {"fixed": 1.0, "per-user": 10.0}


def _game_problem():
    """Seeded rows over users of 1 to 40 rows each, four of them without a
    row (they ride the buckets as padding lanes or are absent); a planted
    model on both coordinates."""
    rng = np.random.default_rng(20261031)
    counts = np.clip((40.0 * rng.uniform(size=N_USERS) ** 2).astype(int),
                     1, 40)
    counts[[5, 17, 29, 41]] = 0
    user = rng.permutation(np.repeat(np.arange(N_USERS), counts))
    n = user.size
    xg = rng.normal(size=(n, D_FIXED))
    xu = rng.normal(size=(n, D_USER))
    margin = xg @ rng.normal(size=D_FIXED) + np.sum(
        xu * (0.5 * rng.normal(size=(N_USERS, D_USER)))[user], axis=1)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(float)
    return xg, xu, user, y


def _descent(num_buckets, dtype, fuse=True, optimizer=OptimizerType.NEWTON):
    xg, xu, user, y = _game_problem()
    data = GameData.create(
        features={"global": xg, "per_user": xu}, labels=y,
        entity_ids={"userId": user},
    )
    n = y.size
    yj = jnp.asarray(y, dtype)
    zeros, ones = jnp.zeros((n,), dtype), jnp.ones((n,), dtype)
    common = dict(
        task=TaskType.LOGISTIC_REGRESSION, optimizer=optimizer,
        max_iters=NEWTON_ITERATIONS, tolerance=0.0,
    )
    design = build_bucketed_random_effect_design(
        data, "userId", "per_user", N_USERS, num_buckets=num_buckets,
        dtype=dtype,
    )
    assert design.num_buckets == num_buckets
    coords = {
        "fixed": FixedEffectCoordinate(
            LabeledBatch(features=jnp.asarray(xg, dtype), labels=yj,
                         offsets=zeros, weights=ones, mask=ones),
            CoordinateConfig(shard="global", reg_weight=L2["fixed"],
                             **common),
        ),
        "per-user": RandomEffectCoordinate(
            design=design, row_features=jnp.asarray(xu, dtype),
            row_entities=jnp.asarray(user, jnp.int32),
            full_offsets_base=zeros,
            config=CoordinateConfig(
                shard="per_user", reg_weight=L2["per-user"],
                random_effect="userId", **common),
        ),
    }
    return CoordinateDescent(
        coordinates=coords, labels=yj, base_offsets=zeros, weights=ones,
        task=TaskType.LOGISTIC_REGRESSION, fuse_passes=fuse,
    )


@functools.lru_cache(maxsize=None)
def _reference():
    xg, xu, user, y = _game_problem()
    problem = {"labels": y, "coordinates": [
        {"name": "fixed", "kind": "fixed", "x": xg, "l2": L2["fixed"]},
        {"name": "per-user", "kind": "random", "x": xu, "ids": user,
         "entities": N_USERS, "l2": L2["per-user"], "sample": {}},
    ]}
    return ref.block_coordinate_descent(
        problem, CD_ITERATIONS, NEWTON_ITERATIONS, jnp.dtype("float64"))


# float64: the same arithmetic up to the order of the sums. float32: the
# program reads at most 2.4e-7 on an objective and 1.1e-6 on a parameter
# set here; the limits of tests/test_game_multi_re.py, which bfloat16
# fails by two decades
TOLERANCE = {"float64": (1e-9, 1e-8), "float32": (2e-5, 6e-4)}


@pytest.mark.parametrize("dtype_name", ["float64", "float32"])
@pytest.mark.parametrize("num_buckets", [1, 4])
def test_batched_newton_coordinate_matches_plain_reference(
        num_buckets, dtype_name):
    cd = _descent(num_buckets, jnp.dtype(dtype_name))
    model, history = cd.run(num_iterations=CD_ITERATIONS)
    want_params, want_values = _reference()
    values = max(abs(h.objective - w) / abs(w)
                 for h, w in zip(history, want_values))
    params = max(
        float(np.linalg.norm(np.asarray(model.params[k], np.float64)
                             - np.asarray(want_params[k]))
              / np.linalg.norm(np.asarray(want_params[k])))
        for k in want_params)
    tol_values, tol_params = TOLERANCE[dtype_name]
    assert values <= tol_values and params <= tol_params, (values, params)
    # users without a row keep a zero row of the table
    np.testing.assert_array_equal(
        np.asarray(model.params["per-user"])[[5, 17, 29, 41]], 0.0)


# -- what the coordinate books and what the compiler is left with --------

def _layout_spans():
    return [s for s in obs.recent_spans() if s[0] == "game.solve_layout"]


@pytest.mark.parametrize("optimizer, layout", [
    (OptimizerType.NEWTON, "entity_minor"),
    (OptimizerType.TRON, "block_minor"),
    (OptimizerType.LBFGS, "block_minor"),
])
def test_solve_layout_is_booked_once_a_bucket_at_trace_time(
        optimizer, layout):
    from photon_ml_tpu.game import coordinates as coordinates_mod

    for cached in (coordinates_mod._make_solve_cached,
                   coordinates_mod._make_multi_bucket_update_cached):
        cached.cache_clear()
    counter = "game.solve_layout." + layout
    before = obs.registry().snapshot()["counters"].get(counter, 0)
    cd = _descent(4, jnp.dtype("float32"), optimizer=optimizer)
    cd.run(num_iterations=1)
    spans = _layout_spans()
    design = cd.coordinates["per-user"].design
    assert sorted((s[6]["depth"], s[6]["entities"]) for s in spans) == sorted(
        (b.rows_per_entity, b.num_entities) for b in design.buckets)
    for s in spans:
        assert s[6]["layout"] == layout and s[6]["dim"] == D_USER
        assert s[6]["optimizer"] == optimizer.name
    after = obs.registry().snapshot()["counters"].get(counter, 0)
    assert after - before == len(design.buckets) == 4
    # a second run traces nothing: no span, no count
    cd.run(num_iterations=1)
    assert len(_layout_spans()) == 4
    assert obs.registry().snapshot()["counters"].get(counter, 0) == after
    assert obs.taxonomy.matches("game.solve_layout")
    assert obs.taxonomy.matches(counter)


# lax's factorization in a lowered text: ``stablehlo.cholesky``, or
# LAPACK's ``potrf`` where the text is lowered for the CPU
_LAX_CHOLESKY = re.compile(r"cholesky|potrf", re.IGNORECASE)


def _lowered_solve_text(d, entities=BATCH, depth=6):
    spec = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    e, r = entities, depth
    solve = _make_solve(_newton_config(), batched=True)
    return solve.lower(
        spec(e, d), spec(e), spec(e, r, d), spec(e, r), spec(e, r),
        spec(e, r), spec(e, r),
    ).as_text()


def test_dimension_above_the_unrolled_bound_is_block_minor():
    """One past the bound the batched solve is lax's Cholesky on an
    (E, d, d) array, and no unrolled solve is in its text."""
    bound = _UNROLLED_CHO_MAX_DIM
    assert solves_elementwise(bound) and not solves_elementwise(bound + 1)
    text = _lowered_solve_text(bound + 1)
    assert _LAX_CHOLESKY.search(text)
    assert "_small_cho_solve" not in text


def test_unrolled_solve_at_the_bound_lowers_once_at_d_cubed_over_six():
    """What the unrolled solve costs to trace and compile is its count of
    operations, and two things keep that at one d^3 / 6 of multiplies (and
    as many subtractions) a bucket: the solve is jitted, so the plain and
    the jittered solve, every caller and every pass of ``while_loop``'s
    batching rule share ONE function in the lowered text; and the
    Hessian and the line search add a number that does not grow with d.
    A form that loses either reads two to eight times the count. (A
    stopwatch held this before: 5 s to compile at d 16 and 53 s at d 32
    on this sandbox's CPU, a bound that moved with the machine's load.)"""
    d = _UNROLLED_CHO_MAX_DIM
    text = _lowered_solve_text(d)
    assert len(re.findall(r"func\.func private @_small_cho_solve", text)) == 1
    assert not _LAX_CHOLESKY.search(text)
    # factor: (d - j)(j + 1) multiplies a column j; d (d + 1) / 2 each
    # substitution; one for the shift
    in_solve = d * (d + 1) * (d + 2) // 6 + d * (d + 1) + 1
    multiplies = text.count("stablehlo.multiply")
    assert in_solve <= multiplies <= in_solve + 100, (multiplies, in_solve)
    assert text.count("stablehlo.optimization_barrier") == 4 * -(-d // 4)


def _compiled_solve_text(d, entities=BATCH, depth=6):
    rng = np.random.default_rng(7)
    b = _bucket(rng, entities, depth, d, jnp.float32)
    solve = _make_solve(_newton_config(), batched=True)

    @jax.jit
    def scoped(*args):
        with jax.named_scope("re_newton_solve"):
            return solve(*args).w

    args = (b["w0"], b["lam"], b["x"], b["y"], b["off"], b["wt"], b["mask"])
    return scoped.lower(*args).compile().as_text()


def test_compiled_d16_solve_updates_no_slice_in_place():
    """No ``dynamic-update-slice`` and no ``scatter`` under the
    ``re_newton_solve`` scope of a compiled batched d-16 solve."""
    text = _compiled_solve_text(16)
    scoped = [line for line in text.splitlines()
              if "re_newton_solve" in line and " = " in line]
    assert len(scoped) > 100
    opcode = re.compile(r" = [^ ]+ ([a-z\-]+)\(")
    found = {m.group(1) for m in map(opcode.search, scoped) if m}
    assert "multiply" in found and "rsqrt" in found
    assert not found & {"dynamic-update-slice", "scatter"}, found
