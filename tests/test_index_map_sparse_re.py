"""The ragged INDEX_MAP random effect over a sparse shard
(``game.data.build_index_map_design``, ``game.projectors.RaggedIndexMap``,
``game.projected.IndexMapRandomEffectCoordinate``): a lane's columns are the
union of its ACTIVE rows' columns, a bucket's width its widest lane's union
rounded up to 128; the fused descent against the benchmark's plain
reference (``chipbench/reference_sparse_user.py``); TRON's fixed work a
lane; the save / load / score round trip in per-entity lists."""

import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.core.tasks import TaskType
from photon_ml_tpu.game import (
    CoordinateConfig,
    CoordinateDescent,
    GameData,
    IndexMapRandomEffectCoordinate,
    build_index_map_design,
)
from photon_ml_tpu.models.training import OptimizerType
from photon_ml_tpu.ops.sparse import from_coo

D = 5000


def _bag(seed, n=600, users=9, pool=40, nnz=4, skew=True):
    """Rows of ``users`` users (row counts skewed when ``skew``), each row
    ``nnz`` columns from its user's private pool of the D-wide space."""
    rng = np.random.default_rng(seed)
    p = np.arange(1, users + 1, dtype=np.float64) ** (-1.0 if skew else 0.0)
    user = rng.choice(users, size=n, p=p / p.sum()).astype(np.int32)
    pools = rng.choice(D, size=(users, pool), replace=False)
    rows = np.repeat(np.arange(n), nnz)
    cols = pools[user.repeat(nnz), rng.integers(0, pool, n * nnz)]
    vals = rng.normal(size=n * nnz)
    sf = from_coo(rows, cols, vals, n, D)
    y = (rng.uniform(size=n) < 0.5).astype(np.float64)
    return GameData.create(features={"bag": sf}, labels=y,
                           entity_ids={"userId": user}), sf, user, y


def _config(optimizer=OptimizerType.TRON, **kw):
    base = dict(max_iters=3, tolerance=0.0, tron_max_cg=4)
    base.update(kw)
    return CoordinateConfig(
        shard="bag", task=TaskType.LOGISTIC_REGRESSION, optimizer=optimizer,
        reg_weight=2.0, random_effect="userId", **base)


def _descent(coords, y, fuse_passes=True):
    n = y.shape[0]
    return CoordinateDescent(
        coordinates=coords, labels=jnp.asarray(y),
        base_offsets=jnp.zeros((n,)), weights=jnp.ones((n,)),
        task=TaskType.LOGISTIC_REGRESSION, fuse_passes=fuse_passes)


def test_bucket_widths_are_their_lanes_largest_active_union():
    data, sf, user, _ = _bag(0)
    cap = 40
    design = build_index_map_design(data, "userId", "bag", 9, num_buckets=3,
                                    active_cap=cap, dtype=jnp.float64)
    imap = design.index_map
    ind = np.asarray(sf.indices)
    assert len(imap.widths) == design.num_buckets == 3
    base = 0
    for bucket, lanes, width in zip(design.buckets, design.entity_index,
                                    imap.widths):
        rows = np.asarray(bucket.row_index)
        unions = []
        for lane, e in enumerate(lanes):
            active = rows[lane][rows[lane] >= 0]
            union = np.unique(ind[active][ind[active] < D])
            slots = slice(base + lane * width, base + (lane + 1) * width)
            held = imap.columns[slots]
            # the lane's columns are its active rows' union, ascending
            np.testing.assert_array_equal(held[held >= 0], union)
            assert np.all(imap.entities[slots][held >= 0] == e)
            unions.append(union.size)
        assert width % 128 == 0
        assert width - 128 < max(unions) <= width
        base += lanes.size * width
    assert base == imap.size
    # a capped user has passive rows, and a column only they store is in
    # no union
    counts = np.bincount(user, minlength=9)
    assert counts.max() > cap
    e = int(np.argmax(counts))
    lane_cols = imap.columns[(imap.entities == e) & (imap.columns >= 0)]
    held_rows = np.concatenate([np.asarray(b.row_index)[
        np.asarray(ei) == e].ravel() for b, ei in zip(
            design.buckets, design.entity_index)])
    held_rows = held_rows[held_rows >= 0]
    passive = np.setdiff1d(np.flatnonzero(user == e), held_rows)
    only_passive = np.setdiff1d(ind[passive], ind[held_rows])
    only_passive = only_passive[only_passive < D]
    assert only_passive.size and not np.isin(only_passive, lane_cols).any()


def test_fused_descent_matches_the_plain_reference():
    """Fixed effect + the INDEX_MAP random effect through the fused pass;
    the benchmark's plain reference (a sort-join of the rows' triples with
    the fetched lists, segment sums over pairs) reads the program's
    objective and a gradient left near zero at the fetched model; the
    unfused loop gives the same model."""
    from chipbench import reference_sparse_user as ref
    from photon_ml_tpu.core.types import LabeledBatch
    from photon_ml_tpu.game import FixedEffectCoordinate

    data, sf, user, y = _bag(1)
    n = y.shape[0]
    xg = np.random.default_rng(11).normal(size=(n, 5))
    cfg = _config(max_iters=10, tron_max_cg=10)

    def build():
        return {
            "fixed": FixedEffectCoordinate(
                LabeledBatch(features=jnp.asarray(xg), labels=jnp.asarray(y),
                             offsets=jnp.zeros(n), weights=jnp.ones(n),
                             mask=jnp.ones(n)),
                CoordinateConfig(shard="g", optimizer=OptimizerType.NEWTON,
                                 reg_weight=1.0, max_iters=3,
                                 tolerance=0.0)),
            "user": IndexMapRandomEffectCoordinate.from_sparse_shard(
                data, "userId", "bag", 9, cfg, num_buckets=2,
                active_cap=60, dtype=jnp.float64),
        }

    coords = build()
    model, history = _descent(coords, y).run(num_iterations=6)
    unfused, _ = _descent(build(), y, fuse_passes=False).run(
        num_iterations=6)
    for k in model.params:
        np.testing.assert_allclose(np.asarray(unfused.params[k]),
                                   np.asarray(model.params[k]), atol=1e-10)

    imap = coords["user"].design.index_map
    ents, cols, vals = imap.lists(np.asarray(model.params["user"]))
    # train weight of a row: 1 under the cap, count / cap sampled, 0 passive
    weight = np.zeros(n)
    for b in coords["user"].design.buckets:
        rows = np.asarray(b.row_index)
        weight[rows[rows >= 0]] = np.asarray(b.weights)[rows >= 0]
    columns = np.asarray(sf.indices).T
    values = np.asarray(sf.values).T
    joined = ref.join(user, columns, values, weight, ents, cols, D)
    assert joined["union_missing"] == 0

    def parts(w, coef):
        return [
            {"kind": "fixed", "x": jnp.asarray(xg), "params": w, "l2": 1.0},
            {"kind": "sparse", "entry_pair": jnp.asarray(
                joined["entry_pair"]), "values": jnp.asarray(values),
             "train_weight": jnp.asarray(weight), "params": coef,
             "l2": 2.0},
        ]

    value, grads, _ = ref.value_grads(
        parts(np.asarray(model.params["fixed"]),
              ref.coefficients(joined, vals)), jnp.asarray(y))
    _, grads0, _ = ref.value_grads(
        parts(np.zeros(5), np.zeros(joined["pairs"].size)), jnp.asarray(y))
    assert abs(history[-1].objective - float(value)) <= 1e-5 * abs(
        float(value))
    for g, g0 in zip(grads, grads0):
        assert float(jnp.linalg.norm(g) / jnp.linalg.norm(g0)) < 1e-3


def test_tron_passes_are_the_same_on_two_seeds():
    """Under tolerance 0 the budget is the rule: every bucket's batched
    solve runs max_iters outer iterations of max_cg Hessian-vector
    products and one value/gradient each, plus the first, on any data of
    the same shapes; ``game.sparse_re.passes`` books them."""
    got = []
    for seed in (2, 3):
        data, _, _, y = _bag(seed, skew=False)
        coord = IndexMapRandomEffectCoordinate.from_sparse_shard(
            data, "userId", "bag", 9, _config(), num_buckets=2,
            dtype=jnp.float64)
        before = obs.registry().counter("game.sparse_re.passes").value
        _, history = _descent({"user": coord}, y).run(num_iterations=2)
        passes = [h.inner_iterations[0]["sparse_re"]["passes"]
                  for h in history]
        assert obs.registry().counter("game.sparse_re.passes").value - before == (
            sum(map(sum, passes)))
        got.append(passes)
    assert got[0] == got[1] == [[3 + 1 + 3 * 4] * 2] * 2


def test_newton_is_refused():
    data, _, _, _ = _bag(4)
    with pytest.raises(ValueError, match="NEWTON"):
        IndexMapRandomEffectCoordinate.from_sparse_shard(
            data, "userId", "bag", 9, _config(OptimizerType.NEWTON))


def test_save_load_score_round_trip_builds_no_entity_by_width_table(
        tmp_path, monkeypatch):
    """Per-entity lists saved as ``coefficientLayout=entity-sparse``,
    loaded back as lists and scored: nothing of (entities, d) is made on
    the way (``np.zeros`` / ``np.full`` of that size raise here)."""
    from photon_ml_tpu.game.scoring import CompactReTable, score_game_data
    from photon_ml_tpu.io.models import load_game_model, save_game_model
    from photon_ml_tpu.io.vocab import FeatureVocabulary

    data, _, _, y = _bag(5)
    coord = IndexMapRandomEffectCoordinate.from_sparse_shard(
        data, "userId", "bag", 9, _config(), num_buckets=2,
        dtype=jnp.float64)
    model, _ = _descent({"user": coord}, y).run(num_iterations=1)
    lists = coord.back_project(model.params["user"])
    want = np.asarray(coord.score(model.params["user"]))
    vocab_path = tmp_path / "bag.txt"
    vocab_path.write_text("".join(f"c{j}\x01\n" for j in range(D)))
    vocab = FeatureVocabulary.load(str(vocab_path))
    evocab = {f"u{e}": e for e in range(9)}

    too_big = 9 * D
    for name in ("zeros", "full"):
        real = getattr(np, name)

        def guarded(shape, *a, _real=real, **k):
            if int(np.prod(shape)) >= too_big:
                raise AssertionError(f"np.{_real.__name__}{shape!r}")
            return _real(shape, *a, **k)

        monkeypatch.setattr(np, name, guarded)
    save_game_model(str(tmp_path / "m"), {"user": lists}, {"user": "bag"},
                    {"user": vocab}, {"user": evocab}, {"user": "userId"})
    params, _, _, evocabs = load_game_model(
        str(tmp_path / "m"), {"user": vocab}, {"user": evocab})
    back = params["user"]
    assert isinstance(back, CompactReTable)
    assert evocabs["user"] == evocab
    keep = np.asarray(lists.values) != 0
    np.testing.assert_array_equal(np.asarray(back.columns)[keep],
                                  np.asarray(lists.columns)[keep])
    np.testing.assert_allclose(np.asarray(back.values)[keep],
                               np.asarray(lists.values)[keep], rtol=1e-15)
    got = np.asarray(score_game_data(
        {"user": back}, {"user": "bag"}, {"user": "userId"}, data))
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-12)


@pytest.mark.parametrize("min_support, ratio", [(2, None), (0, 0.5),
                                                (2, 0.3)])
def test_filters_keep_the_dense_builders_columns(min_support, ratio):
    """The support and Pearson filters of the compact builder keep, lane
    by lane, the columns the dense builder's filters leave nonzero on the
    densified shard (``filter_features_by_support``,
    ``select_features_by_pearson``)."""
    from photon_ml_tpu.game import build_bucketed_random_effect_design
    from photon_ml_tpu.ops.sparse import to_dense

    data, sf, user, y = _bag(7, n=300, users=6, pool=12, nnz=3)
    common = dict(num_buckets=2, active_cap=50, dtype=jnp.float64,
                  min_support=min_support, feature_ratio=ratio)
    design = build_index_map_design(data, "userId", "bag", 6, **common)
    dense = build_bucketed_random_effect_design(
        GameData.create(features={"bag": to_dense(sf)}, labels=y,
                        entity_ids={"userId": user}),
        "userId", "bag", 6, **common)
    imap = design.index_map
    for bucket, lanes in zip(dense.buckets, dense.entity_index):
        feats = np.asarray(bucket.features)
        for lane, e in enumerate(lanes):
            kept = np.flatnonzero(np.any(feats[lane] != 0, axis=0))
            mine = imap.columns[(imap.entities == e) & (imap.columns >= 0)]
            np.testing.assert_array_equal(np.sort(mine), kept)


def _lane_case(width, dtype, lanes=3, slots=8, rows=70, seed=None):
    """A vmapped batch of lanes: local ids, values, the lanes' vectors over
    a wide span of magnitudes (float32 1e-30..1e30, so the low parts carry
    bits; float64 1e-20..1e20, where all seven parts are normal bfloat16)
    and per-row weights."""
    rng = np.random.default_rng(width if seed is None else seed)
    span = 30 if dtype == np.float32 else 20
    cols = rng.integers(0, width, (lanes, slots, rows)).astype(np.int32)
    vals = rng.normal(size=(lanes, slots, rows)).astype(dtype)
    w = (rng.choice([-1.0, 1.0], (lanes, width))
         * 10.0 ** rng.uniform(-span, span, (lanes, width))).astype(dtype)
    a = rng.normal(size=(lanes, rows)).astype(dtype)
    return cols, vals, w, a


def _lane_products(cols, vals, w, a):
    import jax

    from photon_ml_tpu.game import coordinates as C

    width = w.shape[1]
    cols, vals, w, a = map(jnp.asarray, (cols, vals, w, a))
    pick = jax.jit(jax.vmap(C._lane_pick))(w, cols)
    z = jax.jit(jax.vmap(C._lane_matvec))(w, cols, vals)
    g = jax.jit(jax.vmap(lambda a, c, v: C._lane_rmatvec(a, c, v, width)))(
        a, cols, vals)
    return np.asarray(pick), np.asarray(z), np.asarray(g)


def _assert_lane_products(cols, vals, w, a, pick, z, g, tol):
    """Picks equal the gather to the bit; the margins and the transpose
    (against float64 ``np.add.at``) within ``tol`` of the magnitudes they
    sum, and in float64 within 1e-12 of each value as well."""
    for e in range(cols.shape[0]):
        np.testing.assert_array_equal(pick[e], w[e][cols[e]])
        terms = vals[e].astype(np.float64) * w[e][cols[e]]
        assert np.all(np.abs(z[e] - terms.sum(0))
                      <= tol * np.abs(terms).sum(0))
        terms = vals[e].astype(np.float64) * a[e][None, :].astype(np.float64)
        want, scale = np.zeros(w.shape[1]), np.zeros(w.shape[1])
        np.add.at(want, cols[e].ravel(), terms.ravel())
        np.add.at(scale, cols[e].ravel(), np.abs(terms).ravel())
        assert np.all(np.abs(g[e] - want) <= tol * scale)
        if w.dtype == np.float64:
            np.testing.assert_allclose(
                z[e], (vals[e] * w[e][cols[e]]).sum(0), rtol=1e-12)
            np.testing.assert_allclose(g[e], want, rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("width, dtype, tol", [
    pytest.param(width, dtype, tol, id=f"{width}{suffix}")
    for dtype, tol, suffix in [(np.float64, 1e-12, ""),
                               (np.float32, 1e-6, "-float32")]
    for width in (128, 384, 2688, 6016)])
def test_lane_products_are_the_gather_and_the_segment_sum(width, dtype, tol):
    """The lanes' one-hot contractions at widths of one and of many
    blocks of 128, and past one 128-wide tile of stacked parts (6,016 =
    47 blocks, 141 rows in float32): the margins are the gather of the
    lane's vector at the rows' local ids (each pick to the bit), the
    transpose the segment sum by local id."""
    cols, vals, w, a = _lane_case(width, dtype)
    _assert_lane_products(cols, vals, w, a, *_lane_products(cols, vals, w, a),
                          tol)


@pytest.mark.parametrize("width", [384, 2688, 6016])
def test_lane_products_run_one_bfloat16_pass(width):
    """A bucket's value/gradient and Hessian-vector product at float32
    lower with no HIGHEST-precision dot (HIGHEST would split each float32
    operand into three bfloat16 passes a side), and the bucket books
    ceil(3 x blocks / 128) MXU tiles in ``game.sparse_re.product_tiles``."""
    import jax

    from photon_ml_tpu.core.tasks import TaskType
    from photon_ml_tpu.game import coordinates as C
    from photon_ml_tpu.ops.losses import loss_for_task

    name = "game.sparse_re.product_tiles"
    assert obs.taxonomy.matches(name)
    cols, vals, w, a = _lane_case(width, np.float32, lanes=2)
    rows = a.shape[1]
    f32 = np.float32
    labels, offsets, weights = (np.zeros((2, rows), f32),) * 3
    reg = obs.registry()
    before = reg.counter(name).value

    def both(w, v, cols, vals, labels, offsets, weights):
        def one(w, v, cols, vals, labels, offsets, weights):
            _, _, hvp_at, vgc = C._compact_lane_objective(
                loss_for_task(TaskType.LOGISTIC_REGRESSION), f32(1.0), cols,
                vals, labels, offsets, weights, width)
            value, grad, curvature = vgc(w)
            return value, grad, hvp_at(curvature, v)

        return jax.vmap(one)(w, v, cols, vals, labels, offsets, weights)

    text = jax.jit(both).lower(w, w, cols, vals, labels, offsets,
                               weights).as_text()
    dots = [line for line in text.splitlines() if "dot_general" in line]
    assert len(dots) >= 4  # the margins twice, the transpose twice
    assert not [line for line in dots if "HIGHEST" in line]
    assert reg.counter(name).value - before == -(-3 * (width // 128) // 128)


# The cell's four buckets (game_music_sparse_user.cd): (slots, depth,
# width) at 16 lanes each, where the cell runs 8,806 / 3,768 / 1,799 /
# 2,011.
_CELL_BUCKETS = [(8, 70, 384), (8, 204, 768), (8, 497, 1408),
                 (8, 1024, 2688)]


@pytest.mark.parametrize("slots, depth, width", _CELL_BUCKETS)
def test_lane_products_on_the_chip(slots, depth, width):
    """The one-hot products as the chip compiles them, at the cell's
    bucket shapes, against the gather and the segment sum: a form of a
    one-hot contraction that is exact on the CPU read 0.85 of the margins'
    size wrong on TPU v5e (PERF.md section 6), so a jax upgrade that
    changes how these compile shows here. Runs where the default backend
    is a TPU (``PHOTON_TEST_PLATFORMS=tpu,cpu``; conftest pins the CPU
    otherwise)."""
    import jax

    if jax.default_backend() != "tpu":
        pytest.skip("needs a TPU backend")
    cols, vals, w, a = _lane_case(width, np.float32, lanes=16, slots=slots,
                                  rows=depth)
    _assert_lane_products(cols, vals, w, a, *_lane_products(cols, vals, w, a),
                          1e-6)
