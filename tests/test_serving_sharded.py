"""Entity-sharded serving + tiered entity cache drills (docs/SERVING.md).

The contracts under test:

- routing: every request gets EXACTLY one primary placement (fixed
  effect applied once), one placement per additional owner shard, and
  the merge reassembles per-request scores deterministically.
- sharded engine: scores == the unsharded engine == offline
  ``score_game_data`` to 1e-10 at widths 2/4/8, including cold-start
  entities and requests whose entities span shards; the compiled
  per-bucket executable contains ZERO collective instructions; mixed
  routed traffic after warmup never recompiles; the per-process
  resident RE footprint drops ~P x at P shards.
- sharded checkpoints: an engine stood up straight from a PR-11
  sharded checkpoint step — at a DIFFERENT shard count than the
  writer's — scores == offline to 1e-10, streaming one checkpoint
  shard file at a time.
- tiered cache: a miss scores fixed-effect-only (== the degraded
  executable == cold-start, to 1e-10) and NEVER stalls the batch;
  promotion/demotion under a fixed request trace is deterministic;
  promotions never recompile.
- faults: a single-shard ``serving.shard_route`` fault degrades that
  shard's entities to fixed-effect-only with zero lost requests; a
  ``serving.cache_tier`` fault leaves entities cold, never corrupt.
- hot-reload: a sharded registry swap under concurrent load drops
  nothing and retires the old shard set + cache workers atomically.
"""

import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.game.data import GameData, entity_shard_assignment
from photon_ml_tpu.game.factored import FactoredParams
from photon_ml_tpu.game.scoring import (
    CompactReTable,
    _compact_table,
    compact_table_rows,
    score_game_data,
    shard_compact_table,
)
from photon_ml_tpu.obs.xla_cost import count_collectives
from photon_ml_tpu.resilience.faults import FaultSpec, inject
from photon_ml_tpu.serving import (
    MicroBatcher,
    ModelRegistry,
    ScoreRequest,
    ScoringEngine,
    ShardedScoringEngine,
    TieredEntityCache,
    load_sharded_re_table,
    route_batch,
    xla_compile_events,
)

pytestmark = pytest.mark.serving


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------


def _model(rng, n_users=23, n_items=17, d_g=5, d_u=4, d_i=3, latent_k=2):
    """Two RE keys (userId, itemId) so requests can SPAN shards, plus a
    factored coordinate sharing the user key."""
    params = {
        "global": rng.normal(size=d_g),
        "per-user": rng.normal(size=(n_users, d_u))
        * (rng.uniform(size=(n_users, d_u)) < 0.5),
        "per-item": rng.normal(size=(n_items, d_i)),
        "fact": FactoredParams(
            gamma=jnp.asarray(rng.normal(size=(n_users, latent_k))),
            projection=jnp.asarray(rng.normal(size=(d_u, latent_k))),
        ),
    }
    shards = {"global": "g", "per-user": "u", "per-item": "i", "fact": "u"}
    res = {
        "global": None,
        "per-user": "userId",
        "per-item": "itemId",
        "fact": "userId",
    }
    return params, shards, res


def _batch(rng, n, n_users=23, n_items=17, d_g=5, d_u=4, d_i=3,
           cold_every=5):
    feats = {
        "g": rng.normal(size=(n, d_g)),
        "u": rng.normal(size=(n, d_u)),
        "i": rng.normal(size=(n, d_i)),
    }
    users = rng.integers(0, n_users, size=n).astype(np.int32)
    items = rng.integers(0, n_items, size=n).astype(np.int32)
    users[::cold_every] = -1
    items[1::cold_every] = -1
    return feats, {"userId": users, "itemId": items}


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------


class TestRouting:
    def test_primary_exactly_once_and_owners_covered(self, rng):
        n_users, n_items, P = 23, 17, 4
        assignments = {
            "userId": entity_shard_assignment(n_users, P),
            "itemId": entity_shard_assignment(n_items, P),
        }
        _, ents = _batch(rng, 64)
        plan = route_batch(ents, assignments, 64, P)
        # each row's fixed effect applies exactly once
        fixed_rows = plan.p_row[plan.fixed_mask > 0]
        assert sorted(fixed_rows.tolist()) == list(range(64))
        # each known entity is gathered on exactly its owner shard
        for rk, a in assignments.items():
            e = ents[rk]
            for i in range(64):
                if e[i] < 0:
                    continue
                owner = int(a.owner_of_global(np.asarray([e[i]]))[0])
                sel = (plan.p_row == i) & (plan.p_shard == owner)
                assert sel.sum() == 1
                local = plan.ents[rk][sel][0]
                assert local == int(
                    a.local_of_global(np.asarray([e[i]]))[0]
                )

    def test_merge_sums_partials_per_request(self, rng):
        P = 4
        assignments = {"userId": entity_shard_assignment(10, P)}
        ents = {"userId": np.asarray([0, 1, 2, 3, -1], np.int32)}
        plan = route_batch(ents, assignments, 5, P)
        partials = np.zeros((P, plan.bucket))
        partials[plan.p_shard, plan.p_slot] = 1.0
        merged = plan.merge(partials)
        # one placement per row here (single RE key): merge == 1 each
        np.testing.assert_allclose(merged, np.ones(5))

    def test_bucket_is_power_of_two(self, rng):
        assignments = {"userId": entity_shard_assignment(23, 4)}
        for n in (1, 3, 17, 64, 100):
            plan = route_batch(
                {"userId": np.zeros(n, np.int32)}, assignments, n, 4
            )
            assert plan.bucket & (plan.bucket - 1) == 0


# ---------------------------------------------------------------------------
# sharded-vs-unsharded equivalence
# ---------------------------------------------------------------------------


class TestShardedEquivalence:
    @pytest.mark.parametrize(
        "num_shards",
        # tier-1 keeps the four-chip host's width; 2 and 8 are the same
        # assertions at other widths (slow tier, ROADMAP D10)
        [
            pytest.param(2, marks=pytest.mark.slow),
            4,
            pytest.param(8, marks=pytest.mark.slow),
        ],
    )
    def test_matches_unsharded_and_offline(self, rng, devices, num_shards):
        params, shards, res = _model(rng)
        feats, ents = _batch(rng, 37)
        base = ScoringEngine(params, shards, res)
        ref = base.score_arrays(feats, ents)
        data = GameData.create(
            feats, np.zeros(37), entity_ids=ents
        )
        offline = np.asarray(score_game_data(params, shards, res, data))
        np.testing.assert_allclose(ref, offline, atol=1e-10)
        eng = ShardedScoringEngine(
            params, shards, res, num_shards=num_shards
        )
        got = eng.score_arrays(feats, ents)
        np.testing.assert_allclose(got, ref, atol=1e-10)
        # offsets apply once per request, not once per placement
        offs = rng.normal(size=37)
        np.testing.assert_allclose(
            eng.score_arrays(feats, ents, offs), ref + offs, atol=1e-10
        )

    def test_cold_start_rows_score_fixed_only(self, rng, devices):
        params, shards, res = _model(rng)
        feats, ents = _batch(rng, 16)
        all_cold = {
            k: np.full_like(v, -1) for k, v in ents.items()
        }
        eng = ShardedScoringEngine(params, shards, res, num_shards=4)
        base = ScoringEngine(params, shards, res)
        np.testing.assert_allclose(
            eng.score_arrays(feats, all_cold),
            base.score_arrays(feats, all_cold, fixed_only=True),
            atol=1e-10,
        )

    def test_zero_collectives_in_compiled_scorer(self, rng, devices):
        params, shards, res = _model(rng)
        eng = ShardedScoringEngine(params, shards, res, num_shards=4)
        eng.warmup(max_batch=16)
        compiled = eng._compiled[8]
        assert count_collectives(compiled.as_text()) == {}, (
            "the per-shard gather+dot must not cross shards"
        )

    def test_zero_steady_state_recompiles(self, rng, devices):
        params, shards, res = _model(rng)
        eng = ShardedScoringEngine(params, shards, res, num_shards=4)
        eng.warmup(max_batch=64)
        warm_compiles = eng.compile_count
        before = xla_compile_events()
        for n in (1, 3, 7, 8, 15, 16, 33, 64, 5, 40, 2, 63):
            feats, ents = _batch(rng, n, cold_every=3)
            eng.score_arrays(feats, ents)
        assert eng.compile_count == warm_compiles
        assert xla_compile_events() - before == 0

    def test_resident_bytes_drop_with_shards(self, rng, devices):
        params, shards, res = _model(rng, n_users=64, n_items=64)
        gauge = "serving.shard.resident_re_bytes_per_process"

        def resident(engine):
            return engine.stats.registry.gauge(gauge).value

        full = resident(ScoringEngine(params, shards, res))
        assert full > 0
        prev = full
        for P in (2, 4, 8):
            cur = resident(
                ShardedScoringEngine(params, shards, res, num_shards=P)
            )
            # ~P x drop overall (padding allows slack); monotone in P
            assert cur < prev
            assert cur <= full / P * 1.5
        # at 8 shards of 64 entities the slice is an honest eighth
        assert cur <= full / 8 * 1.5

    def test_shard_presort_groups_batch(self, rng, devices):
        from photon_ml_tpu.io.vocab import FeatureVocabulary, feature_key

        n_users, d_u = 16, 3
        params = {
            "global": rng.normal(size=2),
            "per-user": rng.normal(size=(n_users, d_u)),
        }
        kw = dict(
            shards={"global": "g", "per-user": "u"},
            random_effects={"global": None, "per-user": "userId"},
            shard_vocabs={
                "g": FeatureVocabulary([feature_key("g0", ""),
                                        feature_key("g1", "")]),
                "u": FeatureVocabulary(
                    [feature_key(f"u{j}", "") for j in range(d_u)]
                ),
            },
            re_vocabs={"userId": {f"user{i}": i for i in range(n_users)}},
        )
        eng = ShardedScoringEngine(params, num_shards=4, **kw)
        reqs = [
            ScoreRequest(
                features={"u0": 1.0}, entities={"userId": f"user{i}"}
            )
            for i in (7, 0, 13, 2, 9, 4)
        ]
        keys = eng.shard_presort_key(reqs)
        a = eng.assignments["userId"]
        expected = [
            int(a.owner_of_global(np.asarray([i]))[0])
            for i in (7, 0, 13, 2, 9, 4)
        ]
        assert keys.tolist() == expected
        # the batcher applies the grouping AND keeps futures aligned
        seen_orders = []

        def score_fn(requests):
            seen_orders.append(
                [r.entities["userId"] for r in requests]
            )
            return eng.score(requests)

        batcher = MicroBatcher(
            score_fn, max_batch=len(reqs), max_wait_ms=20.0,
            presort_fn=eng.shard_presort_key,
        )
        try:
            futs = [batcher.submit(r) for r in reqs]
            direct = {
                r.entities["userId"]: eng.score([r])[0] for r in reqs
            }
            for r, f in zip(reqs, futs):
                assert abs(
                    f.result(timeout=30) - direct[r.entities["userId"]]
                ) < 1e-9
        finally:
            batcher.drain(timeout=5.0)
        grouped = [k for order in seen_orders for k in order]
        if len(seen_orders) == 1:  # fully coalesced: assert the grouping
            shard_seq = [
                int(a.owner_of_global(
                    np.asarray([int(u[4:])])
                )[0])
                for u in grouped
            ]
            assert shard_seq == sorted(shard_seq)


# ---------------------------------------------------------------------------
# sharded-checkpoint loading (PR-11 layout, different shard count)
# ---------------------------------------------------------------------------


class TestShardedCheckpointLoad:
    def _write_ckpt(self, tmp_path, rng, n_users, d_u, ckpt_shards):
        from photon_ml_tpu.io.checkpoint import save_checkpoint_sharded

        table = rng.normal(size=(n_users, d_u)) * (
            rng.uniform(size=(n_users, d_u)) < 0.6
        )
        fixed = rng.normal(size=3)
        keys = [f"u{i:03d}" for i in range(n_users)]
        step_dir = save_checkpoint_sharded(
            str(tmp_path / "ckpt"),
            step=5,
            params={"global": fixed, "per-user": table},
            rng_key=jax.random.PRNGKey(0),
            entity_keys={"per-user": keys},
            num_shards=ckpt_shards,
        )
        return step_dir, fixed, table, keys

    @pytest.mark.parametrize(
        "serve_shards", [pytest.param(2, marks=pytest.mark.slow), 4]
    )
    def test_resume_at_different_shard_count(
        self, rng, devices, tmp_path, serve_shards
    ):
        n_users, d_u = 21, 4
        step_dir, fixed, table, keys = self._write_ckpt(
            tmp_path, rng, n_users, d_u, ckpt_shards=3
        )
        shards = {"global": "g", "per-user": "u"}
        res = {"global": None, "per-user": "userId"}
        eng = ShardedScoringEngine.from_sharded_checkpoint(
            step_dir, shards, res, num_shards=serve_shards
        )
        assert eng.re_vocabs["userId"]["u007"] == 7
        n = 19
        feats = {
            "g": rng.normal(size=(n, 3)),
            "u": rng.normal(size=(n, d_u)),
        }
        ents = rng.integers(-1, n_users, size=n).astype(np.int32)
        data = GameData.create(
            feats, np.zeros(n), entity_ids={"userId": ents}
        )
        offline = np.asarray(
            score_game_data(
                {"global": fixed, "per-user": table}, shards, res, data
            )
        )
        np.testing.assert_allclose(
            eng.score_arrays(feats, {"userId": ents}),
            offline,
            atol=1e-10,
        )

    def test_streaming_loader_matches_global_compaction(
        self, rng, devices, tmp_path
    ):
        n_users, d_u = 21, 4
        step_dir, _, table, keys = self._write_ckpt(
            tmp_path, rng, n_users, d_u, ckpt_shards=3
        )
        sharded, got_keys = load_sharded_re_table(
            step_dir, "per-user", num_shards=4
        )
        assert got_keys == keys
        a = sharded.assignment
        cols, vals = _compact_table(table)
        # the loader's forced-k per-block compaction == slicing the
        # global compaction (possibly wider-padded; compare row by row)
        for g in range(n_users):
            s = a.global_to_stored[g]
            k = cols.shape[1]
            np.testing.assert_array_equal(
                sharded.columns[s][:k], cols[g]
            )
            np.testing.assert_allclose(sharded.values[s][:k], vals[g])
            assert np.all(sharded.values[s][k:] == 0)

    def test_only_shard_block_load(self, rng, devices, tmp_path):
        n_users, d_u = 21, 4
        step_dir, _, table, _ = self._write_ckpt(
            tmp_path, rng, n_users, d_u, ckpt_shards=3
        )
        full, _ = load_sharded_re_table(step_dir, "per-user", 4)
        a = full.assignment
        for q in range(4):
            block, _ = load_sharded_re_table(
                step_dir, "per-user", 4, only_shard=q
            )
            lo = q * a.rows_per_shard
            np.testing.assert_array_equal(
                block.columns, full.columns[lo: lo + a.rows_per_shard]
            )

    def test_compact_table_rows_width_guard(self):
        rows = np.asarray([[1.0, 0.0, 2.0], [0.0, 0.0, 0.0]])
        cols, vals = compact_table_rows(rows, k=2)
        np.testing.assert_array_equal(cols, [[0, 2], [3, 3]])
        with pytest.raises(ValueError, match="cannot compact"):
            compact_table_rows(rows, k=1)

    def test_shard_compact_table_roundtrip(self, rng):
        table = rng.normal(size=(10, 5)) * (
            rng.uniform(size=(10, 5)) < 0.5
        )
        cols, vals = _compact_table(table)
        compact = CompactReTable(cols, vals)
        a = entity_shard_assignment(10, 4)
        stored = shard_compact_table(compact, a)
        back_c = stored.columns[a.global_to_stored[:10]]
        np.testing.assert_array_equal(back_c, cols)
        pad = a.stored_to_global >= 10
        assert np.all(np.asarray(stored.values)[pad] == 0)


# ---------------------------------------------------------------------------
# tiered entity cache
# ---------------------------------------------------------------------------


class TestTieredCache:
    def _cached_engine(self, rng, capacity, **extra):
        params, shards, res = _model(rng)
        return (
            ScoringEngine(
                params, shards, res, hbm_cache_entities=capacity, **extra
            ),
            ScoringEngine(params, shards, res),
        )

    def test_miss_serves_fixed_only_then_promotes_exact(self, rng):
        cached, base = self._cached_engine(rng, capacity=8)
        try:
            feats, ents = _batch(rng, 24, cold_every=6)
            ref = base.score_arrays(feats, ents)
            fixed_ref = base.score_arrays(feats, ents, fixed_only=True)
            got = cached.score_arrays(feats, ents)
            # preloaded head (entities < 8 on BOTH keys) is exact; a row
            # missing on EVERY key scores fixed-effect-only == cold-start
            hot = (
                ((ents["userId"] >= 0) & (ents["userId"] < 8))
                | (ents["userId"] < 0)
            ) & (
                ((ents["itemId"] >= 0) & (ents["itemId"] < 8))
                | (ents["itemId"] < 0)
            )
            all_miss = (ents["userId"] >= 8) & (ents["itemId"] >= 8)
            np.testing.assert_allclose(got[hot], ref[hot], atol=1e-10)
            np.testing.assert_allclose(
                got[all_miss], fixed_ref[all_miss], atol=1e-10
            )
            snap = cached.stats.snapshot()["cache"]
            assert snap["misses"] > 0 and snap["hits"] > 0
        finally:
            cached.close()

    def test_full_capacity_promotion_reaches_exact(self, rng):
        cached, base = self._cached_engine(rng, capacity=32)
        try:
            feats, ents = _batch(rng, 24)
            ref = base.score_arrays(feats, ents)
            cached.score_arrays(feats, ents)  # misses enqueue
            for cache in cached._caches.values():
                cache.flush()
            np.testing.assert_allclose(
                cached.score_arrays(feats, ents), ref, atol=1e-10
            )
            assert cached.stats.snapshot()["cache"]["promotions"] > 0
        finally:
            cached.close()

    def test_promotions_never_recompile(self, rng):
        cached, _ = self._cached_engine(rng, capacity=8)
        try:
            cached.warmup(max_batch=32)
            warm = cached.compile_count
            before = xla_compile_events()
            for _ in range(6):
                feats, ents = _batch(rng, 24, cold_every=3)
                cached.score_arrays(feats, ents)
                for cache in cached._caches.values():
                    cache.flush()
            assert cached.compile_count == warm
            assert xla_compile_events() - before == 0
        finally:
            cached.close()

    def test_deterministic_promotion_demotion_under_fixed_trace(self):
        host = np.arange(40, dtype=np.float64).reshape(20, 2)
        trace = [
            np.asarray(t, np.int32)
            for t in ([0, 1, 2], [5, 6, 1], [9, 9, 9, 2], [11, 5, 0],
                      [13, 14, 15], [1, 2, 3])
        ]

        def replay():
            cache = TieredEntityCache(
                "userId", num_entities=20, capacity=4,
                worker=False, preload_head=True, promote_batch=4,
            )
            cache.add_table("t", "values", host)
            cache.seal()
            slots = []
            for step in trace:
                slots.append(cache.translate(step).tolist())
                cache.promote_pending()
            return (
                slots,
                cache.slot_of.tolist(),
                cache.entity_of.tolist(),
            )

        first = replay()
        second = replay()
        assert first == second, "replayed trace must be bit-identical"
        # and demotion actually happened (20 entities through 4 slots)
        assert set(first[2]) != {0, 1, 2, 3}

    def test_lru_demotion_prefers_stale_slots(self):
        cache = TieredEntityCache(
            "userId", num_entities=8, capacity=2,
            worker=False, preload_head=False, promote_batch=2,
        )
        cache.add_table("t", "values", np.arange(8.0).reshape(8, 1))
        cache.seal()
        cache.translate(np.asarray([0, 1], np.int32))
        cache.promote_pending()
        cache.translate(np.asarray([0], np.int32))  # touch 0: 1 is LRU
        cache.translate(np.asarray([5], np.int32))
        cache.promote_pending()
        assert cache.slot_of[1] == -1, "LRU entity must be demoted"
        assert cache.slot_of[0] >= 0 and cache.slot_of[5] >= 0

    def test_registry_retire_stops_cache_worker(self, rng, tmp_path):
        import tests.test_serving as ts

        root_a = ts._save_disk_model(str(tmp_path / "v1"), rng)
        root_b = ts._save_disk_model(str(tmp_path / "v2"), rng, scale=2.0)
        reg = ModelRegistry(
            warmup_max_batch=8, hbm_cache_entities=2
        )
        v1 = reg.load(root_a)
        caches = list(v1.engine._caches.values())
        assert caches and all(c._thread is not None for c in caches)
        reg.load(root_b)
        assert v1.retired and v1.engine is None
        assert all(c._thread is None for c in caches), (
            "retiring a version must stop its promotion workers"
        )

    def test_sharded_engine_rejects_cache(self, rng, devices):
        params, shards, res = _model(rng)
        with pytest.raises(ValueError, match="unsharded engine"):
            ShardedScoringEngine(
                params, shards, res, num_shards=2, hbm_cache_entities=4
            )


# ---------------------------------------------------------------------------
# fault sites
# ---------------------------------------------------------------------------


class TestServingFaults:
    def test_single_shard_fault_degrades_only_its_entities(
        self, rng, devices
    ):
        params, shards, res = _model(rng)
        eng = ShardedScoringEngine(params, shards, res, num_shards=4)
        base = ScoringEngine(params, shards, res)
        feats, ents = _batch(rng, 32, cold_every=1000)
        exact = base.score_arrays(feats, ents)
        a_user = eng.assignments["userId"]
        a_item = eng.assignments["itemId"]
        victim = 2
        u_hit = a_user.owner_of_global(ents["userId"]) == victim
        i_hit = a_item.owner_of_global(ents["itemId"]) == victim
        with inject(
            FaultSpec(
                "serving.shard_route", "raise", nth=1, count=-1,
                key=str(victim),
            )
        ):
            got = eng.score_arrays(feats, ents)
        assert np.all(np.isfinite(got))
        clean = ~u_hit & ~i_hit
        np.testing.assert_allclose(got[clean], exact[clean], atol=1e-10)
        # affected rows lose exactly the victim-owned coordinates
        hand = base.score_arrays(
            feats,
            {
                "userId": np.where(u_hit, -1, ents["userId"]),
                "itemId": np.where(i_hit, -1, ents["itemId"]),
            },
        )
        np.testing.assert_allclose(got, hand, atol=1e-10)
        assert (
            eng.stats.registry.counter(
                "serving.shard.degraded_rows"
            ).value
            > 0
        )
        # recovery: next batch exact
        np.testing.assert_allclose(
            eng.score_arrays(feats, ents), exact, atol=1e-10
        )

    def test_chaos_drill_passes_on_the_test_mesh(self, devices):
        from photon_ml_tpu.resilience.drills import drill_shard_fault

        out = drill_shard_fault(smoke=True)
        assert out["serving_shards"] == 2
        assert out["batched_requests"] == 24
        assert out["cache_tier_errors"] >= 1

    def test_sites_registered(self):
        from photon_ml_tpu.resilience.faults import known_sites

        assert "serving.shard_route" in known_sites()
        assert "serving.cache_tier" in known_sites()


# ---------------------------------------------------------------------------
# hot-reload under load (sharded registry)
# ---------------------------------------------------------------------------


class TestShardedRegistry:
    def test_hot_reload_under_load_drops_nothing(
        self, rng, devices, tmp_path
    ):
        import tests.test_serving as ts

        root_a = ts._save_disk_model(str(tmp_path / "v1"), rng, scale=1.0)
        root_b = ts._save_disk_model(str(tmp_path / "v2"), rng, scale=3.0)
        reg = ModelRegistry(warmup_max_batch=16, serving_shards=2)
        v1 = reg.load(root_a)
        assert isinstance(v1.engine, ShardedScoringEngine)
        probe = ScoreRequest(
            features={"uf0": 1.0, "uf2": 0.5}, entities={"userId": "u2"}
        )
        s_a = reg.score([probe])[0]
        s_b = ShardedScoringEngine.from_model_dir(
            root_b, num_shards=2
        ).score([probe])[0]
        # sharded == unsharded on both versions
        assert (
            abs(s_a - ScoringEngine.from_model_dir(root_a).score([probe])[0])
            < 1e-10
        )
        assert abs(s_a - s_b) > 1e-6
        batcher = MicroBatcher(
            reg.score, max_batch=16, max_wait_ms=0.5, stats=reg.stats
        )
        results = [[] for _ in range(4)]
        errors = []

        def client(ci):
            try:
                for _ in range(30):
                    results[ci].append(
                        batcher.submit(probe).result(timeout=30)
                    )
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [
            threading.Thread(target=client, args=(ci,))
            for ci in range(4)
        ]
        for t in threads:
            t.start()
        time.sleep(0.02)
        reg.load(root_b)  # hot-reload mid-storm: swaps the shard set
        for t in threads:
            t.join()
        assert batcher.drain()
        assert not errors, errors
        flat = [s for chunk in results for s in chunk]
        assert len(flat) == 120, "requests were dropped"
        for s in flat:
            assert min(abs(s - s_a), abs(s - s_b)) < 1e-9
        assert reg.version() == "v2"
        assert v1.retired and v1.engine is None
        health = reg.health()
        assert health["serving_shards"] == 2


# ---------------------------------------------------------------------------
# stats / taxonomy / sentinel wiring
# ---------------------------------------------------------------------------


class TestObservabilityWiring:
    def test_snapshot_carries_cache_and_shard_keys(self, rng, devices):
        params, shards, res = _model(rng)
        eng = ShardedScoringEngine(params, shards, res, num_shards=4)
        feats, ents = _batch(rng, 16)
        eng.score_arrays(feats, ents)
        snap = eng.stats.snapshot()
        assert snap["resident_re_bytes_per_process"] > 0
        assert set(snap["cache"]) == {
            "hits", "misses", "promotions", "demotions", "tier_errors",
            "hit_frac", "admission_logged", "admission_promoted",
        }
        assert snap["shards"], "per-shard occupancy must be recorded"
        for info in snap["shards"].values():
            assert "occupancy" in info

    def test_taxonomy_binds_new_names(self):
        from photon_ml_tpu.obs import taxonomy

        for name in (
            "serving.cache.hits",
            "serving.cache.tier_errors",
            "serving.shard.occupancy.3",
            "serving.shard.device_ms.0",
            "serving.shard.resident_re_bytes_per_process",
        ):
            assert taxonomy.matches(name), name
        assert taxonomy.subsystem_of("serving.cache.hits") == (
            "serving.cache"
        )
        assert taxonomy.subsystem_of("serving.shard.occupancy.0") == (
            "serving.shard"
        )

    def test_sentinel_directions(self):
        from photon_ml_tpu.obs.sentinel import (
            HIGHER_IS_BETTER,
            LOWER_IS_BETTER,
            metric_direction,
        )

        assert (
            metric_direction(
                "extra.serving_sharded.serving_sharded_qps"
            )
            == HIGHER_IS_BETTER
        )
        assert (
            metric_direction("extra.serving_sharded.cache_hit_frac")
            == HIGHER_IS_BETTER
        )
        assert (
            metric_direction(
                "extra.serving_sharded.resident_re_bytes_per_process"
            )
            == LOWER_IS_BETTER
        )

    def test_serving_lab_zipf_record(self, capsys):
        import sys

        sys.path.insert(
            0, os.path.join(os.path.dirname(__file__), "..")
        )
        try:
            from benchmarks.serving_lab import run
        finally:
            sys.path.pop(0)
        record = run(
            [
                "--smoke", "--clients", "2", "--requests", "64",
                "--baseline-requests", "8", "--zipf-alpha", "1.2",
                "--tenants", "2", "--hbm-cache-entities", "16",
            ]
        )
        extra = record["extra"]
        assert extra["steady_state_compiles"] == 0
        assert set(extra["per_tenant"]) == {"tenant0", "tenant1"}
        for t in extra["per_tenant"].values():
            assert t["requests"] == 32 and t["qps"] > 0
        assert 0.0 <= extra["cache_hit_frac"] <= 1.0
        assert extra["cache"]["promotions"] > 0
        assert extra["resident_re_bytes_per_process"] > 0
        capsys.readouterr()
