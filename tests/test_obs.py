"""Observability layer drills: span tracer (nesting, thread-safety,
Chrome-trace validity), metrics registry (counter/gauge/histogram edge
cases, Prometheus exposition), disabled-mode no-op contract, PhotonLogger
upgrades (utf-8/jsonl/env level, timed->span), ServingStats schema
stability on top of the registry, and the GAME train e2e asserting one
span per pass per coordinate plus a registry snapshot with solver
iteration counts, recompile count, and checkpoint bytes."""

import json
import os
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.obs.metrics import (
    Counter,
    Gauge,
    LatencyHistogram,
    MetricsRegistry,
)
from photon_ml_tpu.obs.trace import Tracer

pytestmark = pytest.mark.obs


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------


class TestTracer:
    def test_span_nesting_windows_contain(self, tmp_path):
        with obs.trace(str(tmp_path / "t")) as tracer:
            with obs.span("outer"):
                with obs.span("inner"):
                    pass
        events = {
            e["name"]: e for e in tracer.events() if e["ph"] == "X"
        }
        outer, inner = events["outer"], events["inner"]
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6

    def test_chrome_trace_json_valid(self, tmp_path):
        tdir = str(tmp_path / "t")
        with obs.trace(tdir):
            with obs.span("a", cat="x", foo=1):
                pass
            obs.emit_event("bang", cat="y", bar="z")
        doc = json.load(open(os.path.join(tdir, "trace.json")))
        assert "traceEvents" in doc
        evs = doc["traceEvents"]
        # monotone ts in file order, non-negative durations, required keys
        assert all(
            evs[i]["ts"] <= evs[i + 1]["ts"] for i in range(len(evs) - 1)
        )
        for e in evs:
            assert {"ph", "name", "pid", "tid", "ts"} <= set(e)
            if e["ph"] == "X":
                assert e["dur"] >= 0
        names = [e["name"] for e in evs]
        assert "a" in names and "bang" in names

    def test_jsonl_event_log_one_record_per_line(self, tmp_path):
        tdir = str(tmp_path / "t")
        with obs.trace(tdir):
            with obs.span("phase", k=1):
                pass
            obs.emit_event("retry", label="x", attempt=2)
        lines = [
            json.loads(l)
            for l in open(
                os.path.join(tdir, "events.jsonl"), encoding="utf-8"
            )
        ]
        kinds = {l["kind"] for l in lines}
        assert kinds == {"span", "event"}
        span_rec = next(l for l in lines if l["kind"] == "span")
        assert span_rec["name"] == "phase" and span_rec["k"] == 1
        assert span_rec["duration_ms"] >= 0

    def test_thread_safety_all_spans_recorded(self, tmp_path):
        n_threads, n_spans = 8, 50
        with obs.trace(str(tmp_path / "t")) as tracer:

            def work(i):
                for j in range(n_spans):
                    with obs.span("w", thread=i, j=j):
                        pass

            threads = [
                threading.Thread(target=work, args=(i,))
                for i in range(n_threads)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        spans = [e for e in tracer.events() if e["name"] == "w"]
        assert len(spans) == n_threads * n_spans
        # every (thread, j) combination landed exactly once
        seen = {(e["args"]["thread"], e["args"]["j"]) for e in spans}
        assert len(seen) == n_threads * n_spans

    def test_no_tracer_span_is_live_and_lands_in_the_ring(self):
        assert obs.get_tracer() is None
        s = obs.span("anything", key="value")
        with s:
            s.set(more="attrs")
        assert s.sync([1, 2, 3]) == [1, 2, 3]
        obs.emit_event("nothing")  # must not raise
        rec = obs.recent_spans()[-1]
        assert rec[0] == "anything"
        assert rec[6]["key"] == "value" and rec[6]["more"] == "attrs"

    def test_trace_none_dir_is_noop(self):
        with obs.trace(None) as t:
            assert t is None
            assert obs.get_tracer() is None

    def test_nested_install_restores_previous(self, tmp_path):
        with obs.trace(str(tmp_path / "a")) as ta:
            assert obs.get_tracer() is ta
            with obs.trace(str(tmp_path / "b")) as tb:
                assert obs.get_tracer() is tb
            assert obs.get_tracer() is ta
        assert obs.get_tracer() is None

    def test_span_error_annotated(self, tmp_path):
        with obs.trace(str(tmp_path / "t")) as tracer:
            with pytest.raises(RuntimeError):
                with obs.span("doomed"):
                    raise RuntimeError("boom")
        (ev,) = [e for e in tracer.events() if e["name"] == "doomed"]
        assert ev["args"]["error"] is True

    def test_sync_annotates_device_wait(self, tmp_path):
        with obs.trace(str(tmp_path / "t")) as tracer:
            with obs.span("dispatch") as sp:
                out = sp.sync(jnp.ones((4,)) * 2.0)
        np.testing.assert_allclose(np.asarray(out), 2.0)
        (ev,) = [e for e in tracer.events() if e["name"] == "dispatch"]
        assert ev["args"]["device_wait_ms"] >= 0


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_counter_gauge_roundtrip(self):
        reg = MetricsRegistry()
        reg.inc("a.b", 2)
        reg.inc("a.b", 0.5)
        reg.set_gauge("g", -3.5)
        snap = reg.snapshot()
        assert snap["counters"]["a.b"] == 2.5
        assert snap["gauges"]["g"] == -3.5

    def test_counter_rejects_negative(self):
        with pytest.raises(ValueError):
            Counter().inc(-1)

    def test_same_name_same_instrument(self):
        reg = MetricsRegistry()
        assert reg.counter("x") is reg.counter("x")

    def test_kind_conflict_raises(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(TypeError, match="already registered"):
            reg.gauge("x")
        with pytest.raises(TypeError, match="already registered"):
            reg.observe("x", 1.0)

    def test_histogram_empty(self):
        h = LatencyHistogram()
        assert h.quantile(0.5) == 0.0
        snap = h.snapshot()
        assert snap["count"] == 0 and snap["mean_ms"] == 0.0

    def test_histogram_single_sample(self):
        h = LatencyHistogram()
        h.record(10.0)
        # resolution is the bucket edge ratio (~12%)
        assert h.quantile(0.5) == pytest.approx(10.0, rel=0.15)
        assert h.snapshot()["max_ms"] == 10.0

    def test_histogram_overflow_bucket(self):
        h = LatencyHistogram(lo_ms=1.0, hi_ms=100.0, bins=8)
        h.record(1e6)  # far beyond hi: overflow bucket
        h.record(1e7)
        assert h.quantile(0.99) == 1e7  # overflow reports the true max
        assert h.counts[-1] == 2

    def test_histogram_nonpositive_underflow(self):
        h = LatencyHistogram(lo_ms=1.0, hi_ms=100.0, bins=8)
        h.record(0.0)
        h.record(-1.0)
        assert h.counts[0] == 2
        assert h.quantile(0.5) == pytest.approx(1.0)  # lo edge

    def test_histogram_quantiles_bounded_by_samples(self):
        h = LatencyHistogram()
        samples = [0.5, 1.0, 2.0, 4.0, 8.0, 100.0]
        for s in samples:
            h.record(s)
        for q in (0.1, 0.5, 0.9, 0.99, 1.0):
            # within-bucket interpolation: bounded by the max sample up
            # to the bucket-edge ratio (~12% resolution)
            assert 0 < h.quantile(q) <= max(samples) * 1.13

    def test_thread_safe_counters(self):
        reg = MetricsRegistry()

        def work():
            for _ in range(1000):
                reg.inc("n")

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert reg.counter("n").value == 8000

    def test_prometheus_exposition(self):
        reg = MetricsRegistry()
        reg.inc("game.passes", 3)
        reg.set_gauge("game.objective", 1.5)
        reg.observe("serving.request_ms", 2.0)
        text = reg.to_prometheus()
        assert "# TYPE photon_game_passes counter" in text
        assert "photon_game_passes 3" in text
        assert "# TYPE photon_game_objective gauge" in text
        assert "photon_game_objective 1.5" in text
        assert "# TYPE photon_serving_request_ms summary" in text
        assert 'photon_serving_request_ms{quantile="0.5"}' in text
        assert "photon_serving_request_ms_count 1" in text
        assert text.endswith("\n")

    def test_dump_and_reset(self, tmp_path):
        reg = MetricsRegistry()
        reg.inc("x")
        path = reg.dump(str(tmp_path / "metrics.json"))
        doc = json.load(open(path))
        assert doc["counters"]["x"] == 1
        assert "time_unix" in doc
        reg.reset()
        assert reg.snapshot()["counters"] == {}

    def test_default_registry_swap(self):
        mine = MetricsRegistry()
        prev = obs.set_registry(mine)
        try:
            obs.registry().inc("probe")
            assert mine.counter("probe").value == 1
        finally:
            obs.set_registry(prev)


# ---------------------------------------------------------------------------
# MetricsDumper / observe envelope
# ---------------------------------------------------------------------------


class TestObserve:
    def test_observe_writes_final_metrics_and_trace(self, tmp_path):
        tdir = str(tmp_path / "t")
        reg = MetricsRegistry()
        prev = obs.set_registry(reg)
        try:
            with obs.observe(trace_dir=tdir):
                obs.registry().inc("probe")
                with obs.span("inside"):
                    pass
        finally:
            obs.set_registry(prev)
        assert os.path.exists(os.path.join(tdir, "trace.json"))
        assert os.path.exists(os.path.join(tdir, "events.jsonl"))
        snap = json.load(open(os.path.join(tdir, "metrics.json")))
        assert snap["counters"]["probe"] == 1

    def test_observe_all_none_is_noop(self):
        with obs.observe():
            assert obs.get_tracer() is None

    def test_periodic_dumper(self, tmp_path):
        import time

        path = str(tmp_path / "m.json")
        reg = MetricsRegistry()
        reg.inc("tick")
        d = obs.MetricsDumper(path, every_s=0.05, reg=reg).start()
        try:
            deadline = time.monotonic() + 5.0
            while not os.path.exists(path):
                assert time.monotonic() < deadline, "no periodic dump"
                time.sleep(0.02)
        finally:
            d.stop()
        assert json.load(open(path))["counters"]["tick"] == 1


# ---------------------------------------------------------------------------
# PhotonLogger satellite
# ---------------------------------------------------------------------------


class TestPhotonLogger:
    def test_utf8_file(self, tmp_path):
        from photon_ml_tpu.utils.logging import PhotonLogger

        path = str(tmp_path / "log-message.txt")
        with open(os.devnull, "w") as sink:
            with PhotonLogger(path, stream=sink) as lg:
                lg.info("héllo wörld — ƒeature")
        text = open(path, encoding="utf-8").read()
        assert "héllo wörld — ƒeature" in text

    def test_jsonl_mode(self, tmp_path):
        from photon_ml_tpu.utils.logging import PhotonLogger

        path = str(tmp_path / "log.jsonl")
        with open(os.devnull, "w") as sink:
            with PhotonLogger(path, stream=sink, jsonl=True) as lg:
                lg.info("structured")
                lg.warn("second")
        recs = [json.loads(l) for l in open(path, encoding="utf-8")]
        assert [r["level"] for r in recs] == ["INFO", "WARN"]
        assert recs[0]["msg"] == "structured"
        assert recs[0]["ts"] > 0

    def test_env_level_override(self, tmp_path, monkeypatch):
        from photon_ml_tpu.utils.logging import PhotonLogger

        monkeypatch.setenv("PHOTON_LOG_LEVEL", "warn")
        path = str(tmp_path / "log.txt")
        with open(os.devnull, "w") as sink:
            with PhotonLogger(path, level="DEBUG", stream=sink) as lg:
                lg.info("hidden")
                lg.warn("shown")
        text = open(path, encoding="utf-8").read()
        assert "hidden" not in text and "shown" in text

    def test_env_level_bad_value_ignored(self, tmp_path, monkeypatch):
        from photon_ml_tpu.utils.logging import PhotonLogger

        monkeypatch.setenv("PHOTON_LOG_LEVEL", "LOUD")
        path = str(tmp_path / "log.txt")
        with open(os.devnull, "w") as sink:
            with PhotonLogger(path, level="INFO", stream=sink) as lg:
                lg.info("kept")
        assert "kept" in open(path, encoding="utf-8").read()

    def test_timed_emits_span(self, tmp_path):
        from photon_ml_tpu.utils.logging import timed

        with obs.trace(str(tmp_path / "t")) as tracer:
            with timed(None, "phase-x"):
                pass
        names = [e["name"] for e in tracer.events() if e["ph"] == "X"]
        assert "phase-x" in names


# ---------------------------------------------------------------------------
# ServingStats on the registry (schema stability)
# ---------------------------------------------------------------------------


class TestServingStatsCompat:
    # the pre-obs schema plus the PR-6 queue/bucket observability keys
    # (queue_depth gauge, peak, per-bucket device-latency histograms) —
    # additions only; every pre-existing key keeps its shape
    GOLDEN_KEYS = {
        "uptime_s", "requests", "batches", "rejected", "errors",
        "reloads", "qps", "batch_occupancy_mean", "buckets",
        "bucket_hits", "bucket_misses", "compile_count",
        "request_latency", "device_latency",
        "queue_depth", "queue_depth_peak", "bucket_latency",
        # chaos-hardened serving (docs/ROBUSTNESS.md): deadline expiry,
        # admission-control shedding, degraded mode, breaker failures —
        # additive keys; everything above is byte-compatible
        "expired", "shed", "degraded", "degraded_batches",
        "reload_failures",
        # model-quality observability (docs/OBSERVABILITY.md "Quality &
        # drift"): per-model-version score-distribution histograms —
        # additive key; everything above keeps its shape
        "score_distribution",
        # entity-sharded serving + tiered entity cache (docs/SERVING.md):
        # cache hit/miss/promotion counters, per-shard occupancy/latency,
        # and the per-process resident RE footprint gauge — additive
        # keys; everything above keeps its shape
        "cache", "shards", "resident_re_bytes_per_process",
    }

    def test_snapshot_schema_unchanged(self):
        from photon_ml_tpu.serving.stats import ServingStats

        st = ServingStats()
        st.record_batch(4, 0.002)
        st.record_request_latency(0.001)
        st.record_bucket(8, hit=False)
        st.record_bucket(8, hit=True)
        st.record_compile()
        st.record_rejected()
        st.record_error()
        st.record_reload()
        st.record_scores("v1", [0.5, -0.5, 1.5, 2.0])
        snap = st.snapshot()
        assert set(snap) == self.GOLDEN_KEYS
        assert snap["requests"] == 4 and snap["batches"] == 1
        assert snap["score_distribution"]["v1"]["count"] == 4
        assert snap["buckets"] == {"8": 2}
        assert snap["bucket_hits"] == 1 and snap["bucket_misses"] == 1
        assert isinstance(snap["requests"], int)
        lat = snap["request_latency"]
        assert {"count", "mean_ms", "p50_ms", "p95_ms", "p99_ms", "max_ms"} == set(lat)
        # json round-trips (the cli stats command wire format); uptime/qps
        # are time-dependent so compare a re-serialization of THIS snapshot
        assert json.loads(json.dumps(snap)) == snap

    def test_counter_attributes_still_readable(self):
        from photon_ml_tpu.serving.stats import ServingStats

        st = ServingStats()
        st.record_batch(3, 0.001)
        assert st.requests == 3
        assert st.batches == 1
        with pytest.raises(AttributeError):
            st.not_a_counter

    def test_stats_metrics_visible_in_registry(self):
        from photon_ml_tpu.serving.stats import ServingStats

        st = ServingStats()
        st.record_batch(2, 0.001)
        text = st.registry.to_prometheus()
        assert "photon_serving_requests 2" in text

    def test_old_import_location_still_works(self):
        from photon_ml_tpu.serving.stats import (
            LatencyHistogram as FromServing,
            install_compile_listener as icl,
            xla_compile_events as xce,
        )
        from photon_ml_tpu.obs.compile_events import (
            install_compile_listener,
            xla_compile_events,
        )

        assert FromServing is LatencyHistogram
        assert icl is install_compile_listener
        assert xce is xla_compile_events


# ---------------------------------------------------------------------------
# Resilience + io events
# ---------------------------------------------------------------------------


class TestEventInstrumentation:
    def test_retry_emits_events_and_counters(self, tmp_path):
        from photon_ml_tpu.resilience.retry import retry_call

        reg = MetricsRegistry()
        prev = obs.set_registry(reg)
        calls = [0]

        def flaky():
            calls[0] += 1
            if calls[0] < 3:
                raise OSError("transient")
            return "ok"

        try:
            with obs.trace(str(tmp_path / "t")) as tracer:
                assert retry_call(flaky, base_delay=0.001, seed=1) == "ok"
        finally:
            obs.set_registry(prev)
        assert reg.counter("resilience.retries").value == 2
        retries = [
            e for e in tracer.events()
            if e["name"] == "resilience.retry"
        ]
        assert len(retries) == 2
        assert retries[0]["args"]["attempt"] == 1

    def test_fault_injection_counted(self):
        from photon_ml_tpu.resilience.faults import (
            FaultSpec,
            InjectedFault,
            fire,
            inject,
        )

        reg = MetricsRegistry()
        prev = obs.set_registry(reg)
        try:
            with inject(FaultSpec(site="ingest.read", mode="raise", nth=1)):
                with pytest.raises(InjectedFault):
                    fire("ingest.read")
        finally:
            obs.set_registry(prev)
        assert reg.counter("resilience.faults_injected").value == 1
        assert (
            reg.counter("resilience.faults_injected.ingest.read").value == 1
        )

    def test_checkpoint_bytes_and_latency_recorded(self, tmp_path):
        from photon_ml_tpu.io.checkpoint import (
            latest_checkpoint,
            save_checkpoint,
        )

        reg = MetricsRegistry()
        prev = obs.set_registry(reg)
        try:
            save_checkpoint(
                str(tmp_path / "ck"),
                1,
                {"w": np.ones((4, 2))},
                np.zeros(2, np.uint32),
            )
            ck = latest_checkpoint(str(tmp_path / "ck"))
        finally:
            obs.set_registry(prev)
        assert ck is not None and ck.step == 1
        snap = reg.snapshot()
        assert snap["counters"]["io.checkpoint.saves"] == 1
        assert snap["counters"]["io.checkpoint.bytes_written"] > 0
        assert snap["counters"]["io.checkpoint.loads"] == 1
        assert snap["counters"]["io.checkpoint.bytes_read"] > 0
        assert snap["histograms"]["io.checkpoint.save_ms"]["count"] == 1

    def test_preemption_event_recorded(self):
        from photon_ml_tpu.resilience.shutdown import GracefulShutdown

        reg = MetricsRegistry()
        prev = obs.set_registry(reg)
        try:
            sd = GracefulShutdown()
            sd.request(15)
            sd.request(15)  # second request must not double-count
        finally:
            obs.set_registry(prev)
        assert reg.counter("resilience.preemptions").value == 1


# ---------------------------------------------------------------------------
# GAME train e2e: one span per pass per coordinate + registry contents
# ---------------------------------------------------------------------------


def _build_cd(rng, fuse_passes=True):
    from photon_ml_tpu.core.tasks import TaskType
    from photon_ml_tpu.game import (
        CoordinateConfig,
        CoordinateDescent,
        FixedEffectCoordinate,
        GameData,
        RandomEffectCoordinate,
        build_random_effect_design,
    )
    from photon_ml_tpu.models.training import OptimizerType

    dtype = jnp.float64
    n, d, e, du = 600, 6, 20, 3
    user = rng.integers(0, e, n).astype(np.int32)
    xg = rng.standard_normal((n, d))
    xu = rng.standard_normal((n, du))
    y = (rng.uniform(size=n) < 0.5).astype(np.float64)
    data = GameData.create(
        features={"global": xg, "per_user": xu},
        labels=y,
        entity_ids={"userId": user},
    )
    fixed = FixedEffectCoordinate(
        data.fixed_effect_batch("global", dtype),
        CoordinateConfig(
            shard="global",
            task=TaskType.LOGISTIC_REGRESSION,
            optimizer=OptimizerType.TRON,
            reg_weight=1.0,
            max_iters=5,
            tolerance=1e-6,
        ),
    )
    design = build_random_effect_design(
        data, "userId", "per_user", e, dtype=dtype
    )
    random = RandomEffectCoordinate(
        design=design,
        row_features=jnp.asarray(xu, dtype),
        row_entities=jnp.asarray(user),
        full_offsets_base=jnp.asarray(data.offsets, dtype),
        config=CoordinateConfig(
            shard="per_user",
            task=TaskType.LOGISTIC_REGRESSION,
            optimizer=OptimizerType.TRON,
            reg_weight=5.0,
            max_iters=5,
            tolerance=1e-6,
            random_effect="userId",
        ),
    )
    return CoordinateDescent(
        coordinates={"fixed": fixed, "per-user": random},
        labels=jnp.asarray(y, dtype),
        base_offsets=jnp.asarray(data.offsets, dtype),
        weights=jnp.asarray(data.weights, dtype),
        task=TaskType.LOGISTIC_REGRESSION,
        fuse_passes=fuse_passes,
    )


class TestGameTraceE2E:
    N_ITER = 3

    def _assert_trace(self, tdir, n_coords=2, fused=None):
        doc = json.load(open(os.path.join(tdir, "trace.json")))
        evs = doc["traceEvents"]
        assert all(
            evs[i]["ts"] <= evs[i + 1]["ts"] for i in range(len(evs) - 1)
        )
        updates = [e for e in evs if e["name"] == "game.update"]
        dispatches = [e for e in evs if e["name"] == "game.dispatch"]
        (root,) = [e for e in evs if e["name"] == "game.cd.run"]
        assert root["args"]["iterations"] == self.N_ITER
        for e in dispatches + updates:
            assert e["dur"] >= 0
            assert root["ts"] <= e["ts"]
            assert e["ts"] + e["dur"] <= root["ts"] + root["dur"] + 1.0
        if fused:
            # a fused pass is ONE indivisible dispatch: one real span a
            # pass that says how many coordinates it covers, and no
            # per-coordinate window invented inside it
            passes = [
                e for e in dispatches if e["args"]["kind"] == "fused"
            ]
            assert [e["args"]["iteration"] for e in passes] == list(
                range(self.N_ITER)
            )
            assert all(
                e["args"]["passes"] == 1
                and e["args"]["coordinates"] == n_coords
                for e in passes
            )
            assert not updates
            return
        # exactly one span per pass per coordinate
        assert len(updates) == self.N_ITER * n_coords
        seen = {
            (e["args"]["iteration"], e["args"]["coordinate"])
            for e in updates
        }
        assert len(seen) == self.N_ITER * n_coords
        assert {it for it, _ in seen} == set(range(self.N_ITER))
        # each update's dispatches lie inside its own window
        for u in updates:
            inside = [
                d for d in dispatches
                if u["ts"] <= d["ts"]
                and d["ts"] + d["dur"] <= u["ts"] + u["dur"] + 1.0
            ]
            assert any(d["args"]["kind"] == "update" for d in inside)

    def test_fused_run_trace_and_metrics(self, rng, tmp_path):
        cd = _build_cd(rng, fuse_passes=True)
        reg = MetricsRegistry()
        prev = obs.set_registry(reg)
        tdir = str(tmp_path / "trace")
        try:
            with obs.observe(trace_dir=tdir):
                cd.run(num_iterations=self.N_ITER)
        finally:
            obs.set_registry(prev)
        self._assert_trace(tdir, fused=True)
        snap = json.load(open(os.path.join(tdir, "metrics.json")))
        assert snap["counters"]["game.passes"] == self.N_ITER
        assert snap["counters"]["game.updates"] == self.N_ITER * 2
        assert snap["counters"]["game.solver_iterations"] > 0
        assert "xla.compiles" in snap["counters"]
        assert snap["histograms"]["game.pass_ms"]["count"] == self.N_ITER
        assert "game.objective" in snap["gauges"]

    def test_unfused_run_per_coordinate_durations(self, rng, tmp_path):
        cd = _build_cd(rng, fuse_passes=False)
        tdir = str(tmp_path / "trace")
        with obs.observe(trace_dir=tdir):
            cd.run(num_iterations=self.N_ITER)
        self._assert_trace(tdir, fused=False)

    def test_untraced_run_identical_history(self, rng, tmp_path):
        """Observability must not perturb the math: the same seed with
        and without the tracer produces bit-identical objectives."""
        cd_a = _build_cd(rng, fuse_passes=False)
        _, hist_plain = cd_a.run(num_iterations=2, seed=7)
        with obs.observe(trace_dir=str(tmp_path / "t")):
            _, hist_traced = cd_a.run(num_iterations=2, seed=7)
        assert [h.objective for h in hist_plain] == [
            h.objective for h in hist_traced
        ]


# ---------------------------------------------------------------------------
# Driver e2e: --trace-dir surfacing through run_game_training
# ---------------------------------------------------------------------------


def _sigmoid(z):
    return 1.0 / (1.0 + np.exp(-z))


def _write_game_input(rng, tmp_path, n_users=10, rows_per_user=20,
                      d_g=4, d_u=2):
    from photon_ml_tpu.io.avro import write_avro_file
    from photon_ml_tpu.io.schemas import TRAINING_EXAMPLE_SCHEMA
    from photon_ml_tpu.io.vocab import FeatureVocabulary, feature_key

    w_g = rng.normal(size=d_g)
    w_u = rng.normal(size=(n_users, d_u))
    records = []
    for u in range(n_users):
        for i in range(rows_per_user):
            xg = rng.normal(size=d_g)
            xu = rng.normal(size=d_u)
            y = float(rng.uniform() < _sigmoid(xg @ w_g + xu @ w_u[u]))
            records.append(
                {
                    "uid": f"r{u}-{i}",
                    "label": y,
                    "features": [
                        {"name": f"gf{j}", "term": "", "value": float(v)}
                        for j, v in enumerate(xg)
                    ]
                    + [
                        {"name": f"uf{j}", "term": "", "value": float(v)}
                        for j, v in enumerate(xu)
                    ],
                    "metadataMap": {"userId": f"user{u}"},
                    "weight": None,
                    "offset": None,
                }
            )
    train = str(tmp_path / "gtrain.avro")
    write_avro_file(train, TRAINING_EXAMPLE_SCHEMA, records)
    gshard = str(tmp_path / "g.features")
    FeatureVocabulary(
        [feature_key(f"gf{j}", "") for j in range(d_g)], add_intercept=True
    ).save(gshard)
    ushard = str(tmp_path / "u.features")
    FeatureVocabulary(
        [feature_key(f"uf{j}", "") for j in range(d_u)], add_intercept=True
    ).save(ushard)
    return train, gshard, ushard


class TestDriverSurfacing:
    def test_game_train_trace_dir_acceptance(self, rng, tmp_path):
        """The PR's acceptance artifact: a smoke GAME training run with
        trace_dir set produces (a) a valid Chrome trace with one
        game.dispatch span per fused pass and (b) a metrics.json
        carrying solver iteration counts, the recompile count, and
        ingest + checkpoint bytes."""
        from photon_ml_tpu.cli.game_train import run_game_training

        train, gshard, ushard = _write_game_input(rng, tmp_path)
        tdir = str(tmp_path / "trace")
        reg = MetricsRegistry()
        prev = obs.set_registry(reg)
        n_iter = 2
        try:
            run_game_training(
                {
                    "train_input": [train],
                    "output_dir": str(tmp_path / "out"),
                    "task": "LOGISTIC_REGRESSION",
                    "num_iterations": n_iter,
                    "updating_sequence": ["global", "per-user"],
                    "feature_shards": {
                        "gshard": gshard, "ushard": ushard
                    },
                    "coordinates": {
                        "global": {
                            "shard": "gshard",
                            "optimizer": "TRON",
                            "reg_weights": [0.1],
                            "max_iters": 10,
                            "tolerance": 1e-6,
                        },
                        "per-user": {
                            "shard": "ushard",
                            "random_effect": "userId",
                            "optimizer": "TRON",
                            "reg_weights": [1.0],
                            "max_iters": 10,
                            "tolerance": 1e-6,
                            "num_buckets": 1,
                        },
                    },
                    "checkpoint_every": 1,
                    "trace_dir": tdir,
                }
            )
        finally:
            obs.set_registry(prev)

        # (a) valid Chrome trace, one dispatch span per fused pass
        doc = json.load(open(os.path.join(tdir, "trace.json")))
        evs = doc["traceEvents"]
        assert all(
            evs[i]["ts"] <= evs[i + 1]["ts"] for i in range(len(evs) - 1)
        )
        assert all(e.get("dur", 0) >= 0 for e in evs)
        passes = [
            e for e in evs
            if e["name"] == "game.dispatch"
            and e["args"]["kind"] == "fused"
        ]
        assert [e["args"]["iteration"] for e in passes] == list(
            range(n_iter)
        )
        assert all(e["args"]["coordinates"] == 2 for e in passes)
        # checkpoint_every=1: each boundary's host copy is a fetch span
        fetched = {
            e["args"]["what"] for e in evs if e["name"] == "game.fetch"
        }
        assert {"checkpoint", "history"} <= fetched
        # driver phases (timed() call sites) landed as spans for free
        names = {e["name"] for e in evs}
        assert "prepare data" in names and "save models" in names

        # (b) metrics.json registry snapshot contents
        snap = json.load(open(os.path.join(tdir, "metrics.json")))
        c = snap["counters"]
        assert c["game.solver_iterations"] > 0
        assert "xla.compiles" in c
        assert c["io.ingest.bytes_read"] > 0
        assert c["io.checkpoint.bytes_written"] > 0
        assert c["game.passes"] == n_iter

# ---------------------------------------------------------------------------
# XLA cost book
# ---------------------------------------------------------------------------


class TestCostBook:
    def test_compiled_matmul_record(self):
        """XLA-measured FLOPs of a known matmul (2mnk), compiled-only
        memory fields, lookup/snapshot round trip."""
        import jax

        from photon_ml_tpu.obs.xla_cost import CostBook

        m = 64
        comp = (
            jax.jit(lambda a, b: a @ b)
            .lower(
                jnp.zeros((m, m), jnp.float32),
                jnp.zeros((m, m), jnp.float32),
            )
            .compile()
        )
        book = CostBook()
        reg = MetricsRegistry()
        rec = book.record("drill.mm", comp, bucket="64", registry=reg)
        assert rec.flops == 2.0 * m * m * m
        assert rec.source == "compiled"
        assert rec.argument_bytes == 2 * m * m * 4
        assert rec.collectives == {}
        assert book.lookup("drill.mm", "64") is rec
        assert book.lookup("drill.mm", "128") is None
        snap = book.snapshot()
        assert snap["drill.mm.64"]["flops"] == rec.flops
        assert reg.snapshot()["gauges"]["xla.cost.drill.mm.64.flops"] == (
            rec.flops
        )

    def test_sharded_objective_collectives_vs_former_regex(
        self, devices
    ):
        """The cost book's collective counts on a feature-sharded
        objective pass, checked against bench.py's former inline regex
        on the same HLO. Since PR 5 ``count_collectives`` counts
        INSTRUCTIONS (opcode followed by its operand list) where the
        former regex also matched ``%all-reduce`` operand REFERENCES in
        fusion consumers — so the instruction count must never exceed
        the former count, must find the same op set, and must still see
        the sharded margin reduction."""
        import dataclasses as _dc
        import re as _re
        from collections import Counter as _Counter

        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from photon_ml_tpu.core.types import LabeledBatch
        from photon_ml_tpu.obs.xla_cost import CostBook
        from photon_ml_tpu.ops import sparse as sparse_ops
        from photon_ml_tpu.ops.losses import LOGISTIC_LOSS
        from photon_ml_tpu.ops.objective import GLMObjective
        from photon_ml_tpu.parallel import make_feature_mesh
        from photon_ml_tpu.parallel.mesh import DATA_AXIS, FEATURE_AXIS

        n, d, nnz, f_shards = 512, 1024, 8, 4
        rng = np.random.default_rng(3)
        rows = np.repeat(np.arange(n), nnz)
        cols = rng.integers(0, d, size=n * nnz)
        vals = rng.standard_normal(n * nnz).astype(np.float32)
        sf = sparse_ops.from_coo(rows, cols, vals, n, d, dtype=jnp.float32)
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        batch = LabeledBatch.create(sf, y, dtype=jnp.float32)
        mesh = make_feature_mesh(1, f_shards)
        blocked = sparse_ops.shard_columns(batch.features, f_shards)
        spec = NamedSharding(mesh, P(DATA_AXIS, FEATURE_AXIS, None))
        placed = sparse_ops.FeatureShardedSparse(
            indices=jax.device_put(blocked.indices, spec),
            values=jax.device_put(blocked.values, spec),
            d_shard=blocked.d_shard,
            d_orig=blocked.d_orig,
        )
        w0 = jax.device_put(
            jnp.zeros((f_shards * blocked.d_shard,), jnp.float32),
            NamedSharding(mesh, P(FEATURE_AXIS)),
        )
        pb = _dc.replace(batch, features=placed)
        obj = GLMObjective(loss=LOGISTIC_LOSS, l2_weight=1.0)
        with jax.set_mesh(mesh):
            comp = (
                jax.jit(lambda w, b: obj.value_and_grad(w, b))
                .lower(w0, pb)
                .compile()
            )
        rec = CostBook().record(
            "drill.sharded_pass", comp, bucket=f"F{f_shards}"
        )
        # bench.py's former inline regex, verbatim
        former = _Counter(
            m.split("-start")[0]
            for m in _re.findall(
                r"\b(all-reduce(?:-start)?|all-gather(?:-start)?|"
                r"all-to-all|reduce-scatter|collective-permute)\b",
                comp.as_text(),
            )
        )
        # instruction counting never exceeds occurrence counting, and
        # finds exactly the same collective op set
        assert set(rec.collectives) == set(former)
        for op, count in rec.collectives.items():
            assert 1 <= count <= former[op], (op, count, former[op])
        # the sharded margin reduction must actually be there
        assert rec.collectives.get("all-reduce", 0) >= 1
        # per-device memory fields come straight from memory_analysis
        ma = comp.memory_analysis()
        assert rec.argument_bytes == int(ma.argument_size_in_bytes)
        assert rec.temp_bytes == int(ma.temp_size_in_bytes)

    def test_per_span_mfu_within_10pct_of_hand_computed(self):
        """CostRecord.achieved arithmetic (bench.py's MFU): mfu /
        achieved_tflops over a measured window must match
        flops*passes/seconds against the named device's peaks (the v5e
        row of the one shared table)."""
        from photon_ml_tpu.obs.xla_cost import DEVICE_PEAKS, CostBook

        peaks = DEVICE_PEAKS["TPU v5 lite"]
        PEAK_FLOPS, PEAK_HBM_BPS = peaks.flops, peaks.hbm_bps

        book = CostBook()
        rec = book.record(
            "drill.analytic",
            None,
            bucket="b",
            analytic_flops=4.0e9,
            analytic_bytes=2.0e9,
            registry=MetricsRegistry(),
        )
        assert rec.source == "analytic"
        seconds, passes = 0.25, 23.0
        got = rec.achieved(seconds, passes=passes, peaks=peaks)
        hand_mfu = 4.0e9 * passes / seconds / PEAK_FLOPS
        hand_tflops = 4.0e9 * passes / seconds / 1e12
        hand_bps = 2.0e9 * passes / seconds
        assert abs(got["mfu"] - hand_mfu) <= 0.1 * hand_mfu
        assert abs(got["achieved_tflops"] - hand_tflops) <= 0.1 * hand_tflops
        assert abs(got["bytes_per_s"] - hand_bps) <= 0.1 * hand_bps
        assert (
            abs(got["hbm_util"] - hand_bps / PEAK_HBM_BPS)
            <= 0.1 * hand_bps / PEAK_HBM_BPS
        )

    def test_unlisted_device_gets_no_utilisation(self):
        """A device_kind missing from DEVICE_PEAKS (this CPU) gets rates
        but NO mfu/hbm_util — never another chip's peaks — and is an
        error for callers whose output is a utilisation figure."""
        from photon_ml_tpu.obs.xla_cost import (
            CostRecord,
            device_peaks,
            require_device_peaks,
        )

        assert device_peaks() is None  # the CPU test platform
        assert device_peaks("TPU v5 lite").hbm_bps == 819e9
        rec = CostRecord(
            name="drill", bucket="", flops=1e9, bytes_accessed=1e9
        )
        got = rec.achieved(0.5)
        assert {"flops", "achieved_tflops", "bytes_per_s"} <= set(got)
        assert "mfu" not in got and "hbm_util" not in got
        with pytest.raises(RuntimeError, match="no roofline peaks"):
            require_device_peaks()


# ---------------------------------------------------------------------------
# HBM telemetry
# ---------------------------------------------------------------------------


def _fake_hbm(monkeypatch, sequence):
    """Monkeypatch obs.device.read_memory_stats with a scripted device:
    each call pops the next bytes_in_use (last value repeats)."""
    from photon_ml_tpu.obs import device as device_mod

    state = {"i": 0}

    def fake(device=None):
        idx = min(state["i"], len(sequence) - 1)
        state["i"] += 1
        b = sequence[idx]
        return {
            "bytes_in_use": b,
            "peak_bytes_in_use": max(sequence[: idx + 1]),
        }

    monkeypatch.setattr(device_mod, "read_memory_stats", fake)
    return state


class TestHbmTelemetry:
    def test_unsupported_platform_is_noop(self, tmp_path):
        """CPU devices report no memory stats: watermark yields
        supported=False, the sampler starts no thread, sample_hbm
        returns empty — and nothing lands in registry or trace."""
        from photon_ml_tpu.obs.device import HbmSampler

        assert obs.read_memory_stats() is None  # this suite runs on CPU
        reg = MetricsRegistry()
        prev = obs.set_registry(reg)
        try:
            with obs.trace(str(tmp_path / "t")) as tracer:
                assert obs.sample_hbm() == {}
                with obs.hbm_watermark("drill") as wm:
                    pass
            sampler = HbmSampler(0.01).start()
            assert sampler._thread is None
            sampler.stop()
        finally:
            obs.set_registry(prev)
        assert not wm.supported
        assert wm.peak_bytes is None
        assert reg.names() == []
        assert not [
            e for e in tracer.events() if e["name"].startswith("hbm")
        ]

    def test_watermark_records_peak_and_delta(self, monkeypatch, tmp_path):
        _fake_hbm(monkeypatch, [1000, 5000])
        reg = MetricsRegistry()
        prev = obs.set_registry(reg)
        try:
            with obs.trace(str(tmp_path / "t")) as tracer:
                with obs.hbm_watermark("drill.phase") as wm:
                    pass
        finally:
            obs.set_registry(prev)
        assert wm.supported
        assert wm.before_bytes == 1000
        assert wm.after_bytes == 5000
        assert wm.delta_bytes == 4000
        assert wm.peak_bytes == 5000
        snap = reg.snapshot()["gauges"]
        assert snap["hbm.drill.phase.peak_bytes"] == 5000
        assert snap["hbm.drill.phase.delta_bytes"] == 4000
        events = [
            e for e in tracer.events() if e["name"] == "hbm.watermark"
        ]
        assert len(events) == 1
        assert events[0]["args"]["label"] == "drill.phase"

    def test_sample_emits_counter_track(self, monkeypatch, tmp_path):
        _fake_hbm(monkeypatch, [2048, 4096, 3072])
        reg = MetricsRegistry()
        prev = obs.set_registry(reg)
        try:
            with obs.trace(str(tmp_path / "t")) as tracer:
                for _ in range(3):
                    obs.sample_hbm()
        finally:
            obs.set_registry(prev)
        counters = [e for e in tracer.events() if e["ph"] == "C"]
        # 8 virtual devices share the faked reader; device 0's track
        # carries the scripted sequence in order
        d0 = [e for e in counters if e["name"] == "hbm.d0"]
        assert [e["args"]["bytes_in_use"] for e in d0[:3]] != []
        assert reg.snapshot()["gauges"]["hbm.d0.peak_bytes_in_use"] >= 4096
        # counter events are valid Chrome trace citizens
        for e in counters:
            assert set(e) >= {"ph", "name", "pid", "ts", "args"}

    def test_sampler_thread_samples_periodically(self, monkeypatch):
        from photon_ml_tpu.obs import device as device_mod

        state = _fake_hbm(monkeypatch, [1, 2, 3, 4, 5, 6, 7, 8])
        reg = MetricsRegistry()
        sampler = device_mod.HbmSampler(0.01, registry=reg).start()
        assert sampler._thread is not None
        import time as _time

        _time.sleep(0.15)
        sampler.stop()
        assert sampler._thread is None
        assert state["i"] > 2  # start probe + periodic + final samples
        assert "hbm.d0.bytes_in_use" in reg.snapshot()["gauges"]


# ---------------------------------------------------------------------------
# Regression sentinel
# ---------------------------------------------------------------------------


def _bench_record(**overrides):
    """A synthetic parsed BENCH record with stable metrics."""
    extra = {
        "mfu": 0.001,
        "hbm_util": 0.2,
        "game_cd_iters_per_s": 10.0,
        "sparse_zipf_s": 3.5,
        "rtt_ms": 100.0,
        "transfer_gb": 0.512,
    }
    extra.update(overrides)
    return {
        "metric": "drill",
        "value": 0.13,
        "unit": "s",
        "vs_baseline": 20.0,
        "extra": extra,
    }


class TestSentinel:
    def _history(self, n=4, jitter=0.02, seed=0):
        rng = np.random.default_rng(seed)
        out = []
        for _ in range(n):
            f = 1.0 + float(rng.uniform(-jitter, jitter))
            out.append(
                _bench_record(
                    mfu=0.001 * f,
                    hbm_util=0.2 * f,
                    game_cd_iters_per_s=10.0 * f,
                    sparse_zipf_s=3.5 / f,
                )
            )
        return out

    def test_thirty_pct_regression_flagged(self):
        from photon_ml_tpu.obs import sentinel as s

        hist = [s.flatten_record(r) for r in self._history()]
        baselines = s.fit_baselines(hist)
        degraded = s.flatten_record(
            _bench_record(
                mfu=0.0007,  # -30% (higher is better)
                sparse_zipf_s=4.55,  # +30% (lower is better)
            )
        )
        regs = s.check_record(degraded, baselines)
        names = {r.metric for r in regs}
        assert "extra.mfu" in names
        assert "extra.sparse_zipf_s" in names
        # the untouched metrics pass
        assert "extra.game_cd_iters_per_s" not in names

    def test_within_band_noise_passes(self):
        from photon_ml_tpu.obs import sentinel as s

        hist = [s.flatten_record(r) for r in self._history()]
        baselines = s.fit_baselines(hist)
        noisy = s.flatten_record(
            _bench_record(
                mfu=0.00092,  # -8%: inside the 25% floor
                game_cd_iters_per_s=10.9,  # improvement
                sparse_zipf_s=3.9,  # +11%
            )
        )
        assert s.check_record(noisy, baselines) == []

    def test_new_and_missing_metrics_tolerated(self):
        from photon_ml_tpu.obs import sentinel as s

        hist = [s.flatten_record(r) for r in self._history()]
        baselines = s.fit_baselines(hist)
        current = s.flatten_record(
            _bench_record(brand_new_iters_per_s=5.0)
        )
        del current["extra.hbm_util"]  # metric vanished: tolerated
        assert s.check_record(current, baselines) == []

    def test_direction_awareness(self):
        from photon_ml_tpu.obs import sentinel as s

        assert s.metric_direction("extra.mfu") > 0
        assert s.metric_direction("extra.game_cd_iters_per_s") > 0
        assert s.metric_direction("vs_baseline") > 0
        assert s.metric_direction("extra.sparse_zipf_auc_device") > 0
        assert s.metric_direction("extra.sparse_zipf_s") < 0
        assert s.metric_direction("value") < 0
        assert (
            s.metric_direction(
                "extra.sparse_fs_scaling.2.collectives.all-reduce"
            )
            < 0
        )
        # environment noise is untracked
        assert s.metric_direction("extra.rtt_ms") == 0
        assert s.metric_direction("extra.rtt_ms_max") == 0
        assert s.metric_direction("extra.transfer_gb") == 0
        assert s.metric_direction("extra.phase_s.glm_dense") == 0
        assert s.metric_direction("extra.metrics.counters.game.passes") == 0

    def test_untracked_metric_regression_ignored(self):
        from photon_ml_tpu.obs import sentinel as s

        hist = [s.flatten_record(r) for r in self._history()]
        baselines = s.fit_baselines(hist)
        current = s.flatten_record(_bench_record(rtt_ms=100000.0))
        assert s.check_record(current, baselines) == []

    def test_volatile_history_widens_band(self):
        """A metric that legitimately swung 10x across rounds must not
        flag a 30% move — the MAD term widens its band."""
        from photon_ml_tpu.obs import sentinel as s

        hist = [
            s.flatten_record(_bench_record(game_cd_iters_per_s=v))
            for v in (1.2, 2.5, 9.8, 10.1)
        ]
        baselines = s.fit_baselines(hist)
        b = baselines["extra.game_cd_iters_per_s"]
        assert b.tol > 1.0  # band far wider than the 25% floor
        current = s.flatten_record(_bench_record(game_cd_iters_per_s=4.0))
        assert "extra.game_cd_iters_per_s" not in {
            r.metric for r in s.check_record(current, baselines)
        }

    def test_cli_end_to_end(self, tmp_path):
        """benchmarks/regression_sentinel.py on synthetic history files:
        exit 0 on the healthy newest record, nonzero on a degraded one,
        2 when there is nothing to gate."""
        import importlib.util
        import sys as _sys

        spec = importlib.util.spec_from_file_location(
            "regression_sentinel_drill",
            os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "benchmarks",
                "regression_sentinel.py",
            ),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)

        for i, rec in enumerate(self._history(4)):
            with open(tmp_path / f"BENCH_r{i:02d}.json", "w") as f:
                json.dump({"n": i, "rc": 0, "parsed": rec}, f)
        glob_pat = str(tmp_path / "BENCH_r*.json")
        assert mod.main(["--history", glob_pat]) == 0

        bad = _bench_record(mfu=0.0006, sparse_zipf_s=5.0)
        with open(tmp_path / "degraded.json", "w") as f:
            json.dump(bad, f)  # bare bench.py record form
        assert (
            mod.main(
                ["--history", glob_pat, "--current",
                 str(tmp_path / "degraded.json")]
            )
            == 1
        )
        assert (
            mod.main(["--history", str(tmp_path / "nothing_*.json")]) == 2
        )
        _sys.modules.pop("regression_sentinel_drill", None)
