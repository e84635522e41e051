"""Distributed observability drills (docs/OBSERVABILITY.md): pod trace
identity + shard merging (adversarial inputs included), the collective
profiler, request-scoped serving traces + SLO tracking, the crash flight
recorder, the tracer flush guard, and the scaling-efficiency sentinel
gate. Everything CPU-only; the one multi-process drill spawns two plain
(jax-free) subprocesses — shard production and merging need no
collectives, so it runs on every jax line tier-1 supports."""

import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.obs import dist as obs_dist
from photon_ml_tpu.obs import sentinel as obs_sentinel
from photon_ml_tpu.obs.metrics import MetricsRegistry

pytestmark = pytest.mark.obs


@pytest.fixture(autouse=True)
def _clean_obs_globals():
    """Every drill here leaves identity / tracer / recorder pristine."""
    yield
    obs.uninstall_flight_recorder()
    obs.set_tracer(None)
    obs_dist._reset_identity_for_tests()


# ---------------------------------------------------------------------------
# process identity + tracer stamping
# ---------------------------------------------------------------------------


class TestProcessIdentity:
    def test_default_single_process(self):
        assert obs.process_identity() == (0, 1)
        assert obs.host_metric_prefix() == ""

    def test_explicit_identity(self):
        obs.set_process_identity(2, 4)
        assert obs.process_identity() == (2, 4)
        assert obs.host_metric_prefix() == "host.2."
        assert obs.host_metric_prefix(index=0) == "host.0."

    def test_env_fallback(self, monkeypatch):
        monkeypatch.setenv("PHOTON_PROCESS_INDEX", "1")
        monkeypatch.setenv("PHOTON_PROCESS_COUNT", "3")
        assert obs.process_identity() == (1, 3)

    def test_bad_identity_rejected(self):
        with pytest.raises(ValueError):
            obs.set_process_identity(3, 2)
        with pytest.raises(ValueError):
            obs.set_process_identity(0, 0)

    def test_tracer_stamps_identity(self, tmp_path):
        obs.set_process_identity(1, 2)
        tdir = str(tmp_path / "t")
        with obs.trace(tdir):
            with obs.span("w"):
                pass
        doc = json.load(open(os.path.join(tdir, "trace.json")))
        assert doc["metadata"]["process_index"] == 1
        assert doc["metadata"]["process_count"] == 2
        spans = [e for e in doc["traceEvents"] if e["name"] == "w"]
        # the Chrome pid IS the process index: a distinct Perfetto track
        assert spans[0]["pid"] == 1
        meta = {
            e["name"]: e["args"]
            for e in doc["traceEvents"]
            if e["ph"] == "M"
        }
        assert "host.1" in meta["process_name"]["name"]
        assert meta["process_sort_index"]["sort_index"] == 1
        # JSONL records carry the host field
        recs = [
            json.loads(line)
            for line in open(os.path.join(tdir, "events.jsonl"))
        ]
        assert all(r["host"] == 1 for r in recs)


# ---------------------------------------------------------------------------
# tracer flush guard (the up-to-63-span loss window)
# ---------------------------------------------------------------------------


class TestTracerFlushGuard:
    def test_close_flushes_buffered_spans(self, tmp_path):
        tdir = str(tmp_path / "t")
        tracer = obs.Tracer(tdir)
        prev = obs.set_tracer(tracer)
        try:
            for i in range(5):  # < _FLUSH_EVERY: all buffered
                with obs.span("s", i=i):
                    pass
        finally:
            obs.set_tracer(prev)
        tracer.close()
        lines = open(os.path.join(tdir, "events.jsonl")).readlines()
        assert len(lines) == 5

    def test_flush_without_close(self, tmp_path):
        tdir = str(tmp_path / "t")
        tracer = obs.Tracer(tdir)
        prev = obs.set_tracer(tracer)
        try:
            with obs.span("s"):
                pass
            tracer.flush()
            lines = open(os.path.join(tdir, "events.jsonl")).readlines()
            assert len(lines) == 1  # visible pre-close
        finally:
            obs.set_tracer(prev)
            tracer.close()

    def test_graceful_shutdown_flushes_tracer(self, tmp_path):
        from photon_ml_tpu.resilience import GracefulShutdown

        tdir = str(tmp_path / "t")
        tracer = obs.Tracer(tdir)
        prev = obs.set_tracer(tracer)
        try:
            for i in range(4):
                with obs.span("pre-sigterm", i=i):
                    pass
            GracefulShutdown().request(signal.SIGTERM)
            lines = open(os.path.join(tdir, "events.jsonl")).readlines()
            # 4 buffered spans + the flushed-immediately preemption event
            assert len(lines) >= 5
            names = [json.loads(line)["name"] for line in lines]
            assert names.count("pre-sigterm") == 4
        finally:
            obs.set_tracer(prev)
            tracer.close()


# ---------------------------------------------------------------------------
# trace-shard merging
# ---------------------------------------------------------------------------


def _make_shard(tmp_path, idx, count=2, spans=("a", "b"), skew_us=0.0,
                sync_id="startup"):
    """Build one real per-process shard directory via the Tracer."""
    obs.set_process_identity(idx, count)
    d = str(tmp_path / f"shard{idx}")
    tracer = obs.Tracer(d, process_name="drill")
    if skew_us:
        # simulate a host whose monotonic epoch started earlier: all its
        # raw timestamps are shifted late by skew_us
        tracer._epoch_ns -= int(skew_us * 1e3)
    prev = obs.set_tracer(tracer)
    try:
        if sync_id is not None:
            obs_dist.emit_clock_sync(sync_id)
        for name in spans:
            with obs.span(f"{name}.{idx}"):
                pass
    finally:
        obs.set_tracer(prev)
    tracer.export()
    tracer.close()
    obs_dist._reset_identity_for_tests()
    return d


def _assert_perfetto_parseable(doc):
    """The invariants Perfetto / chrome://tracing need: a traceEvents
    list of objects each carrying ph/name/pid/tid/ts, JSON-serializable,
    ts-sorted among non-metadata events."""
    assert isinstance(doc["traceEvents"], list)
    for ev in doc["traceEvents"]:
        for key in ("ph", "name", "pid", "tid", "ts"):
            assert key in ev, ev
    json.dumps(doc)  # round-trips
    ts = [e["ts"] for e in doc["traceEvents"] if e["ph"] != "M"]
    assert ts == sorted(ts)
    assert min(ts) >= 0.0


class TestMergeTraceShards:
    def test_two_shards_distinct_pid_tracks(self, tmp_path):
        dirs = [_make_shard(tmp_path, i) for i in range(2)]
        docs = []
        for d in dirs:
            doc, warn = obs_dist.load_trace_shard(d)
            assert warn is None
            docs.append((doc, d))
        merged, info = obs_dist.merge_trace_shards(docs)
        _assert_perfetto_parseable(merged)
        assert info["shards"] == 2 and not info["warnings"]
        assert info["aligned_by"] == "sync"
        pids = {e["pid"] for e in merged["traceEvents"]}
        assert pids == {0, 1}
        names = {
            e["args"]["name"]
            for e in merged["traceEvents"]
            if e["ph"] == "M" and e["name"] == "process_name"
        }
        assert any("host.0" in n for n in names)
        assert any("host.1" in n for n in names)
        syncs = [
            e for e in merged["traceEvents"] if e["name"] == "clock.sync"
        ]
        assert len(syncs) == 2

    def test_skewed_clocks_align_at_sync(self, tmp_path):
        # shard 1's raw timestamps run 5 SECONDS late; the barrier sync
        # event must pull them back onto shard 0's timeline
        d0 = _make_shard(tmp_path, 0)
        d1 = _make_shard(tmp_path, 1, skew_us=5e6)
        docs = [
            (obs_dist.load_trace_shard(d)[0], d) for d in (d0, d1)
        ]
        raw1 = [
            e
            for e in docs[1][0]["traceEvents"]
            if e["name"] == "clock.sync"
        ][0]
        assert raw1["ts"] > 4e6  # the skew is really in the raw shard
        merged, info = obs_dist.merge_trace_shards(docs)
        _assert_perfetto_parseable(merged)
        assert info["aligned_by"] == "sync"
        sync_ts = {
            e["pid"]: e["ts"]
            for e in merged["traceEvents"]
            if e["name"] == "clock.sync"
        }
        # both hosts' sync markers land within the real emission jitter
        # (<1s), not the injected 5s skew
        assert abs(sync_ts[0] - sync_ts[1]) < 1e6

    def test_missing_shard_skipped_with_warning(self, tmp_path):
        d0 = _make_shard(tmp_path, 0)
        doc0, _ = obs_dist.load_trace_shard(d0)
        missing, warn = obs_dist.load_trace_shard(
            str(tmp_path / "nope")
        )
        assert missing is None and "unreadable" in warn
        merged, info = obs_dist.merge_trace_shards([(doc0, d0)])
        _assert_perfetto_parseable(merged)
        assert info["shards"] == 1

    def test_truncated_shard_skipped(self, tmp_path):
        d0 = _make_shard(tmp_path, 0)
        d1 = _make_shard(tmp_path, 1)
        # tear shard 1 mid-file (the crash the merge is investigating)
        p1 = os.path.join(d1, "trace.json")
        blob = open(p1).read()
        with open(p1, "w") as f:
            f.write(blob[: len(blob) // 2])
        doc1, warn = obs_dist.load_trace_shard(d1)
        assert doc1 is None and "truncated" in warn
        doc0, _ = obs_dist.load_trace_shard(d0)
        merged, info = obs_dist.merge_trace_shards([(doc0, d0)])
        _assert_perfetto_parseable(merged)

    def test_duplicate_events_deduped(self, tmp_path):
        d0 = _make_shard(tmp_path, 0)
        doc0, _ = obs_dist.load_trace_shard(d0)
        # duplicate every event (a shard read twice / duplicated span
        # ids); the merge must collapse them
        doubled = dict(doc0)
        doubled["traceEvents"] = list(doc0["traceEvents"]) + [
            dict(e) for e in doc0["traceEvents"]
        ]
        merged, info = obs_dist.merge_trace_shards([(doubled, d0)])
        _assert_perfetto_parseable(merged)
        assert info["duplicates_dropped"] > 0
        names = [
            e["name"] for e in merged["traceEvents"] if e["ph"] != "M"
        ]
        assert len(names) == len(
            [e for e in doc0["traceEvents"] if e["ph"] != "M"]
        )

    def test_identical_spans_with_distinct_request_ids_both_survive(
        self, tmp_path
    ):
        """PR-17 regression drill: two replicas' batchers can emit
        serving spans with IDENTICAL (name, pid, tid, ts, dur) — the
        replication symmetry — but distinct namespaced request ids.
        The merge dedup key includes args.request_id, so these are two
        real requests, not one duplicated event."""
        d0 = _make_shard(tmp_path, 0)
        doc0, _ = obs_dist.load_trace_shard(d0)
        twin = {
            "ph": "X", "name": "serving.request", "cat": "serving",
            "pid": 7, "tid": 1, "ts": 100.0, "dur": 5.0,
        }
        doc = dict(doc0)
        doc["traceEvents"] = list(doc0["traceEvents"]) + [
            # replica 1's batcher: instance_id 1 -> rid (1 << 32) | 1
            dict(twin, args={"request_id": (1 << 32) | 1}),
            # replica 2's batcher: same seq, different namespace
            dict(twin, args={"request_id": (2 << 32) | 1}),
            # a TRUE duplicate of the first (same request seen twice)
            dict(twin, args={"request_id": (1 << 32) | 1}),
        ]
        merged, info = obs_dist.merge_trace_shards([(doc, d0)])
        _assert_perfetto_parseable(merged)
        assert info["duplicates_dropped"] == 1
        rids = [
            e["args"]["request_id"]
            for e in merged["traceEvents"]
            if e.get("name") == "serving.request"
        ]
        assert sorted(rids) == [(1 << 32) | 1, (2 << 32) | 1]

    def test_no_sync_falls_back_to_epoch(self, tmp_path):
        dirs = [
            _make_shard(tmp_path, i, sync_id=None) for i in range(2)
        ]
        docs = [
            (obs_dist.load_trace_shard(d)[0], d) for d in dirs
        ]
        merged, info = obs_dist.merge_trace_shards(docs)
        _assert_perfetto_parseable(merged)
        assert info["aligned_by"] == "epoch_unix"

    def test_events_jsonl_merge_tolerates_torn_lines(self, tmp_path):
        dirs = [_make_shard(tmp_path, i) for i in range(2)]
        ev1 = os.path.join(dirs[1], "events.jsonl")
        with open(ev1, "a") as f:
            f.write('{"kind": "span", "name": "torn-mid-wr')
        records, warns = obs_dist.merge_events_shards(
            [(dirs[0], 0), (dirs[1], 1)]
        )
        assert any("torn" in w for w in warns)
        times = [r["time_unix"] for r in records]
        assert times == sorted(times)
        assert {r["host"] for r in records} == {0, 1}

    def test_metrics_merge_host_prefix_and_pod_sums(self):
        snaps = [
            ({"counters": {"io.bytes": 10.0}, "gauges": {"g": 1.0},
              "histograms": {}}, 0),
            ({"counters": {"io.bytes": 32.0}, "gauges": {"g": 2.0},
              "histograms": {}}, 1),
        ]
        merged = obs_dist.merge_metrics_shards(snaps)
        assert merged["counters"]["host.0.io.bytes"] == 10.0
        assert merged["counters"]["host.1.io.bytes"] == 32.0
        assert merged["counters"]["pod.io.bytes"] == 42.0
        assert merged["gauges"]["host.1.g"] == 2.0


# ---------------------------------------------------------------------------
# 2-process CPU run -> shards -> photon-obs merge (acceptance)
# ---------------------------------------------------------------------------

_CHILD = r"""
import os, sys
from photon_ml_tpu import obs

shard_dir = sys.argv[1]
with obs.observe(trace_dir=shard_dir):
    with obs.span("child.work", step=1):
        pass
    obs.registry().inc("child.items", 3)
    obs.registry().dump(os.path.join(shard_dir, "metrics.json"))
"""


class TestTwoProcessMergeE2E:
    def test_two_process_shards_merge_to_pod_trace(self, tmp_path):
        """The acceptance drill: a 2-process CPU run (separate host
        processes, each with its own obs envelope and pod identity from
        the environment) produces per-process shards that `photon-obs
        merge` combines into one valid Chrome trace with distinct pid
        tracks and clock-aligned sync markers."""
        child = str(tmp_path / "child.py")
        with open(child, "w") as f:
            f.write(_CHILD)
        dirs = []
        procs = []
        for pid in range(2):
            d = str(tmp_path / f"host{pid}")
            dirs.append(d)
            env = dict(os.environ)
            env["PHOTON_PROCESS_INDEX"] = str(pid)
            env["PHOTON_PROCESS_COUNT"] = "2"
            env["PYTHONPATH"] = os.getcwd()
            env["JAX_PLATFORMS"] = "cpu"
            procs.append(
                subprocess.Popen(
                    [sys.executable, child, d],
                    env=env,
                    stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE,
                    text=True,
                )
            )
        for pid, proc in enumerate(procs):
            out, err = proc.communicate(timeout=300)
            assert proc.returncode == 0, f"child {pid}\n{out}\n{err}"

        from photon_ml_tpu.cli import obs_tools

        out_dir = str(tmp_path / "pod")
        rc = obs_tools.main(["merge", "--out", out_dir] + dirs)
        assert rc == 0
        doc = json.load(open(os.path.join(out_dir, "trace.json")))
        _assert_perfetto_parseable(doc)
        work = [
            e for e in doc["traceEvents"] if e["name"] == "child.work"
        ]
        assert {e["pid"] for e in work} == {0, 1}
        syncs = [
            e for e in doc["traceEvents"] if e["name"] == "clock.sync"
        ]
        assert len(syncs) >= 2
        assert {
            e["args"]["process_index"] for e in syncs
        } == {0, 1}
        # both children ran within seconds of each other: aligned sync
        # markers must be near-coincident on the merged timeline
        ts = sorted(e["ts"] for e in syncs)
        assert ts[-1] - ts[0] < 120e6
        # host-tagged events + pod metric sums merged alongside
        recs = [
            json.loads(line)
            for line in open(os.path.join(out_dir, "events.jsonl"))
        ]
        assert {r["host"] for r in recs} == {0, 1}
        metrics = json.load(open(os.path.join(out_dir, "metrics.json")))
        assert metrics["counters"]["pod.child.items"] == 6.0
        assert metrics["counters"]["host.1.child.items"] == 3.0

    def test_merge_cli_no_readable_shards(self, tmp_path):
        from photon_ml_tpu.cli import obs_tools

        rc = obs_tools.main(
            ["merge", "--out", str(tmp_path / "o"),
             str(tmp_path / "missing")]
        )
        assert rc == 2


# ---------------------------------------------------------------------------
# collective profiler
# ---------------------------------------------------------------------------


class TestCollectiveProfiler:
    def test_record_collective_metrics(self):
        reg = MetricsRegistry()
        obs.record_collective(
            "allgather_host", mesh_width=4, nbytes=1024, wall_s=0.002,
            registry=reg,
        )
        obs.record_collective(
            "allgather_host", mesh_width=4, nbytes=1024, registry=reg
        )
        snap = reg.snapshot()
        key = "collective.allgather_host.w4"
        assert snap["counters"][f"{key}.count"] == 2
        assert snap["counters"][f"{key}.bytes"] == 2048
        assert snap["histograms"][f"{key}.wall_ms"]["count"] == 1

    def test_collective_span_emits_span_and_wall(self, tmp_path):
        reg = MetricsRegistry()
        tdir = str(tmp_path / "t")
        with obs.trace(tdir):
            with obs.collective_span(
                "drill", mesh_width=2, nbytes=64, registry=reg
            ):
                pass
        doc = json.load(open(os.path.join(tdir, "trace.json")))
        spans = [
            e for e in doc["traceEvents"]
            if e["name"] == "collective.drill"
        ]
        assert spans and spans[0]["args"]["mesh_width"] == 2
        snap = reg.snapshot()
        assert snap["histograms"]["collective.drill.w2.wall_ms"][
            "count"
        ] == 1

    def test_bucketed_reduction_traced_note(self, rng, devices):
        """Tracing an objective pass over a feature-sharded design books
        the bucketed all-reduce's payload geometry under
        collective.traced.matvec_and_feature_dots.w<F>.*."""
        import jax
        import jax.numpy as jnp

        from photon_ml_tpu.ops import sparse as sparse_ops

        reg = MetricsRegistry()
        prev = obs.set_registry(reg)
        try:
            n, d, k, f_shards = 64, 32, 4, 2
            sf = sparse_ops.SparseFeatures(
                indices=jnp.asarray(
                    rng.integers(0, d, size=(n, k)).astype(np.int32)
                ),
                values=jnp.asarray(
                    rng.standard_normal((n, k)).astype(np.float32)
                ),
                d=d,
            )
            blocked = sparse_ops.shard_columns(sf, f_shards)
            w = jnp.zeros((f_shards * blocked.d_shard,), jnp.float32)

            def fn(w, x):
                z, (dot,) = sparse_ops.matvec_and_feature_dots(
                    x, w, [(w, w)]
                )
                return z.sum() + dot

            jax.jit(fn).lower(w, blocked)  # trace (no execution needed)
            snap = reg.snapshot()
            key = "collective.traced.matvec_and_feature_dots.w2"
            assert snap["counters"][f"{key}.count"] >= 1
            assert snap["counters"][f"{key}.bytes"] > 0
        finally:
            obs.set_registry(prev)

    def test_eager_shard_map_psum_profiled(self, rng, devices, tmp_path):
        """An EAGER shard-mapped value+grad under an active tracer
        records a collective.psum.value_and_grad span + wall metrics;
        the jitted path stays raw (numerics identical either way)."""
        import jax
        import jax.numpy as jnp

        from photon_ml_tpu.core.types import LabeledBatch
        from photon_ml_tpu.ops.losses import LOGISTIC_LOSS
        from photon_ml_tpu.ops.objective import GLMObjective
        from photon_ml_tpu.parallel import (
            make_mesh,
            shard_batch,
            shard_map_value_and_grad,
        )

        x = rng.normal(size=(64, 6))
        y = (rng.uniform(size=64) < 0.5).astype(float)
        batch = LabeledBatch.create(x, y, dtype=jnp.float64)
        obj = GLMObjective(loss=LOGISTIC_LOSS, l2_weight=0.5)
        w = jnp.asarray(rng.normal(size=6))
        mesh = make_mesh()
        sharded = shard_batch(batch, mesh)
        vg = shard_map_value_and_grad(obj, mesh)

        reg = MetricsRegistry()
        prev = obs.set_registry(reg)
        tdir = str(tmp_path / "t")
        try:
            with obs.trace(tdir):
                v_eager, g_eager = vg(w, sharded)
            v_jit, g_jit = jax.jit(vg)(w, sharded)
        finally:
            obs.set_registry(prev)
        np.testing.assert_allclose(
            float(v_eager), float(v_jit), rtol=1e-12
        )
        np.testing.assert_allclose(
            np.asarray(g_eager), np.asarray(g_jit), rtol=1e-10
        )
        snap = reg.snapshot()
        key = f"collective.psum.value_and_grad.w{mesh.shape['data']}"
        assert snap["counters"][f"{key}.count"] == 1
        assert snap["counters"][f"{key}.bytes"] == (6 + 1) * 8
        doc = json.load(open(os.path.join(tdir, "trace.json")))
        assert any(
            e["name"] == "collective.psum.value_and_grad"
            for e in doc["traceEvents"]
        )

    def test_untraced_eager_call_records_nothing(self, rng, devices):
        import jax.numpy as jnp

        from photon_ml_tpu.core.types import LabeledBatch
        from photon_ml_tpu.ops.losses import LOGISTIC_LOSS
        from photon_ml_tpu.ops.objective import GLMObjective
        from photon_ml_tpu.parallel import (
            make_mesh,
            shard_batch,
            shard_map_value_and_grad,
        )

        x = rng.normal(size=(32, 4))
        y = (rng.uniform(size=32) < 0.5).astype(float)
        batch = LabeledBatch.create(x, y, dtype=jnp.float64)
        obj = GLMObjective(loss=LOGISTIC_LOSS)
        mesh = make_mesh()
        vg = shard_map_value_and_grad(obj, mesh)
        reg = MetricsRegistry()
        prev = obs.set_registry(reg)
        try:
            vg(jnp.zeros(4), shard_batch(batch, mesh))
        finally:
            obs.set_registry(prev)
        assert not reg.names("collective.")


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_ring_is_bounded(self):
        rec = obs.FlightRecorder(capacity=4)
        for i in range(10):
            rec.note({"kind": "span", "i": i})
        records = rec.records()
        assert len(records) == 4
        assert [r["i"] for r in records] == [6, 7, 8, 9]
        assert records[-1]["seq"] == 10

    def test_dump_contains_final_unflushed_spans(self, tmp_path):
        """The acceptance contract: spans still sitting in the tracer's
        64-deep JSONL buffer are present in the flight dump."""
        tdir = str(tmp_path / "t")
        tracer = obs.Tracer(tdir)
        prev = obs.set_tracer(tracer)
        try:
            rec = obs.install_flight_recorder(
                capacity=64, flight_dir=str(tmp_path)
            )
            for i in range(3):
                with obs.span("unflushed", i=i):
                    pass
            # nothing on disk yet: below the flush threshold
            assert open(
                os.path.join(tdir, "events.jsonl")
            ).read() == ""
            path = obs.flight_dump("test")
        finally:
            obs.set_tracer(prev)
            tracer.close()
        assert path is not None and os.path.basename(path) == (
            "flight-test.json"
        )
        payload = json.load(open(path))
        names = [
            r.get("name") for r in payload["records"]
            if r.get("kind") == "span"
        ]
        assert names == ["unflushed"] * 3
        assert payload["reason"] == "test"
        assert "metrics" in payload and "counters" in payload["metrics"]

    def test_repeat_dump_does_not_clobber(self, tmp_path):
        obs.install_flight_recorder(flight_dir=str(tmp_path))
        p1 = obs.flight_dump("divergence")
        p2 = obs.flight_dump("divergence")
        assert p1 != p2 and os.path.exists(p1) and os.path.exists(p2)

    def test_metrics_delta_records(self):
        reg = MetricsRegistry()
        prev = obs.set_registry(reg)
        try:
            rec = obs.FlightRecorder(capacity=16)
            reg.inc("drill.count", 2)
            rec.sample_metrics()
            reg.inc("drill.count", 3)
            rec.sample_metrics()
            rec.sample_metrics()  # no movement: no record
        finally:
            obs.set_registry(prev)
        deltas = [
            r for r in rec.records() if r["kind"] == "metrics_delta"
        ]
        assert len(deltas) == 2
        assert deltas[0]["changed"]["drill.count"] == 2
        assert deltas[1]["changed"]["drill.count"] == 3

    def test_sigterm_dumps_flight(self, tmp_path):
        from photon_ml_tpu.resilience import GracefulShutdown

        tracer = obs.Tracer(None, keep_events=False)
        prev = obs.set_tracer(tracer)
        try:
            obs.install_flight_recorder(flight_dir=str(tmp_path))
            with obs.span("about-to-die"):
                pass
            GracefulShutdown().request(signal.SIGTERM)
        finally:
            obs.set_tracer(prev)
        files = [
            f for f in os.listdir(str(tmp_path))
            if f.startswith("flight-preemption")
        ]
        assert len(files) == 1
        payload = json.load(open(os.path.join(str(tmp_path), files[0])))
        names = [r.get("name") for r in payload["records"]]
        assert "about-to-die" in names
        assert "resilience.preemption_requested" in names

    def test_divergence_rollback_dumps_flight(self, rng, tmp_path):
        """A forced divergence (injected NaN under the guard) leaves a
        flight-divergence.json with the spans leading into it."""
        from photon_ml_tpu.resilience import FaultSpec, inject
        from test_game import build_game, make_mixed_effects_data

        data, _, n_users = make_mixed_effects_data(
            rng, n_users=4, rows_per_user=10
        )
        cd = build_game(data, n_users)
        tdir = str(tmp_path / "t")
        with obs.observe(trace_dir=tdir, flight_dir=str(tmp_path)):
            with inject(
                FaultSpec(
                    "descent.update", "corrupt", nth=4, count=1,
                    key="per-user",
                )
            ):
                model, hist = cd.run(
                    num_iterations=3, divergence_guard=True
                )
        assert "recovered" in [h.event for h in hist]
        files = [
            f for f in os.listdir(str(tmp_path))
            if f.startswith("flight-divergence")
        ]
        assert len(files) == 1
        payload = json.load(open(os.path.join(str(tmp_path), files[0])))
        names = [r.get("name") for r in payload["records"]]
        assert "resilience.rollback" in names
        assert any(n == "game.update" for n in names)

    def test_crash_excepthook_dumps_flight(self, tmp_path):
        obs.install_flight_recorder(flight_dir=str(tmp_path))
        hook = sys.excepthook
        try:
            raise RuntimeError("boom")
        except RuntimeError:
            info = sys.exc_info()
        # invoke the chained hook directly (raising for real would kill
        # the test runner); it must dump then delegate
        hook(*info)
        files = [
            f for f in os.listdir(str(tmp_path))
            if f.startswith("flight-crash")
        ]
        assert len(files) == 1
        payload = json.load(open(os.path.join(str(tmp_path), files[0])))
        crash = [
            r for r in payload["records"] if r.get("name") == "crash"
        ]
        assert crash and "boom" in crash[0]["exception"]

    def test_crash_inside_observe_dumps_flight(self, tmp_path):
        """An unhandled exception propagating through the observe()
        envelope must leave flight-crash.json: the ExitStack uninstalls
        the recorder during unwind BEFORE sys.excepthook ever runs, so
        the envelope itself dumps on the way out."""
        tdir = str(tmp_path / "t")
        with pytest.raises(RuntimeError, match="mid-run boom"):
            with obs.observe(trace_dir=tdir):
                with obs.span("doomed.work"):
                    pass
                raise RuntimeError("mid-run boom")
        files = [
            f for f in os.listdir(tdir) if f.startswith("flight-crash")
        ]
        assert len(files) == 1
        payload = json.load(open(os.path.join(tdir, files[0])))
        names = [r.get("name") for r in payload["records"]]
        assert "doomed.work" in names
        crash = [r for r in payload["records"] if r.get("name") == "crash"]
        assert crash and "mid-run boom" in crash[0]["exception"]

    def test_deliberate_exit_inside_observe_no_crash_dump(self, tmp_path):
        """sys.exit() through the envelope is a deliberate exit, not a
        crash — no flight-crash.json noise on normal CLI teardown."""
        tdir = str(tmp_path / "t")
        with pytest.raises(SystemExit):
            with obs.observe(trace_dir=tdir):
                raise SystemExit(1)
        assert not [
            f for f in os.listdir(tdir) if f.startswith("flight-")
        ]

    def test_uninstall_restores_excepthook(self):
        before = sys.excepthook
        obs.install_flight_recorder()
        assert sys.excepthook is not before
        obs.uninstall_flight_recorder()
        assert sys.excepthook is before
        assert obs.flight_dump("noop") is None


# ---------------------------------------------------------------------------
# request-scoped serving traces + SLO
# ---------------------------------------------------------------------------


class TestServingRequestTraces:
    def _run_batcher(self, tmp_path, score_fn=None, slo=None, n=6):
        from photon_ml_tpu.serving.batcher import MicroBatcher
        from photon_ml_tpu.serving.stats import ServingStats

        stats = ServingStats()
        seen_ctx = []

        def default_fn(reqs):
            seen_ctx.append(obs.current_span_context())
            return np.arange(len(reqs), dtype=float)

        tdir = str(tmp_path / "t")
        with obs.observe(trace_dir=tdir):
            b = MicroBatcher(
                score_fn or default_fn,
                max_batch=4,
                max_wait_ms=1.0,
                stats=stats,
                slo=slo,
            )
            futs = [b.submit(i) for i in range(n)]
            for f in futs:
                f.result(10)
            b.drain()
        return tdir, stats, seen_ctx

    def test_request_spans_decompose_latency(self, rng, tmp_path):
        tdir, stats, seen_ctx = self._run_batcher(tmp_path)
        doc = json.load(open(os.path.join(tdir, "trace.json")))
        reqs = [
            e for e in doc["traceEvents"]
            if e["name"] == "serving.request"
        ]
        assert len(reqs) == 6
        rids = {e["args"]["request_id"] for e in reqs}
        # rids are namespaced (instance_id << 32) | seq so two batcher
        # instances (replicas) can never collide; one batcher = one
        # namespace with seqs 1..6
        assert {r & 0xFFFFFFFF for r in rids} == set(range(1, 7))
        assert len({r >> 32 for r in rids}) == 1
        assert all(r >> 32 >= 1 for r in rids)
        for e in reqs:
            a = e["args"]
            for key in (
                "batch_id", "queue_wait_ms", "assembly_ms", "device_ms"
            ):
                assert key in a
            # the decomposition is consistent with the span window
            assert a["queue_wait_ms"] >= 0 and a["device_ms"] >= 0
            total = e["dur"] / 1e3
            assert a["device_ms"] <= total + 1e-3

    def test_two_batcher_instances_never_collide_rids(self, tmp_path):
        """Replicated serving runs R batchers in one process; their
        request ids must be globally unique or the merged trace dedup
        would collapse distinct requests (the PR-17 bug)."""
        from photon_ml_tpu.serving.batcher import MicroBatcher

        def fn(reqs):
            return np.zeros(len(reqs))

        b1 = MicroBatcher(fn, max_batch=4, max_wait_ms=0.5)
        b2 = MicroBatcher(fn, max_batch=4, max_wait_ms=0.5)
        assert b1.instance_id != b2.instance_id
        tdir = str(tmp_path / "t")
        with obs.observe(trace_dir=tdir):
            futs = [b.submit(i) for i in range(4) for b in (b1, b2)]
            for f in futs:
                f.result(10)
            b1.drain()
            b2.drain()
        doc = json.load(open(os.path.join(tdir, "trace.json")))
        rids = [
            e["args"]["request_id"] for e in doc["traceEvents"]
            if e["name"] == "serving.request"
        ]
        assert len(rids) == 8
        assert len(set(rids)) == 8  # no collisions across instances
        assert {r >> 32 for r in rids} == {
            b1.instance_id, b2.instance_id
        }

    def test_batch_context_propagates_to_score_fn(self, tmp_path):
        """The ambient span context carries the batch identity across
        the score_fn seam — the engine's serving.score span inherits it
        without signature changes."""
        tdir, stats, seen_ctx = self._run_batcher(tmp_path)
        assert seen_ctx and all(
            ctx is not None and "batch_id" in ctx and "batch_size" in ctx
            for ctx in seen_ctx
        )

    def test_span_context_merges_into_spans(self, tmp_path):
        tdir = str(tmp_path / "t")
        with obs.trace(tdir):
            with obs.span_context(request_id=7, tenant="a"):
                with obs.span("inner", tenant="b"):
                    pass
            with obs.span("outer"):
                pass
        doc = json.load(open(os.path.join(tdir, "trace.json")))
        by_name = {
            e["name"]: e["args"] for e in doc["traceEvents"]
            if e["ph"] == "X"
        }
        assert by_name["inner"]["request_id"] == 7
        assert by_name["inner"]["tenant"] == "b"  # explicit attr wins
        assert "request_id" not in by_name["outer"]

    def test_queue_depth_and_bucket_latency_in_snapshot(self, tmp_path):
        from photon_ml_tpu.serving.stats import ServingStats

        stats = ServingStats()
        stats.record_queue_depth(3)
        stats.record_queue_depth(1)
        stats.record_bucket_latency(8, 0.002)
        stats.record_bucket_latency(8, 0.004)
        stats.record_bucket_latency(64, 0.01)
        snap = stats.snapshot()
        assert snap["queue_depth"] == 1
        assert snap["queue_depth_peak"] == 3
        assert snap["bucket_latency"]["8"]["count"] == 2
        assert snap["bucket_latency"]["64"]["count"] == 1
        assert snap["bucket_latency"]["64"]["p99_ms"] > 0


class TestSloTracker:
    def test_p99_and_budget_math(self):
        from photon_ml_tpu.serving.stats import SloTracker

        slo = SloTracker(target_p99_ms=10.0, objective=0.99)
        for _ in range(98):
            slo.record(0.001)  # 1ms: fine
        slo.record(0.05)  # 50ms: violation
        slo.record(0.05, ok=False)  # error: violation
        snap = slo.snapshot()
        assert snap["window_requests"] == 100
        assert snap["violations"] == 2
        assert snap["violation_rate"] == pytest.approx(0.02)
        # 2% violations against a 1% budget: fully burned
        assert snap["error_budget_remaining"] == 0.0
        assert snap["p99_ms"] > 10.0 and snap["slo_met"] is False

    def test_budget_half_burned(self):
        from photon_ml_tpu.serving.stats import SloTracker

        slo = SloTracker(target_p99_ms=10.0, objective=0.99)
        for i in range(200):
            slo.record(0.5 if i == 0 else 0.001)  # 0.5% violations
        snap = slo.snapshot()
        assert snap["error_budget_remaining"] == pytest.approx(
            0.5, abs=0.01
        )
        assert snap["slo_met"] is True

    def test_gauges_exported(self):
        from photon_ml_tpu.serving.stats import SloTracker

        reg = MetricsRegistry()
        slo = SloTracker(target_p99_ms=1.0, registry=reg)
        slo.record(0.01)
        slo.snapshot()
        snap = reg.snapshot()
        assert snap["gauges"]["serving.slo.p99_ms"] > 0
        assert "serving.slo.error_budget_remaining" in snap["gauges"]

    def test_bad_objective_rejected(self):
        from photon_ml_tpu.serving.stats import SloTracker

        with pytest.raises(ValueError):
            SloTracker(objective=1.0)

    def test_serve_lines_slo_cmd(self):
        from io import StringIO

        from photon_ml_tpu.cli.serve import serve_lines
        from photon_ml_tpu.serving.batcher import MicroBatcher
        from photon_ml_tpu.serving.stats import SloTracker

        slo = SloTracker(target_p99_ms=10.0)
        b = MicroBatcher(
            lambda reqs: np.zeros(len(reqs)),
            max_wait_ms=0.5,
            slo=slo,
        )
        out = StringIO()
        # commands execute at READ time, so score first and let the
        # batch complete before asking for the SLO view
        serve_lines(
            iter([json.dumps({"features": {"f": 1.0}})]), out, b
        )
        serve_lines(iter([json.dumps({"cmd": "slo"})]), out, b)
        b.drain()
        replies = [json.loads(s) for s in out.getvalue().splitlines()]
        assert "score" in replies[0]
        assert replies[1]["target_p99_ms"] == 10.0
        assert replies[1]["window_requests"] >= 1
        assert "error_budget_remaining" in replies[1]


# ---------------------------------------------------------------------------
# scaling-efficiency sentinel gate
# ---------------------------------------------------------------------------


class TestScalingEfficiencySentinel:
    def test_direction_and_floor(self):
        name = "extra.sparse_fs_scaling.2.scaling_efficiency"
        assert (
            obs_sentinel.metric_direction(name)
            == obs_sentinel.HIGHER_IS_BETTER
        )
        # RAISED absolute per-width targets since the overlap path
        # landed (docs/PARALLEL.md; was the 0.25/N rule)
        assert obs_sentinel.metric_floor(name) == pytest.approx(0.25)
        assert obs_sentinel.metric_floor(
            "extra.sparse_fs_scaling.8.scaling_efficiency"
        ) == pytest.approx(0.055)
        assert obs_sentinel.metric_floor("extra.dense.wall_s") is None

    def test_floor_gates_without_history(self):
        """The floor binds from the FIRST record carrying the metric —
        no history band needed."""
        regs = obs_sentinel.check_record(
            {"extra.sparse_fs_scaling.2.scaling_efficiency": 0.06}, {}
        )
        assert len(regs) == 1
        assert regs[0].baseline.n_samples == 0
        assert "below" in regs[0].describe()
        ok = obs_sentinel.check_record(
            {"extra.sparse_fs_scaling.2.scaling_efficiency": 0.3}, {}
        )
        assert ok == []

    def _record(self, eff2=0.29, eff8=0.15, wall=3.0):
        return {
            "metric": "photon_bench",
            # headline inside the committed r03-r05 band (0.094-0.134 s):
            # only the scaling metrics under test may trip the sentinel
            "value": 0.12,
            "extra": {
                "sparse_fs_scaling": {
                    "1": {"wall_s": wall, "scaling_efficiency": 1.0},
                    "2": {
                        "wall_s": wall, "scaling_efficiency": eff2,
                        "collective_wall_ms": 40.0,
                    },
                    "8": {
                        "wall_s": wall, "scaling_efficiency": eff8,
                        "collective_wall_ms": 55.0,
                    },
                }
            },
        }

    def test_sentinel_cli_end_to_end_tracks_scaling_efficiency(
        self, tmp_path
    ):
        """regression_sentinel.py over the real BENCH_r03-r05 history
        plus synthetic rounds carrying scaling_efficiency: once >= 2
        records carry the metric it is band-tracked (a halved efficiency
        fails), and the absolute floor fails a sub-floor record even
        when the band would tolerate it."""
        import glob as glob_mod

        from benchmarks.regression_sentinel import main as sentinel_main

        hist_dir = str(tmp_path / "hist")
        os.makedirs(hist_dir)
        repo = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        )
        real = sorted(glob_mod.glob(os.path.join(repo, "BENCH_r*.json")))
        assert len(real) >= 2, "committed BENCH history missing"
        for p in real:
            with open(p) as f, open(
                os.path.join(hist_dir, os.path.basename(p)), "w"
            ) as g:
                g.write(f.read())
        # two new rounds RECORD the metric into the history
        for i, eff in ((6, 0.28), (7, 0.30)):
            with open(
                os.path.join(hist_dir, f"BENCH_r{i:02d}.json"), "w"
            ) as f:
                json.dump(self._record(eff2=eff), f)
        glob_pat = os.path.join(hist_dir, "BENCH_r*.json")

        # healthy current record: passes
        cur = str(tmp_path / "cur_ok.json")
        with open(cur, "w") as f:
            json.dump(self._record(eff2=0.27), f)
        assert sentinel_main(["--history", glob_pat, "--current", cur]) == 0

        # tracked once recorded: halving the efficiency trips the band
        cur_bad = str(tmp_path / "cur_bad.json")
        with open(cur_bad, "w") as f:
            json.dump(self._record(eff2=0.14), f)
        assert (
            sentinel_main(
                ["--history", glob_pat, "--current", cur_bad]
            ) == 1
        )

        # the absolute floor binds even below the band's reach
        cur_floor = str(tmp_path / "cur_floor.json")
        with open(cur_floor, "w") as f:
            json.dump(self._record(eff2=0.29, eff8=0.01), f)
        assert (
            sentinel_main(
                ["--history", glob_pat, "--current", cur_floor]
            ) == 1
        )
