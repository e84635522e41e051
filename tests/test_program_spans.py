"""Program spans (docs/OBSERVABILITY.md): one primitive, always on.

Every ``obs.span`` is one record in the flight ring on the
``time.perf_counter()`` clock and, while open, a profiler annotation; the
span sites at dispatch, fetch, decode and every serving stage; the names
on the device; and the per-layer readers of ``chipbench/layer_metrics``
that cut the ring to a run's window. CPU, toy sizes.
"""

import glob
import importlib.util
import json
import os
import time
from io import StringIO

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.obs import flight
from photon_ml_tpu.obs.flight import (
    ATTRS,
    END,
    NAME,
    PARENT_ID,
    SPAN_ID,
    START,
    THREAD,
)

from test_obs import _build_cd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _named(name, records=None):
    records = obs.recent_spans() if records is None else records
    return [r for r in records if r[NAME] == name]


# ---------------------------------------------------------------------------
# the primitive and its ring
# ---------------------------------------------------------------------------


class TestRing:
    def test_records_nest_on_the_perf_counter_clock(self):
        assert obs.get_tracer() is None
        before = time.perf_counter()
        with obs.span("game.outer", job=7) as outer:
            with obs.span("game.inner"):
                pass
            retro = obs.add_span(
                "game.retro", before, time.perf_counter(), what="x"
            )
        after = time.perf_counter()
        (o,), (i,), (r,) = (
            _named("game.outer"), _named("game.inner"), _named("game.retro")
        )
        # children close first, so they are recorded first
        assert [x[NAME] for x in obs.recent_spans()] == [
            "game.inner", "game.retro", "game.outer"
        ]
        assert o[PARENT_ID] == 0 and o[SPAN_ID] == outer.span_id
        assert i[PARENT_ID] == o[SPAN_ID] and r[PARENT_ID] == o[SPAN_ID]
        assert r[SPAN_ID] == retro
        assert before <= o[START] <= i[START] <= i[END] <= o[END] <= after
        assert o[ATTRS] == {"job": 7} and r[ATTRS] == {"what": "x"}
        assert o[THREAD] == i[THREAD]

    def test_ring_is_bounded_and_counts_what_it_drops(self):
        assert flight.SPAN_CAPACITY == 65536
        flight.reset_spans(capacity=4)
        for k in range(10):
            with obs.span("game.k", k=k):
                pass
        records = obs.recent_spans()
        assert [r[ATTRS]["k"] for r in records] == [6, 7, 8, 9]
        assert obs.spans_dropped() == 6
        assert obs.recent_spans(since_s=records[2][END]) == records[2:]
        flight.reset_spans()
        assert obs.recent_spans() == [] and obs.spans_dropped() == 0

    def test_span_context_reaches_records_and_error_is_marked(self):
        with obs.span_context(batch_id=3, rows=2):
            with obs.span("serving.a", rows=5):
                pass
        with pytest.raises(RuntimeError):
            with obs.span("serving.b"):
                raise RuntimeError("boom")
        (a,), (b,) = _named("serving.a"), _named("serving.b")
        assert a[ATTRS] == {"batch_id": 3, "rows": 5}  # explicit wins
        assert b[ATTRS] == {"error": True}
        # nothing is left open on the thread
        with obs.span("serving.c"):
            pass
        assert _named("serving.c")[0][PARENT_ID] == 0

    def test_flight_dump_has_the_spans_without_a_trace_dir(self, tmp_path):
        with obs.span("game.before_install"):
            pass
        obs.install_flight_recorder(flight_dir=str(tmp_path), capacity=8)
        try:
            with obs.span("game.after_install", iteration=1):
                pass
            path = obs.flight_dump("test")
        finally:
            obs.uninstall_flight_recorder()
        records = json.load(open(path))["records"]
        spans = [r for r in records if r["kind"] == "span"]
        assert [r["name"] for r in spans] == [
            "game.before_install", "game.after_install"
        ]
        assert spans[1]["iteration"] == 1 and spans[1]["duration_ms"] >= 0

    def test_tracer_exports_the_same_records(self, tmp_path):
        with obs.trace(str(tmp_path / "t")) as tracer:
            with obs.span("game.live", cat="game", k=1):
                pass
            t = time.perf_counter()
            obs.add_span("game.retro", t - 0.002, t - 0.001, cat="game")
        exported = {
            e["name"]: e for e in tracer.events() if e["ph"] == "X"
        }
        for rec in obs.recent_spans():
            ev = exported[rec[NAME]]
            assert ev["args"] == rec[ATTRS]
            assert ev["dur"] == pytest.approx(
                (rec[END] - rec[START]) * 1e6, abs=1e-2
            )
            assert ev["ts"] == pytest.approx(
                tracer.us_of(rec[START]), abs=1e-2
            )


# ---------------------------------------------------------------------------
# training span sites
# ---------------------------------------------------------------------------


def _glm_problem(path_mode):
    from photon_ml_tpu.core.types import LabeledBatch
    from photon_ml_tpu.models import (
        GLMTrainingConfig,
        OptimizerType,
        TaskType,
    )
    from photon_ml_tpu.ops import RegularizationContext

    rng = np.random.default_rng(5)
    n, d = 256, 8
    x = rng.standard_normal((n, d)).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    batch = LabeledBatch.create(x, y, dtype=jnp.float32)
    cfg = GLMTrainingConfig(
        task=TaskType.LOGISTIC_REGRESSION,
        optimizer=OptimizerType.LBFGS,
        regularization=RegularizationContext("L2"),
        reg_weights=(1.0, 0.1),
        max_iters=5,
        track_states=False,
        path_mode=path_mode,
    )
    return batch, cfg


class _SyncCounter:
    """Counts the blocking reads the program asks jax for."""

    def __init__(self, monkeypatch):
        self.calls = {"device_get": 0, "block_until_ready": 0}
        for name in self.calls:
            monkeypatch.setattr(jax, name, self._counted(name))

    def _counted(self, name):
        orig = getattr(jax, name)

        def counted(*args, **kwargs):
            self.calls[name] += 1
            return orig(*args, **kwargs)

        return counted


class TestTrainingSites:
    def test_cd_run_untraced_leaves_root_dispatch_fetch_decode(
        self, rng, monkeypatch
    ):
        cd = _build_cd(rng, fuse_passes=True)
        cd.run(num_iterations=1)  # compile outside the counted run
        flight.reset_spans()
        assert obs.get_tracer() is None
        syncs = _SyncCounter(monkeypatch)
        with obs.count_dispatches() as dc:
            cd.run(num_iterations=3)
        records = obs.recent_spans()
        (root,) = _named("game.cd.run", records)
        assert root[ATTRS]["iterations"] == 3 and root[ATTRS]["job"] >= 2
        passes = [
            r for r in _named("game.dispatch", records)
            if r[ATTRS]["kind"] == "fused"
        ]
        assert len(passes) == dc.for_program("one_pass") == 3
        assert [r[ATTRS]["iteration"] for r in passes] == [0, 1, 2]
        fetches = _named("game.fetch", records)
        assert [r[ATTRS]["what"] for r in fetches] == ["history"]
        assert fetches[0][ATTRS]["bytes"] > 0
        (decode,) = _named("game.decode", records)
        assert decode[ATTRS]["updates"] == 6
        for r in passes + fetches + [decode]:
            assert r[PARENT_ID] == root[SPAN_ID]
            assert root[START] <= r[START] <= r[END] <= root[END]
        # no span synchronises: the parent commit's fused run makes one
        # batched device_get (the history drain) and never blocks
        assert syncs.calls == {"device_get": 1, "block_until_ready": 0}

    @pytest.mark.parametrize(
        "fuse_passes,kinds",
        [
            ("coordinate", {"score": 1, "coordinate": 4}),
            (False, {"score": 1, "update": 4, "objective": 4}),
        ],
    )
    def test_cd_per_coordinate_paths_nest_dispatch_in_update(
        self, rng, fuse_passes, kinds
    ):
        cd = _build_cd(rng, fuse_passes=fuse_passes)
        cd.run(num_iterations=2)
        records = obs.recent_spans()
        (root,) = _named("game.cd.run", records)
        updates = _named("game.update", records)
        assert len(updates) == 4
        assert all(u[PARENT_ID] == root[SPAN_ID] for u in updates)
        dispatches = _named("game.dispatch", records)
        counted = {}
        for d in dispatches:
            kind = d[ATTRS]["kind"]
            counted[kind] = counted.get(kind, 0) + 1
            if kind != "score":
                assert d[PARENT_ID] in {u[SPAN_ID] for u in updates}
        assert counted == kinds

    def test_guarded_run_reads_the_objective_in_fetch_spans(self, rng):
        cd = _build_cd(rng, fuse_passes=True)
        cd.run(num_iterations=1, divergence_guard=True)
        whats = [r[ATTRS]["what"] for r in _named("game.fetch")]
        assert whats.count("objective") == 2 and whats[-1] == "history"

    @pytest.mark.parametrize(
        "path_mode,root_name,dispatches", [
            ("scan", "glm.solve_path", 1), ("loop", "glm.solve", 2),
        ],
    )
    def test_train_glm_leaves_one_dispatch_a_program_call(
        self, path_mode, root_name, dispatches
    ):
        from photon_ml_tpu.models import train_glm

        batch, cfg = _glm_problem(path_mode)
        train_glm(batch, cfg)
        records = obs.recent_spans()
        roots = _named(root_name, records)
        got = _named("glm.dispatch", records)
        assert len(got) == dispatches
        assert {r[PARENT_ID] for r in got} == {r[SPAN_ID] for r in roots}
        assert len({r[ATTRS]["job"] for r in roots}) == 1
        if path_mode == "scan":
            (root,), (decode,) = roots, _named("glm.decode", records)
            assert got[0][ATTRS]["program"] == "solve_path"
            assert decode[PARENT_ID] == root[SPAN_ID]
            assert got[0][END] <= decode[START]

    @pytest.mark.parametrize("what", ["train_glm", "cd_run"])
    def test_a_tracer_changes_neither_dispatches_nor_compiles(
        self, rng, tmp_path, what
    ):
        """Nothing is lowered, compiled or dispatched for tracing's sake:
        the solve and pass programs run as often, and jax is asked to
        compile as often, with a Tracer installed as without."""
        obs.install_compile_listener()
        if what == "train_glm":
            from photon_ml_tpu.models import train_glm

            batch, cfg = _glm_problem("scan")
            job, program = (lambda: train_glm(batch, cfg)), "solve_path"
        else:
            cd = _build_cd(rng, fuse_passes=True)
            job, program = (lambda: cd.run(num_iterations=2)), "one_pass"
        job()  # compile

        def counted():
            before = obs.xla_compile_events()
            with obs.count_dispatches() as dc:
                job()
            return (
                dc.for_program(program),
                obs.xla_compile_events() - before,
            )

        plain = counted()
        with obs.trace(str(tmp_path / "t")):
            traced = counted()
        assert plain == traced
        assert plain[0] == (1 if what == "train_glm" else 2)


# ---------------------------------------------------------------------------
# serving span sites
# ---------------------------------------------------------------------------


def _toy_engine(stats):
    from photon_ml_tpu.io.vocab import FeatureVocabulary, feature_key
    from photon_ml_tpu.serving.engine import ScoringEngine

    d_f, d_u, users = 3, 2, 5
    return ScoringEngine(
        {
            "global": np.arange(1.0, d_f + 1),
            "per-user": np.arange(users * d_u, dtype=float).reshape(
                users, d_u
            ),
        },
        shards={"global": "g", "per-user": "u"},
        random_effects={"global": None, "per-user": "userId"},
        shard_vocabs={
            "g": FeatureVocabulary(
                [feature_key(f"g{j}", "") for j in range(d_f)]),
            "u": FeatureVocabulary(
                [feature_key(f"u{j}", "") for j in range(d_u)]),
        },
        re_vocabs={"userId": {i: i for i in range(users)}},
        stats=stats,
    )


class TestServingSites:
    def test_serve_lines_leaves_one_record_a_request(self):
        from photon_ml_tpu.cli.serve import serve_lines
        from photon_ml_tpu.serving.batcher import MicroBatcher
        from photon_ml_tpu.serving.stats import ServingStats

        stats = ServingStats()
        engine = _toy_engine(stats)
        engine.warmup(max_batch=8)
        n = 23
        lines = [
            json.dumps({
                "features": {"g0": 1.0, "u1": float(k)},
                "entities": {"userId": k % 5},
            })
            for k in range(n)
        ] + [json.dumps({"cmd": "stats"}), "not json"]
        out = StringIO()
        batcher = MicroBatcher(
            engine.score, max_batch=8, max_wait_ms=1.0, stats=stats
        )
        flight.reset_spans()
        assert serve_lines(iter(lines), out, batcher, stats=stats) == n
        batcher.drain()
        replies = [json.loads(s) for s in out.getvalue().splitlines()]
        assert len(replies) == n + 2
        records = obs.recent_spans()
        requests = _named("serving.request", records)
        assert len(requests) == n
        for r in requests:
            a = r[ATTRS]
            assert a["ok"] is True
            assert (
                r[START] <= a["enqueued"] <= a["flush"] <= a["scored"]
                <= r[END]
            )
        # written in reply order: request ids ascend
        rids = [r[ATTRS]["request_id"] for r in requests]
        assert rids == sorted(rids) and len(set(rids)) == n
        batch_ids = {r[ATTRS]["batch_id"] for r in requests}
        assert len(batch_ids) == stats.batches
        scores = {
            r[ATTRS]["batch_id"]: r for r in _named("serving.score", records)
        }
        assert set(scores) == batch_ids
        for stage in ("featurize", "dispatch", "fetch"):
            spans = _named("serving." + stage, records)
            assert {s[ATTRS]["batch_id"] for s in spans} == batch_ids
            assert len(spans) == stats.batches
            for s in spans:
                parent = scores[s[ATTRS]["batch_id"]]
                assert s[PARENT_ID] == parent[SPAN_ID]
                assert parent[START] <= s[START] <= s[END] <= parent[END]
        rows = sum(
            s[ATTRS]["rows"] for s in _named("serving.featurize", records)
        )
        assert rows == n
        engine.close()

    def test_direct_submit_is_recorded_by_the_batcher(self):
        from photon_ml_tpu.serving.batcher import MicroBatcher

        b = MicroBatcher(
            lambda reqs: np.zeros(len(reqs)), max_batch=4, max_wait_ms=0.5
        )
        futs = [b.submit(k) for k in range(5)]
        for f in futs:
            f.result(10)
        b.drain()
        requests = _named("serving.request")
        assert len(requests) == 5
        for r in requests:
            a = r[ATTRS]
            assert r[START] == a["enqueued"] and r[END] == a["scored"]
            assert a["queue_wait_ms"] >= 0 and a["device_ms"] >= 0
        assert not hasattr(futs[0], "request_stamps")

    def test_failed_batch_and_refused_request_are_recorded_not_ok(self):
        from photon_ml_tpu.cli.serve import serve_lines
        from photon_ml_tpu.serving.batcher import MicroBatcher

        def boom(reqs):
            raise RuntimeError("device gone")

        b = MicroBatcher(boom, max_batch=4, max_wait_ms=0.5)
        out = StringIO()
        lines = [json.dumps({"features": {}}) for _ in range(3)]
        assert serve_lines(iter(lines), out, b) == 0
        b.drain()
        requests = _named("serving.request")
        assert len(requests) == 3
        assert all(
            r[ATTRS]["ok"] is False and r[ATTRS]["error"] == "RuntimeError"
            for r in requests
        )


# ---------------------------------------------------------------------------
# the profiler's trace and the names on the device
# ---------------------------------------------------------------------------


def _host_event_names(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    )
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    return names


class TestProfilerMirror:
    def test_spans_land_in_the_xplane_host_plane(self, rng, tmp_path):
        from photon_ml_tpu.models import train_glm

        cd = _build_cd(rng, fuse_passes=True)
        batch, cfg = _glm_problem("scan")
        cd.run(num_iterations=1)
        train_glm(batch, cfg)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        with jax.profiler.trace(str(tmp_path), profiler_options=options):
            cd.run(num_iterations=2)
            train_glm(batch, cfg)
        names = _host_event_names(str(tmp_path))
        assert {
            "game.cd.run", "game.dispatch", "game.fetch", "game.decode",
            "glm.solve_path", "glm.dispatch", "glm.decode",
        } <= names


def _lowered_glm_solve():
    from photon_ml_tpu.core.types import LabeledBatch
    from photon_ml_tpu.models.training import _build_path_solver
    from photon_ml_tpu.core.normalization import NormalizationContext
    from photon_ml_tpu.ops.sparse import SparseFeatures

    _, cfg = _glm_problem("scan")
    rng = np.random.default_rng(2)
    n, d, k = 64, 32, 3
    feats = SparseFeatures(
        jnp.asarray(rng.integers(0, d, (n, k)), jnp.int32),
        jnp.asarray(rng.standard_normal((n, k)), jnp.float32),
        d,
    )
    ones = jnp.ones((n,), jnp.float32)
    batch = LabeledBatch(feats, ones, 0 * ones, ones, ones)
    return _build_path_solver(cfg).lower(
        jnp.zeros((d,), jnp.float32), jnp.asarray([1.0, 0.1], jnp.float32),
        batch, NormalizationContext(None, None),
    )


def _lowered_fused_pass():
    cd = _build_cd(np.random.default_rng(20260729), fuse_passes=True)
    cd._fused_pass_fn()
    names = list(cd.coordinates)
    params = {n: cd.coordinates[n].initial_params() for n in names}
    scores = {n: cd.coordinates[n].score(params[n]) for n in names}
    states = {n: cd.coordinates[n].fused_state() for n in names}
    return cd._fused_pass.lower(
        states, cd.labels, cd.base_offsets, cd.weights, params, scores,
        jax.random.PRNGKey(0),
    )


def _lowered_scorer():
    from photon_ml_tpu.serving.stats import ServingStats

    engine = _toy_engine(ServingStats())
    try:
        return engine._scorer.lower(
            engine._params, *engine._abstract_inputs(4, None, False)
        )
    finally:
        engine.close()


class TestDeviceScopes:
    @pytest.mark.parametrize(
        "lower,scopes",
        [
            (_lowered_glm_solve,
             ("objective_pass", "sparse_gather", "sparse_scatter")),
            (_lowered_fused_pass,
             ("objective_pass", "fe_solve", "re_newton_solve")),
            (_lowered_scorer, ("score",)),
        ],
        ids=["glm_solve", "game_fused_pass", "scorer"],
    )
    def test_lowered_text_carries_the_scopes(self, lower, scopes):
        import re

        text = lower().as_text(debug_info=True)
        for scope in scopes:
            # a location's name stack: "<outer>/<scope>/<op>"
            assert re.search(r'["/]' + scope + "/", text), scope

    def test_scopes_are_metadata_only(self):
        """The same program with and without its scopes: named_scope
        touches locations, never an operation."""
        import re

        def strip(text):
            text = re.sub(r"loc\(.*?\)\s*$", "", text, flags=re.M)
            return [ln for ln in text.splitlines() if "#loc" not in ln]

        from photon_ml_tpu.ops.sparse import SparseFeatures, matvec

        feats = SparseFeatures(
            jnp.zeros((8, 2), jnp.int32), jnp.ones((8, 2), jnp.float32), 16
        )
        def scoped(w):
            return matvec(feats, w)

        def bare(w):
            gathered = w.at[feats.indices].get(mode="fill", fill_value=0.0)
            return jnp.sum(feats.values * gathered, axis=-1)

        scoped.__name__ = bare.__name__ = "program"
        w = jnp.ones((16,), jnp.float32)
        with_scope = jax.jit(scoped).lower(w)
        assert "sparse_gather" in with_scope.as_text(debug_info=True)
        assert strip(with_scope.as_text()) == strip(
            jax.jit(bare).lower(w).as_text()
        )


# ---------------------------------------------------------------------------
# compile-path spans from the compile listener
# ---------------------------------------------------------------------------

COMPILE_PATH = ("xla.trace", "xla.lower", "xla.compile")


class TestCompileSpans:
    @pytest.mark.parametrize("installs", [1, 2])
    def test_a_fresh_jit_leaves_one_record_of_each_step(self, installs):
        """Installing the listener again records nothing twice; the
        records nest under the span that asked for the compile, on the
        ring's clock."""
        for _ in range(installs):
            obs.install_compile_listener()
        x = jnp.ones((4,), jnp.float32)
        fresh = jax.jit(lambda v: jax.lax.mul(v, v))
        flight.reset_spans()  # x's own eager compiles
        with obs.span("game.outer") as outer:
            time.sleep(0.002)
            fresh(x).block_until_ready()
            time.sleep(0.002)
        (o,) = _named("game.outer")
        for name in COMPILE_PATH:
            (r,) = _named(name)
            assert r[PARENT_ID] == outer.span_id
            assert o[START] < r[START] <= r[END] < o[END]
            assert r[THREAD] == o[THREAD]
        (trace,), (compiled,) = _named("xla.trace"), _named("xla.compile")
        assert trace[ATTRS] == {"fun": "<lambda>"}
        assert compiled[ATTRS]["fun"] == _named("xla.lower")[0][ATTRS]["fun"]
        assert compiled[ATTRS]["cache_hit"] is False
        assert compiled[ATTRS]["retrieval_s"] == 0.0

    def test_a_second_call_leaves_none(self):
        obs.install_compile_listener()
        x = jnp.ones((4,), jnp.float32)
        fresh = jax.jit(lambda v: jax.lax.mul(v, v))
        fresh(x).block_until_ready()
        assert all(_named(name) for name in COMPILE_PATH)
        flight.reset_spans()
        with obs.span("game.outer"):
            fresh(x).block_until_ready()
        assert [r[NAME] for r in obs.recent_spans()] == ["game.outer"]

    def test_nested_traces_are_counted_once(self):
        """An inner jit traces inside the outer one's trace: two nested
        xla.trace records, whose union is the outer one's window."""
        from chipbench import setup_spans

        obs.install_compile_listener()

        def doubled(v):  # lax primitives: no jitted jnp function inside
            return jax.lax.add(v, v)

        inner = jax.jit(doubled)
        outer = jax.jit(lambda v: jax.lax.mul(inner(v), v))
        x = jnp.ones((4,), jnp.float32)
        flight.reset_spans()  # x's own eager compiles
        outer(x).block_until_ready()
        traces = _named("xla.trace")
        assert [r[ATTRS]["fun"] for r in traces] == ["doubled", "<lambda>"]
        (i, o) = traces
        assert o[START] < i[START] <= i[END] < o[END]
        later = time.perf_counter() + 1.0
        run = _Run([("job", later, later + 1.0)], {})
        assert setup_spans.setup_seconds(run, ("xla.trace",)) == (
            pytest.approx(o[END] - o[START], rel=1e-9)
        )

    def test_the_cache_load_is_the_compile_of_a_hit(self, tmp_path):
        """With a persistent cache and jax's in-memory caches cleared, the
        same function's second compile is a load: cache_hit, and the
        cache's retrieval time."""
        from jax._src import compilation_cache

        obs.install_compile_listener()
        keys = {
            "jax_compilation_cache_dir": str(tmp_path),
            "jax_persistent_cache_min_compile_time_secs": 0.0,
            "jax_persistent_cache_min_entry_size_bytes": 0,
        }
        before = {k: getattr(jax.config, k) for k in keys}
        x = jnp.ones((4,), jnp.float32)

        def tripled(v):
            return jax.lax.mul(v, jnp.float32(3.0))

        flight.reset_spans()  # x's own eager compiles
        try:
            for k, v in keys.items():
                jax.config.update(k, v)
            compilation_cache.reset_cache()
            for _ in range(2):
                jax.clear_caches()
                jax.jit(tripled)(x).block_until_ready()
        finally:
            for k, v in before.items():
                jax.config.update(k, v)
            compilation_cache.reset_cache()
        first, second = (r[ATTRS] for r in _named("xla.compile"))
        assert first["cache_hit"] is False and first["retrieval_s"] == 0.0
        assert second["cache_hit"] is True and second["retrieval_s"] > 0
        assert first["fun"] == second["fun"] == "jit(tripled)"


# ---------------------------------------------------------------------------
# the per-layer readers over a hand-made ring
# ---------------------------------------------------------------------------


class _Run:
    """What a reader sees of ``chipbench.run.Run``."""

    def __init__(self, spans, counts, traffic=None):
        self.spans, self.counts = spans, dict(counts)
        self.traffic = traffic or {}


def _read(metric, run):
    path = os.path.join(
        REPO, "chipbench", "layer_metrics", metric + ".py"
    )
    spec = importlib.util.spec_from_file_location("lm_under_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def _training_ring(t0):
    """Two jobs of a GAME-shaped run, then a third outside the window:
    job k starts at t0 + 10 k; its root runs 1.0 .. 9.0 into it."""
    for k in range(3):
        base = t0 + 10.0 * k
        root = 100 + k
        ms = 1e-3

        def put(name, a, b, parent=root, **attrs):
            flight.note_span(
                (name, base + a, base + b, 0, parent, 1, attrs)
            )

        put("game.dispatch", 1.0, 1.0 + 2 * ms, kind="score")
        put("game.dispatch", 2.0, 2.0 + 3 * ms, kind="fused")
        put("game.dispatch", 3.0, 3.0 + 5 * ms, kind="fused")
        put("game.fetch", 4.0, 8.0, what="history")
        # a dispatch nested in another span of the root still counts, once
        put("game.dispatch", 5.0, 6.0, parent=999, kind="nested")
        put("game.decode", 8.0, 8.0 + 20 * ms)
        flight.note_span(
            ("game.cd.run", base + 1.0, base + 9.0, root, 0, 1, {"job": k})
        )
    spans = [("job", t0 + 10.0 * k, t0 + 10.0 * k + 9.5) for k in range(3)]
    return _Run(spans, {"jobs": 2})


def _serving_ring(t0):
    """Requests whose records end at t0 + 2 + k/10 (k = 0..19) after a
    lead-in of 2 s, and two before it; one batch span set for every four
    requests."""
    ms = 1e-3
    for k in range(-2, 20):
        end = t0 + 2.0 + k / 10.0
        if k == -2:
            end = t0 + 1.0
        if k == -1:
            end = t0 + 1.5
        j = max(k, 0)
        flight.note_span((
            "serving.request", end - 10 * ms, end, 0, 0, 2,
            {
                "ok": True, "request_id": k, "batch_id": j // 4,
                "enqueued": end - 10 * ms + (0.1 + 0.01 * j) * ms,
                "flush": end - 6 * ms + 0.1 * j * ms,
                "scored": end - (1 + 0.2 * j) * ms,
            },
        ))
        if k >= 0 and k % 4 == 0:
            b = k // 4
            for name, dur in (
                ("serving.featurize", 1.0 + b), ("serving.dispatch", 0.5),
                ("serving.fetch", 0.1 * (b + 1)),
            ):
                flight.note_span(
                    (name, end - dur * ms, end, 0, 0, 3, {"batch_id": b})
                )
    # a refused request: no stamps, never counted
    flight.note_span(
        ("serving.request", t0 + 2.5, t0 + 2.6, 0, 0, 2, {"ok": False})
    )
    return _Run(
        [("wait_generator", t0, t0 + 10.0)], {}, {"lead_in_s": 2.0}
    )


_J = np.arange(20)
TRAIN_WANT = {
    # 2 + 3 + 5 ms a job, and one of 1 s nested inside the fetch
    "dispatch.ms_per_job": 1010.0,
    "host.fetch_ms_per_job": 4000.0,
    # 8 s of root less 10 ms of dispatch and 4 s of fetch (the 20 ms of
    # decode are host time of this kind and stay in)
    "host.self_ms_per_job": 8000.0 - 10.0 - 4000.0,
}
SERVE_WANT = {
    "wire.parse_ms_p50": float(np.percentile(0.1 + 0.01 * _J, 50)),
    "batcher.wait_ms_p95": float(
        np.percentile((4 + 0.1 * _J) - (0.1 + 0.01 * _J), 95)
    ),
    "engine.featurize_ms_p50": 3.0,  # 1, 2, 3, 4, 5 ms
    "engine.dispatch_ms_p50": 0.5,
    "engine.fetch_ms_p50": 0.3,
    "wire.reply_ms_p95": float(np.percentile(1 + 0.2 * _J, 95)),
}


def _setup_ring(t0, coordinate=True):
    """Set-up's records on two threads before a window whose first job
    starts at t0 + 100, and a compile of the window after it."""
    def put(name, a, b, thread=1):
        flight.note_span((name, t0 + a, t0 + b, 0, 0, thread, {}))

    put("xla.trace", 1.5, 2.0)  # an inner jit, traced inside the outer
    put("xla.trace", 1.0, 3.0)
    put("xla.trace", 2.0, 2.5, thread=2)
    put("xla.lower", 3.0, 3.25)
    put("xla.compile", 3.25, 4.25)
    put("partition.entity_layout", 5.0, 7.0)
    if coordinate:
        put("partition.coordinate", 7.0, 8.0)
    for name in ("xla.trace", "xla.lower", "xla.compile"):
        put(name, 100.5, 101.0)  # ends in the window: not set-up's
    return _Run([("job", t0 + 100.0, t0 + 101.0)], {"jobs": 1})


SETUP_WANT = {
    # thread 1's union (1.0 .. 3.0) and thread 2's 0.5
    "setup.trace_s": 2.5,
    "setup.lower_s": 0.25,
    "setup.compile_s": 1.0,
    "shard.layout_s": 3.0,
}


class TestLayerMetricReaders:
    @pytest.mark.parametrize("metric", sorted(TRAIN_WANT))
    def test_training_reader_over_a_hand_made_ring(self, metric):
        run = _training_ring(time.perf_counter())
        assert _read(metric, run) == pytest.approx(
            TRAIN_WANT[metric], rel=1e-9
        )

    @pytest.mark.parametrize("metric", sorted(SERVE_WANT))
    def test_serving_reader_over_a_hand_made_ring(self, metric):
        run = _serving_ring(time.perf_counter())
        assert _read(metric, run) == pytest.approx(
            SERVE_WANT[metric], rel=1e-6
        )
        per_batch = metric.startswith("engine.")
        assert run.counts[metric + ".n"] == (5 if per_batch else 20)

    @pytest.mark.parametrize(
        "metric", sorted(TRAIN_WANT) + sorted(SERVE_WANT)
    )
    def test_reader_gives_none_when_the_ring_lost_the_window(self, metric):
        """A ring that dropped records which may have been the window's
        vouches for nothing: no partial number."""
        flight.reset_spans(capacity=8)
        t0 = time.perf_counter()
        make = _training_ring if metric in TRAIN_WANT else _serving_ring
        run = make(t0)
        assert obs.spans_dropped() > 0
        assert _read(metric, run) is None

    def test_drops_from_before_the_window_do_not_matter(self):
        flight.reset_spans(capacity=32)
        t0 = time.perf_counter()
        for k in range(40):  # old records, all ended before the window
            flight.note_span(("game.old", t0 - 50, t0 - 40, 0, 0, 1, {}))
        run = _training_ring(t0)
        assert obs.spans_dropped() > 0
        assert _read("dispatch.ms_per_job", run) == pytest.approx(1010.0)

    @pytest.mark.parametrize("metric", sorted(SETUP_WANT))
    def test_setup_reader_over_a_hand_made_ring(self, metric):
        run = _setup_ring(time.perf_counter())
        assert _read(metric, run) == pytest.approx(
            SETUP_WANT[metric], rel=1e-9
        )

    @pytest.mark.parametrize("metric", sorted(SETUP_WANT))
    def test_setup_reader_gives_none_on_drops_or_without_the_span(
        self, metric
    ):
        """A ring that dropped any record no longer vouches for set-up; a
        checkout without the span (partition.coordinate: the layout alone
        would be a partial sum) reads nothing."""
        flight.reset_spans(capacity=8)
        run = _setup_ring(time.perf_counter())
        assert obs.spans_dropped() > 0
        assert _read(metric, run) is None
        flight.reset_spans()
        run = _setup_ring(time.perf_counter(), coordinate=False)
        got = _read(metric, run)
        if metric == "shard.layout_s":
            assert got is None
        else:
            assert got == pytest.approx(SETUP_WANT[metric], rel=1e-9)
        flight.reset_spans()
        assert _read(metric, run) is None

    def test_glm_job_without_fetches_reads_zero_and_no_root_reads_none(self):
        t0 = time.perf_counter()
        flight.note_span(("glm.dispatch", t0 + 1, t0 + 1.5, 2, 1, 1, {}))
        flight.note_span(("glm.solve_path", t0 + 1, t0 + 3, 1, 0, 1, {}))
        run = _Run([("job", t0, t0 + 4)], {"jobs": 1})
        assert _read("host.fetch_ms_per_job", run) == 0.0
        assert _read("dispatch.ms_per_job", run) == pytest.approx(500.0)
        assert _read("host.self_ms_per_job", run) == pytest.approx(1500.0)
        flight.reset_spans()
        assert _read("host.fetch_ms_per_job", run) is None
        assert _read("wire.parse_ms_p50", _serving_ring(t0)) is not None
        flight.reset_spans()
        assert _read(
            "wire.parse_ms_p50",
            _Run([("wait_generator", t0, t0 + 1)], {}, {"lead_in_s": 0}),
        ) is None
