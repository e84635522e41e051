"""Task ``game_serve``: the JSON-lines socket server that ``cli/serve.py
--socket`` builds (``_serve_socket`` -> ``serve_lines`` -> ``MicroBatcher`` ->
``ScoringEngine.score``), composed in this process over an engine built on
seeded parameters, under open-loop load from ``chipbench.loadgen`` running as
a process of its own.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import loadgen, reference
from chipbench.run import ROOT

_BLOCK = 1 << 22  # table rows made on the device at a time


def table_rows(users, d_user: int, data_seed: int):
    """Rows of the per-user table for ``users`` (any integer array): a 32-bit
    hash of (row, column, data_seed) mapped to (-0.5, 0.5).  The same integer
    arithmetic in numpy (the reference) and in jax.numpy (set-up)."""
    xp = jnp if isinstance(users, jax.Array) else np
    cell = users.astype(xp.uint32)[:, None] * xp.uint32(d_user) + xp.arange(
        d_user, dtype=xp.uint32
    )
    h = (cell + xp.uint32(data_seed & 0xFFFFFFFF)) * xp.uint32(2654435761)
    h = (h ^ (h >> xp.uint32(15))) * xp.uint32(2246822519)
    h = h ^ (h >> xp.uint32(13))
    return (h >> xp.uint32(8)).astype(xp.float32) / xp.float32(1 << 24) - (
        xp.float32(0.5)
    )


def fixed_weights(d_fixed: int, data_seed: int):
    rng = np.random.default_rng([int(data_seed), 7])
    return rng.standard_normal(d_fixed).astype(np.float32) / np.sqrt(d_fixed)


class RangeVocabulary:
    """``re_vocabs`` entry for dense integer ids below ``n``: the engine only
    calls ``get``; a dictionary of 2**26 keys would be gigabytes of host."""

    def __init__(self, n: int):
        self.n = int(n)

    def get(self, raw, default=None):
        if isinstance(raw, int) and 0 <= raw < self.n:
            return raw
        return default

    def __len__(self):
        return self.n


class _Shutdown:
    """What ``_serve_socket`` needs of a GracefulShutdown, without signals."""

    def __init__(self):
        self._event = threading.Event()

    @property
    def requested(self):
        return self._event.is_set()


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _engine(run, stats):
    from photon_ml_tpu.game.scoring import CompactReTable
    from photon_ml_tpu.io.vocab import FeatureVocabulary, feature_key
    from photon_ml_tpu.serving.engine import ScoringEngine

    cfg = run.config
    users, d_f, d_u = run.size("num_users"), cfg["fixed_dim"], cfg["user_dim"]
    with run.phase("table_on_device_and_back"):
        make = jax.jit(lambda lo: table_rows(
            lo + jnp.arange(min(_BLOCK, users), dtype=jnp.uint32), d_u,
            int(cfg["data_seed"])))
        values = np.empty((users, d_u), np.float32)
        for lo in range(0, users, _BLOCK):
            values[lo:lo + _BLOCK] = np.asarray(make(jnp.uint32(lo)))[
                : users - lo
            ]
    columns = np.broadcast_to(np.arange(d_u, dtype=np.int32), (users, d_u))
    with run.phase("engine_pin"):
        engine = ScoringEngine(
            {
                "global": fixed_weights(d_f, cfg["data_seed"]),
                "per-user": CompactReTable(columns=columns, values=values),
            },
            shards={"global": "g", "per-user": "u"},
            random_effects={"global": None, "per-user": "userId"},
            shard_vocabs={
                "g": FeatureVocabulary(
                    [feature_key(f"g{j}", "") for j in range(d_f)]),
                "u": FeatureVocabulary(
                    [feature_key(f"u{j}", "") for j in range(d_u)]),
            },
            re_vocabs={"userId": RangeVocabulary(users)},
            dtype=jnp.float32,
            stats=stats,
        )
    del values
    return engine


def setup(run):
    from photon_ml_tpu.cli.serve import _serve_socket
    from photon_ml_tpu.serving.batcher import MicroBatcher
    from photon_ml_tpu.serving.stats import ServingStats

    cfg = run.config
    stats = ServingStats()
    engine = _engine(run, stats)
    score = engine.score
    if run.fault == "answer_altered":  # one answer of each batch, a little
        def score(requests):
            out = np.array(engine.score(requests))
            out[0] += 1e-3
            return out
    batcher = MicroBatcher(
        score,
        max_batch=int(cfg["max_batch"]),
        max_wait_ms=float(cfg["max_wait_ms"]),
        queue_depth=int(cfg["queue_depth"]),
        stats=stats,
    )
    with run.phase("warm_up"):
        # every padded batch size the batcher can flush
        engine.warmup(max_batch=int(cfg["max_batch"]))
    shutdown, port = _Shutdown(), _free_port()
    server = threading.Thread(
        target=_serve_socket,
        args=(port, batcher, None, stats, shutdown, None),
        name="serve-socket",
        daemon=True,
    )
    server.start()
    state = {
        "engine": engine, "batcher": batcher, "stats": stats,
        "shutdown": shutdown, "server": server, "port": port,
    }
    with run.phase("load_generator_ready"):
        state["child"] = _start_child(run, state)
    return state


def _seconds(run):
    return float(run.traffic["trace_seconds"]) if run.tracing else run.seconds


def _start_child(run, state, traffic=None):
    """The generator as a process of its own; returns once it has built its
    payloads and waits for GO."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # it never imports jax
    child = subprocess.Popen(
        [sys.executable, "-m", "chipbench.loadgen"],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, cwd=ROOT,
        env=env,
    )
    spec = {
        "traffic": dict(traffic or run.traffic,
                        num_users=run.size("num_users")),
        "seed": run.seed, "seconds": _seconds(run), "port": state["port"],
        "d_fixed": run.config["fixed_dim"], "d_user": run.config["user_dim"],
    }
    child.stdin.write(json.dumps(spec) + "\n")
    child.stdin.flush()
    for _ in range(50):  # wait for the server to listen
        try:
            socket.create_connection(("127.0.0.1", state["port"]), 0.2).close()
            break
        except OSError:
            time.sleep(0.1)
    if child.stdout.readline().strip() != "READY":
        child.kill()
        child.wait()
        raise RuntimeError("load generator failed to get ready")
    state["spec"] = spec
    return child


def _snapshot(stats):
    with stats._lock:
        return {
            "requests": float(stats.requests), "batches": float(stats.batches),
            "request_ms_sum": stats.request_ms.sum_ms,
            "request_n": stats.request_ms.count,
            "device_ms_sum": stats.device_ms.sum_ms,
            "device_n": stats.device_ms.count,
        }


def _offer(state, run):
    """GO; returns the generator's result and the server's own counters over
    the measured part."""
    from photon_ml_tpu.obs.compile_events import xla_compile_events

    child, stats = state["child"], state["stats"]
    with run.span("wait_generator"):
        child.stdin.write("GO\n")
        child.stdin.flush()
        if child.stdout.readline().strip() != "WINDOW":
            raise RuntimeError("load generator died before the window")
        before, compiles = _snapshot(stats), xla_compile_events()
        out = json.loads(child.stdout.readline())
    after = _snapshot(stats)
    child.wait(timeout=30)
    state["child"] = None
    out["server"] = {k: after[k] - before[k] for k in after}
    out["compiles_in_window"] = xla_compile_events() - compiles
    return out


def window(state, run):
    out = _offer(state, run)
    state["out"] = out
    done = np.array([np.nan if x is None else x for x in out["done"]])
    due = np.array(out["due"])
    sent = np.array([np.nan if x is None else x for x in out["sent"]])
    ok = np.array([s is not None for s in out["scores"]])
    lat_ms = loadgen.latencies(due, done) * 1e3
    lat_ms[~ok] = 1e9  # refused, shed, failed or never answered: beyond any limit
    run.attempted = int(due.size)
    run.failed = int((~ok).sum())
    run.end_to_end["serve.p50_ms"] = float(np.percentile(lat_ms, 50))
    run.end_to_end["serve.p95_ms"] = float(np.percentile(lat_ms, 95))
    srv = out["server"]
    run.counts.update(
        requests=int(due.size),
        rows_answered=int(ok.sum()),
        window_wall_s=_seconds(run),
        client_ms_mean=float(np.mean(lat_ms[ok])) if ok.any() else None,
        late_ms_p99=float(np.nanpercentile((sent - due) * 1e3, 99)),
        server_request_ms_mean=srv["request_ms_sum"] / max(srv["request_n"], 1),
        engine_call_ms_mean=srv["device_ms_sum"] / max(srv["device_n"], 1),
        rows_per_batch=srv["requests"] / max(srv["batches"], 1),
        compiles_in_window=out["compiles_in_window"],
        backlog_at_close=int(np.sum(~(done <= _seconds(run) + float(
            run.traffic["lead_in_s"])))),
    )


def sweep(state, run, rates, seconds=8.0):
    """One set-up, several offered rates: prints for each what a knee is read
    from (latencies, backlog at the close, how late the generator ran)."""
    state["child"].kill()
    state["child"].wait()
    rows = []
    for rate in rates:
        run.seconds, run.counts, run.end_to_end = float(seconds), {}, {}
        state["child"] = _start_child(
            run, state, dict(run.traffic, rate_per_s=float(rate)))
        window(state, run)
        rows.append(dict(
            rate=float(rate), p50=run.end_to_end["serve.p50_ms"],
            p95=run.end_to_end["serve.p95_ms"], failed=run.failed,
            backlog=run.counts["backlog_at_close"],
            late_p99=run.counts["late_ms_p99"],
            rows_per_batch=run.counts["rows_per_batch"],
            engine_ms=run.counts["engine_call_ms_mean"],
        ))
        print("sweep " + json.dumps(rows[-1]), file=sys.stderr, flush=True)
    return rows


def count(state, run):
    """Nothing more to count: the server's own counters cover the window."""


def release(state):
    state["shutdown"]._event.set()
    state["server"].join(timeout=10)
    state["batcher"].begin_drain()
    state["batcher"].drain(timeout=10)
    if state.get("child") is not None:
        state["child"].kill()
        state["child"].wait()
    state["engine"].close()
    state["engine"] = state["batcher"] = None


def compare(run, spec, served, dtype=jnp.float32):
    """Widest gap between the served scores and the reference's, over the
    spread of the reference's scores.  With a lower ``dtype`` the reference
    in that precision stands in for the server (the control)."""
    cfg = run.config
    due, users, xg, xu, n_lead = loadgen.requests(
        spec["traffic"], spec["seed"], spec["seconds"], cfg["fixed_dim"],
        cfg["user_dim"],
    )
    users, xg, xu = users[n_lead:], xg[n_lead:], xu[n_lead:]
    rows = table_rows(users, cfg["user_dim"], int(cfg["data_seed"]))
    w_f = fixed_weights(cfg["fixed_dim"], cfg["data_seed"])
    want = reference.game_scores(xg, xu, rows, w_f)
    if dtype != jnp.float32:
        served = reference.game_scores(xg, xu, rows, w_f, dtype)
    served = np.asarray(
        [np.nan if s is None else s for s in served], np.float64
    )
    answered = ~np.isnan(served)
    gap = np.abs(served[answered] - want[answered])
    return {"score_gap": float(gap.max() / want.std()) if gap.size else 1.0}


def control(state, run):
    return compare(run, state["spec"], state["out"]["scores"], jnp.bfloat16)


def check(state, run):
    out, lim = state["out"], run.config["limits"]
    got = compare(run, state["spec"], out["scores"])
    # late is late, not wrong; only a reply that never came is for `correct`
    got["unanswered"] = float(sum(d is None for d in out["done"]))
    for name in ("score_gap", "unanswered"):
        run.compared.append((name, got[name], float(lim[name])))
