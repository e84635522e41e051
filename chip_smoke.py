#!/usr/bin/env python3
"""On-chip smoke: the GLM/GAME main path, end to end, on one TPU.

The quickest proof that the system still starts on the chip. ONE process
drives the normal entry points — ``cli.game_train.main``,
``cli.score.main``, ``cli.serve.main`` (in this process, over its TCP
socket) and ``cli.train.main`` — on seeded Avro fixtures written by the
repo's own writer, at the widths of cells ``bench.py`` runs (GAME: fixed
effect d = 64 + 30,000 entities x d = 16; sparse GLM: d = 120,000 at 32
nnz/row). Widths and the entity count are not cut; rows are. Every phase
checks what came out against a float64 numpy reference computed here.
Any failing phase raises: the exit code is non-zero and no result line
is printed.

    python3 chip_smoke.py              # needs a TPU; exits 2 without one
    python3 chip_smoke.py --rehearsal  # CPU debugging at toy sizes

stdout carries two JSON lines and nothing else. The LAST is the result
the driver reads, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as jax reports it. The line before it is the summary
(also written to ``chiprun_out/chip_smoke/summary.json``): the same two
keys, then versions, per-phase seconds / compile requests / persistent-
cache hits, the cache directory, the native codec's state, the sizes
used, every measured agreement, and
``"claim": null`` — this script observes; it claims no speed.

With >= 4 devices the sharded paths run too (entity-sharded GAME,
('data', 'feature')-mesh GLM, entity-sharded serving), each compared
with its one-chip result, and every mesh device must hold memory.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import shutil
import signal
import socket
import sys
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".chip_smoke_work")  # fixtures + driver outputs
REPORT = os.path.join(HERE, "chiprun_out", "chip_smoke")  # small, kept

FULL_SIZES = dict(
    entities=30_000, d_fixed=64, d_re=16, zipf_a=1.95, rows_cap=128,
    heldout_rows=8_192, served_rows=512, served_warm=128,
    sparse_rows=50_000, sparse_d=120_000, sparse_nnz=32,
)
REHEARSAL_SIZES = dict(
    entities=400, d_fixed=64, d_re=16, zipf_a=1.95, rows_cap=128,
    heldout_rows=512, served_rows=96, served_warm=32,
    sparse_rows=2_000, sparse_d=4_000, sparse_nnz=32,
)

# ---- tolerances, each with its reason -----------------------------------
# The drivers run float32 designs at jax's DEFAULT matmul precision
# (nothing in ops/, solvers/ or game/ sets one), and every tier-1 oracle
# ran float64 on CPU — so how close the chip comes was unknown until this
# script measured it. First observation, TPU v5 lite, jax 0.9.0 (PERF.md,
# PR 21): device margins agree with float64 numpy to 9e-7 absolute at
# |z| <= 9.3, the training objective to 2.9e-6 relative, served == batch
# exactly, the sparse gradient to 9.5e-5 relative. That is float32
# rounding, NOT bf16 products: the matvec-shaped contractions these
# drivers issue are not reduced to single-pass bf16 on this toolchain
# (which would cost ~2^-8 |z| ~ 4e-2 here). The bounds sit 30-50x above
# the observation and ~1000x below the bf16 level, so a silent fall to
# bf16 products fails this smoke instead of passing unnoticed.
MARGIN_ATOL = 5e-5        # device margin vs f64 reference (seen 9e-7)
OBJECTIVE_RTOL = 1e-4     # device objective vs f64 recompute (seen 2.9e-6;
#                           the driver logs 6 significant digits)
MONOTONE_RTOL = 1e-5      # f32 slack on "objective never increases"
SERVED_VS_BATCH_ATOL = 5e-5   # two device paths, same math (seen 0.0)
SPARSE_GRAD_RTOL = 1e-3   # f32 scatter-add over 50k rows (seen 9.5e-5)
TRAIN_AUC_FLOOR = 0.90    # seen 0.977: per-user effects fit ~4 rows each
# sharded vs one-chip: same math, different reduction order, f32; TRON /
# L-BFGS line searches may branch differently after a few iterations
SHARDED_OBJECTIVE_RTOL = 1e-3
SHARDED_COEF_RTOL = 5e-2


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


# ---- fixtures ------------------------------------------------------------


def _sigmoid(z):
    return 0.5 * (1.0 + np.tanh(0.5 * z))  # no overflow at large |z|


def _write_examples(path, uids, labels, feature_rows, entity_ids=None):
    """One Avro part file of TrainingExample records through the repo's
    writer. ``feature_rows`` yields per-row [(name, value), ...]."""
    from photon_ml_tpu.io.avro import write_avro_file
    from photon_ml_tpu.io.schemas import TRAINING_EXAMPLE_SCHEMA

    def records():
        for i, feats in enumerate(feature_rows):
            yield {
                "uid": uids[i],
                "label": float(labels[i]),
                "features": [
                    {"name": n, "term": "", "value": v} for n, v in feats
                ],
                "metadataMap": (
                    None if entity_ids is None
                    else {"userId": entity_ids[i]}
                ),
                "weight": None,
                "offset": None,
            }

    write_avro_file(path, TRAINING_EXAMPLE_SCHEMA, records())


def make_game_fixture(sizes, rng):
    """Mixed-effects click data: shared global coefficients + per-user
    coefficients, Zipf-ish rows per user (most users have 1-2 rows, a
    few have ``rows_cap``)."""
    e, dg, du = sizes["entities"], sizes["d_fixed"], sizes["d_re"]
    counts = np.minimum(rng.zipf(sizes["zipf_a"], size=e), sizes["rows_cap"])
    users = rng.permutation(np.repeat(np.arange(e), counts))
    w_g = rng.standard_normal(dg) * 0.15
    w_u = rng.standard_normal((e, du)) * 0.3
    g_names = [f"g{j}" for j in range(dg)]
    u_names = [f"u{j}" for j in range(du)]

    def draw(user_ids, tag, out_dir, parts):
        n = user_ids.size
        # float32-representable values: the Avro doubles round-trip to
        # exactly what the device holds
        xg = rng.standard_normal((n, dg)).astype(np.float32)
        xu = rng.standard_normal((n, du)).astype(np.float32)
        z = xg.astype(np.float64) @ w_g + np.einsum(
            "nd,nd->n", xu.astype(np.float64), w_u[user_ids]
        )
        y = (rng.uniform(size=n) < _sigmoid(z)).astype(np.float32)
        uids = [f"{tag}{i}" for i in range(n)]
        ents = [f"u{u}" for u in user_ids]
        os.makedirs(out_dir, exist_ok=True)
        for p, idx in enumerate(np.array_split(np.arange(n), parts)):
            rows = (
                list(zip(g_names, xg[i].tolist()))
                + list(zip(u_names, xu[i].tolist()))
                for i in idx
            )
            _write_examples(
                os.path.join(out_dir, f"part-{p:05d}.avro"),
                [uids[i] for i in idx], y[idx], rows,
                [ents[i] for i in idx],
            )
        return dict(xg=xg, xu=xu, y=y, users=user_ids, uids=uids)

    root = os.path.join(WORK, "game")
    train = draw(users, "t", os.path.join(root, "train"), parts=8)
    # held-out rows revisit training users in proportion to their traffic
    held_users = rng.choice(users, size=sizes["heldout_rows"])
    held = draw(held_users, "h", os.path.join(root, "heldout"), parts=1)
    for name, keys in (("global", g_names), ("user", u_names)):
        with open(os.path.join(root, f"{name}.features"), "w") as f:
            f.write("\n".join(f"{k}\x01" for k in keys))
    return dict(
        root=root, train=train, held=held, entities=e,
        rows_per_entity=float(counts.mean()), max_rows=int(counts.max()),
    )


def make_sparse_fixture(sizes, rng):
    """Wide sparse logistic data: uniform columns, ``nnz`` distinct
    columns per row, dense planted model."""
    n, d, k = sizes["sparse_rows"], sizes["sparse_d"], sizes["sparse_nnz"]
    cols = rng.integers(0, d, size=(n, k))
    while True:  # distinct columns within a row (ingest need not dedup)
        srt = np.sort(cols, axis=1)
        bad = np.flatnonzero((srt[:, 1:] == srt[:, :-1]).any(axis=1))
        if bad.size == 0:
            break
        cols[bad] = rng.integers(0, d, size=(bad.size, k))
    vals = rng.standard_normal((n, k)).astype(np.float32)
    w_true = rng.standard_normal(d) * (1.5 / np.sqrt(k))
    z = np.einsum("nk,nk->n", vals.astype(np.float64), w_true[cols])
    y = (rng.uniform(size=n) < _sigmoid(z)).astype(np.float32)
    root = os.path.join(WORK, "sparse")
    os.makedirs(os.path.join(root, "train"), exist_ok=True)
    names = [f"f{j}" for j in range(d)]
    for p, idx in enumerate(np.array_split(np.arange(n), 4)):
        rows = (
            [(names[c], v) for c, v in zip(cols[i].tolist(), vals[i].tolist())]
            for i in idx
        )
        _write_examples(
            os.path.join(root, "train", f"part-{p:05d}.avro"),
            [f"s{i}" for i in idx], y[idx], rows,
        )
    with open(os.path.join(root, "all.features"), "w") as f:
        f.write("\n".join(f"{k}\x01" for k in names))
    return dict(root=root, cols=cols, vals=vals, y=y, n=n, d=d)


# ---- float64 references --------------------------------------------------


def logloss_sum(z, y):
    """sum_i log(1 + exp(z_i)) - y_i z_i, stable, float64."""
    return float(np.sum(np.logaddexp(0.0, z) - y * z))


def auc(scores, labels):
    order = np.argsort(scores, kind="stable")
    ranks = np.empty(scores.size)
    ranks[order] = np.arange(1, scores.size + 1)
    pos = labels > 0.5
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    return float(
        (ranks[pos].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg)
    )


def load_game(model_dir, sizes):
    """Saved GAME coefficients as float64 arrays in THIS script's
    feature / entity order."""
    from photon_ml_tpu.io.models import load_game_model_auto

    params, _, _, shard_vocabs, re_vocabs = load_game_model_auto(model_dir)
    gi = [
        shard_vocabs["gshard"].key_to_index[f"g{j}\x01"]
        for j in range(sizes["d_fixed"])
    ]
    ui = [
        shard_vocabs["ushard"].key_to_index[f"u{j}\x01"]
        for j in range(sizes["d_re"])
    ]
    rows = [re_vocabs["userId"][f"u{e}"] for e in range(sizes["entities"])]
    w_g = np.asarray(params["global"], np.float64)[gi]
    table = np.asarray(params["per-user"], np.float64)[rows][:, ui]
    return w_g, table


def game_margins(w_g, table, part):
    return part["xg"].astype(np.float64) @ w_g + np.einsum(
        "nd,nd->n", part["xu"].astype(np.float64), table[part["users"]]
    )


# ---- run bookkeeping -----------------------------------------------------


class Run:
    """Per-phase seconds and compile counters, printed as they happen."""

    def __init__(self):
        self.phases = {}

    @contextlib.contextmanager
    def phase(self, name):
        from photon_ml_tpu import obs

        log(f"=== {name}")
        c0, h0, t0 = (
            obs.xla_compile_events(), obs.xla_cache_hits(),
            time.perf_counter(),
        )
        yield
        requests = obs.xla_compile_events() - c0
        hits = obs.xla_cache_hits() - h0
        self.phases[name] = {
            "seconds": round(time.perf_counter() - t0, 2),
            "compile_requests": requests,
            "cache_hits": hits,
            "backend_compiles": requests - hits,
        }
        log(f"--- {name}: {self.phases[name]}")


@contextlib.contextmanager
def device_memory_watch(devices):
    """Max ``memory_stats()['bytes_in_use']`` per device over the block,
    sampled from a thread (the drivers free their arrays on return).
    Platforms that report no stats yield None per device."""
    seen = {d.id: None for d in devices}
    stop = threading.Event()

    def sample():
        for d in devices:
            stats = d.memory_stats()
            if stats is not None:
                seen[d.id] = max(seen[d.id] or 0, stats["bytes_in_use"])

    def loop():
        while not stop.wait(0.2):
            sample()

    t = threading.Thread(target=loop, name="hbm-watch", daemon=True)
    t.start()
    try:
        yield seen
    finally:
        stop.set()
        t.join()
        sample()


def require_all_devices_used(seen, what):
    log(f"{what}: max bytes_in_use per device {seen}")
    if all(v is None for v in seen.values()):
        log(f"{what}: platform reports no memory stats; not checked")
        return
    empty = [d for d, v in seen.items() if not v]
    if empty:
        raise AssertionError(
            f"{what}: mesh devices {empty} held no memory — the work did "
            f"not reach them ({seen})"
        )


def check(name, ok, detail):
    log(f"check {name}: {'ok' if ok else 'FAILED'} — {detail}")
    if not ok:
        raise AssertionError(f"{name}: {detail}")


def write_json(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f, indent=2)
    return path


# ---- phases --------------------------------------------------------------


def phase_native(t_start):
    """Native Avro reader, built in THIS run from the committed source."""
    from photon_ml_tpu.io import native

    if os.path.isdir(native.BUILD_DIR):
        shutil.rmtree(native.BUILD_DIR)  # start from a tree without it
    so = native.library_path()
    check(
        "native.available", native.native_available(),
        native.native_error() or so,
    )
    check(
        "native.built_this_run",
        os.path.exists(so) and os.path.getmtime(so) >= t_start - 1.0,
        so,
    )
    log(native.codec_report())
    return so


def game_config(fixture, out_dir, **extra):
    return {
        "train_input": [os.path.join(fixture["root"], "train")],
        "output_dir": out_dir,
        "task": "LOGISTIC_REGRESSION",
        "num_iterations": 2,
        "updating_sequence": ["global", "per-user"],
        "feature_shards": {
            "gshard": os.path.join(fixture["root"], "global.features"),
            "ushard": os.path.join(fixture["root"], "user.features"),
        },
        "coordinates": {
            "global": {
                "shard": "gshard", "optimizer": "TRON",
                "reg_weights": [1.0], "max_iters": 20, "tolerance": 1e-8,
            },
            "per-user": {
                "shard": "ushard", "random_effect": "userId",
                "optimizer": "TRON", "reg_weights": [1.0],
                "max_iters": 20, "tolerance": 1e-8,
            },
        },
        "overwrite": True,
        **extra,
    }


def run_game_trainer(fixture, sizes, tag, **extra):
    """``cli.game_train.main`` + everything checked about its outputs.
    Fusion / dispatch switches are the driver's defaults."""
    from photon_ml_tpu.cli import game_train

    out = os.path.join(WORK, f"out-game-{tag}")
    cfg = write_json(
        os.path.join(WORK, f"game-{tag}.json"),
        game_config(fixture, out, **extra),
    )
    game_train.main(["--config", cfg, "--convergence-report"])

    with open(os.path.join(out, "log-message.txt")) as f:
        text = f.read()
    updates = [
        (int(i), c, float(v))
        for i, c, v in re.findall(
            r"iter=(\d+) coord=(\S+) objective=(\S+)", text
        )
    ]
    check(f"game[{tag}].updates", len(updates) == 4, str(updates))
    objs = [v for _, _, v in updates]
    check(
        f"game[{tag}].objective_non_increasing",
        all(np.isfinite(objs)) and all(
            b <= a * (1 + MONOTONE_RTOL) for a, b in zip(objs, objs[1:])
        ),
        f"{objs}",
    )
    with open(os.path.join(out, "convergence-report.json")) as f:
        report = json.load(f)
    for fleet in report["last_fleet"]:
        log(
            f"game[{tag}] solve coord={fleet['coordinate']} "
            f"iter={fleet['iteration']} entities={fleet['entities']} "
            f"reasons={fleet['reason_counts']} "
            f"median_iters={fleet['median_iters']}"
        )
    check(
        f"game[{tag}].reasons_reported",
        report["reason_counts"] and all(
            f["reason_counts"] for f in report["last_fleet"]
        ),
        str(report["reason_counts"]),
    )

    w_g, table = load_game(out, sizes)
    check(
        f"game[{tag}].finite",
        bool(np.isfinite(w_g).all() and np.isfinite(table).all())
        and w_g.shape == (sizes["d_fixed"],)
        and table.shape == (sizes["entities"], sizes["d_re"]),
        f"fixed {w_g.shape}, table {table.shape}",
    )
    z = game_margins(w_g, table, fixture["train"])
    ref_obj = (
        logloss_sum(z, fixture["train"]["y"])
        + 0.5 * 1.0 * float(w_g @ w_g)
        + 0.5 * 1.0 * float(np.sum(table * table))
    )
    rel = abs(objs[-1] - ref_obj) / abs(ref_obj)
    check(
        f"game[{tag}].objective_vs_float64",
        rel <= OBJECTIVE_RTOL,
        f"device {objs[-1]:.6g} vs numpy f64 {ref_obj:.6g} "
        f"(rel {rel:.2e}, tol {OBJECTIVE_RTOL})",
    )
    train_auc = auc(z, fixture["train"]["y"])
    check(
        f"game[{tag}].train_auc", train_auc >= TRAIN_AUC_FLOOR,
        f"{train_auc:.4f} (floor {TRAIN_AUC_FLOOR})",
    )
    return dict(
        out=out, w_g=w_g, table=table, objective=objs[-1],
        objective_rel_err_vs_f64=rel, train_auc=train_auc,
        reasons=report["reason_counts"],
    )


def run_batch_scoring(fixture, model_dir, sizes):
    from photon_ml_tpu.cli import score
    from photon_ml_tpu.io.avro import read_avro_file

    out = os.path.join(WORK, "out-scores")
    cfg = write_json(
        os.path.join(WORK, "score.json"),
        {
            "input": [os.path.join(fixture["root"], "heldout")],
            "model_dir": model_dir, "output_dir": out,
            "model_kind": "game", "task": "LOGISTIC_REGRESSION",
            "evaluate": True, "overwrite": True,
        },
    )
    score.main(["--config", cfg])
    _, recs = read_avro_file(
        os.path.join(out, "scores", "part-00000.avro")
    )
    by_uid = {r["uid"]: r["predictionScore"] for r in recs}
    held = fixture["held"]
    batch = np.asarray([by_uid[u] for u in held["uids"]], np.float64)
    check(
        "score.shape_finite",
        batch.shape == (sizes["heldout_rows"],)
        and bool(np.isfinite(batch).all()),
        f"{batch.shape}",
    )
    return batch


def run_server(fixture, model_dir, sizes, batch_margins, extra_argv=()):
    """``cli.serve.main`` on THIS (main) thread, answering over its TCP
    socket; a client thread sends held-out rows, then SIGTERMs the
    process — the server's own graceful drain is the way out."""
    from photon_ml_tpu import obs
    from photon_ml_tpu.cli import serve

    held = fixture["held"]
    n, warm = sizes["served_rows"], sizes["served_warm"]
    g_names = [f"g{j}" for j in range(sizes["d_fixed"])]
    u_names = [f"u{j}" for j in range(sizes["d_re"])]
    lines = [
        json.dumps({
            "features": {
                **dict(zip(g_names, held["xg"][i].tolist())),
                **dict(zip(u_names, held["xu"][i].tolist())),
            },
            "entities": {"userId": f"u{held['users'][i]}"},
        })
        for i in range(n)
    ]
    with socket.socket() as s:  # a free port
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    result, server_done = {}, threading.Event()

    def client():
        try:
            deadline = time.monotonic() + 600
            while True:
                if server_done.is_set():
                    raise RuntimeError("server exited before listening")
                try:
                    conn = socket.create_connection(
                        ("127.0.0.1", port), timeout=600
                    )
                    break
                except ConnectionRefusedError:
                    if time.monotonic() > deadline:
                        raise
                    time.sleep(0.2)
            scores = []
            with conn, conn.makefile("rw", encoding="utf-8") as io:
                def exchange(chunk):
                    io.write("\n".join(chunk) + "\n")
                    io.flush()
                    for _ in chunk:
                        scores.append(json.loads(io.readline()))

                for lo in range(0, warm, 64):
                    exchange(lines[lo:min(lo + 64, warm)])
                steady0 = obs.xla_compile_events()
                for lo in range(warm, n, 64):
                    exchange(lines[lo:min(lo + 64, n)])
                result["steady_compiles"] = (
                    obs.xla_compile_events() - steady0
                )
                io.write('{"cmd": "stats"}\n')
                io.flush()
                result["stats"] = json.loads(io.readline())
            result["scores"] = scores
        except BaseException as e:  # re-raised on the main thread
            result["error"] = e
        finally:
            if not server_done.is_set():
                os.kill(os.getpid(), signal.SIGTERM)

    # a no-op handler under the server's own: a SIGTERM that lands after
    # the server restored handlers must not kill the smoke
    prev = signal.signal(signal.SIGTERM, lambda *a: None)
    t = threading.Thread(target=client, name="smoke-client")
    t.start()
    try:
        serve.main([
            "--model-dir", model_dir, "--socket", str(port), *extra_argv,
        ])
    finally:
        server_done.set()
        t.join()
        signal.signal(signal.SIGTERM, prev)
    if "error" in result:
        raise result["error"]

    bad = [r for r in result["scores"] if "score" not in r]
    check("serve.all_answered", not bad, f"{len(bad)} errors: {bad[:2]}")
    served = np.asarray([r["score"] for r in result["scores"]], np.float64)
    diff = float(np.max(np.abs(served - batch_margins[:n])))
    check(
        "serve.matches_batch", diff <= SERVED_VS_BATCH_ATOL,
        f"max |served - batch| {diff:.2e} over {n} rows "
        f"(tol {SERVED_VS_BATCH_ATOL})",
    )
    check(
        "serve.zero_steady_state_compiles",
        result["steady_compiles"] == 0,
        f"{result['steady_compiles']} compile requests after the first "
        f"{warm} rows",
    )
    return dict(max_abs_diff_vs_batch=diff, answered=len(served))


def glm_config(fixture, out_dir, **extra):
    return {
        "train_input": [os.path.join(fixture["root"], "train")],
        "output_dir": out_dir,
        "task": "LOGISTIC_REGRESSION",
        "optimizer": "LBFGS",
        "reg_type": "L2",
        # >= 2 weights: the default path_mode="scan" lambda path with its
        # DONATED warm-start carry (live on the chip, skipped on CPU)
        "reg_weights": [10.0, 1.0],
        "max_iters": 8,
        "sparse": True,
        "feature_file": os.path.join(fixture["root"], "all.features"),
        "overwrite": True,
        **extra,
    }


def run_sparse_glm(fixture, tag, **extra):
    from photon_ml_tpu.cli import train
    from photon_ml_tpu.io.models import load_glm_model
    from photon_ml_tpu.io.vocab import FeatureVocabulary

    out = os.path.join(WORK, f"out-glm-{tag}")
    cfg = write_json(
        os.path.join(WORK, f"glm-{tag}.json"),
        glm_config(fixture, out, **extra),
    )
    train.main(["--config", cfg])
    with open(os.path.join(out, "log-message.txt")) as f:
        text = f.read()
    values = {
        float(lam): (int(it), float(v))
        for lam, it, v in re.findall(
            r"lambda=(\S+): iters=(\d+) value=(\S+)", text
        )
    }
    check(
        f"glm[{tag}].path", sorted(values) == [1.0, 10.0]
        and all(np.isfinite(v) for _, v in values.values()),
        str(values),
    )
    vocab = FeatureVocabulary.load(os.path.join(out, "feature-index.txt"))
    order = [vocab.key_to_index[f"f{j}\x01"] for j in range(fixture["d"])]
    coefs = {}
    for i, lam in enumerate((10.0, 1.0)):
        c, _ = load_glm_model(
            os.path.join(out, "models", f"{i}_lambda_{lam:g}.avro"), vocab
        )
        w = np.asarray(c.means, np.float64)[order]
        check(
            f"glm[{tag}].finite[{lam:g}]",
            w.shape == (fixture["d"],) and bool(np.isfinite(w).all())
            and float(np.abs(w).max()) > 0,
            f"|w|_max {np.abs(w).max():.3g}",
        )
        coefs[lam] = w
    return dict(
        out=out, values=values, coefs=coefs, vocab=vocab, order=order
    )


def check_sparse_gradient(fixture, glm, lam):
    """Gradient of the L2 logistic objective at the RETURNED point: the
    library's sparse pass on the device vs a scipy CSR in float64."""
    import jax
    import jax.numpy as jnp
    import scipy.sparse as sp

    from photon_ml_tpu.io.ingest import IngestSource
    from photon_ml_tpu.ops.losses import LOGISTIC_LOSS
    from photon_ml_tpu.ops.objective import GLMObjective

    n, d = fixture["n"], fixture["d"]
    w = glm["coefs"][lam]
    x = sp.csr_matrix(
        (
            fixture["vals"].astype(np.float64).ravel(),
            fixture["cols"].ravel(),
            np.arange(0, n * fixture["cols"].shape[1] + 1,
                      fixture["cols"].shape[1]),
        ),
        shape=(n, d),
    )
    ref = x.T @ (_sigmoid(x @ w) - fixture["y"]) + lam * w

    batch, _, _ = IngestSource(
        [os.path.join(fixture["root"], "train")]
    ).labeled_batch(glm["vocab"], sparse=True, dtype=jnp.float32)
    # a gradient sums over rows, so only the COLUMN order must match
    order = glm["order"]
    w_dev = np.zeros(d, np.float32)
    w_dev[order] = w
    obj = GLMObjective(loss=LOGISTIC_LOSS, l2_weight=lam)
    _, g = jax.jit(obj.value_and_grad)(jnp.asarray(w_dev), batch)
    g = np.asarray(g, np.float64)[order]
    rel = float(np.linalg.norm(g - ref) / np.linalg.norm(ref))
    check(
        "glm.gradient_vs_csr", rel <= SPARSE_GRAD_RTOL,
        f"|g_device - g_csr| / |g_csr| = {rel:.2e} at lambda={lam:g}, "
        f"|g| {np.linalg.norm(ref):.3g} (tol {SPARSE_GRAD_RTOL})",
    )
    return rel


def run_streamed_dense_glm(game_fx, sizes):
    """``cli.train.main --streamed-ingest`` on the GAME rows' 64 global
    columns: the ingest pipeline's DONATED device deposit
    (io/pipeline.py) is live only off-CPU, like the lambda path's carry."""
    from photon_ml_tpu.cli import train
    from photon_ml_tpu.io.models import load_glm_model
    from photon_ml_tpu.io.vocab import FeatureVocabulary

    out = os.path.join(WORK, "out-glm-streamed")
    cfg = write_json(
        os.path.join(WORK, "glm-streamed.json"),
        {
            "train_input": [os.path.join(game_fx["root"], "train")],
            "output_dir": out,
            "task": "LOGISTIC_REGRESSION",
            "optimizer": "TRON",
            "reg_type": "L2",
            "reg_weights": [10.0, 1.0],
            "max_iters": 10,
            "feature_file": os.path.join(game_fx["root"], "global.features"),
            "streamed_ingest": True,
            "overwrite": True,
        },
    )
    train.main(["--config", cfg])
    with open(os.path.join(out, "log-message.txt")) as f:
        text = f.read()
    values = {
        float(lam): float(v)
        for lam, v in re.findall(r"lambda=(\S+): iters=\d+ value=(\S+)", text)
    }
    vocab = FeatureVocabulary.load(os.path.join(out, "feature-index.txt"))
    order = [
        vocab.key_to_index[f"g{j}\x01"] for j in range(sizes["d_fixed"])
    ]
    c, _ = load_glm_model(
        os.path.join(out, "models", "1_lambda_1.avro"), vocab
    )
    w = np.asarray(c.means, np.float64)[order]
    part = game_fx["train"]
    ref = logloss_sum(part["xg"].astype(np.float64) @ w, part["y"]) + (
        0.5 * float(w @ w)
    )
    rel = abs(values[1.0] - ref) / abs(ref)
    check(
        "glm_streamed.objective_vs_float64",
        sorted(values) == [1.0, 10.0] and rel <= OBJECTIVE_RTOL,
        f"device {values[1.0]:.6g} vs numpy f64 {ref:.6g} at lambda=1 "
        f"(rel {rel:.2e}, tol {OBJECTIVE_RTOL}) over {part['y'].size} "
        "streamed rows",
    )
    return rel


def rel_l2(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def phase_multichip(run, game_fx, sparse_fx, sizes, one_chip):
    """The sharded paths on a 4-device host, each against its one-chip
    result; every mesh device must hold memory while it runs."""
    import jax

    devs = jax.devices()[:4]
    out = {}

    with run.phase("5a_game_entity_shards_4"), device_memory_watch(
        devs
    ) as seen:
        g4 = run_game_trainer(game_fx, sizes, "es4", entity_shards=4)
    require_all_devices_used(seen, "entity-sharded GAME")
    g1 = one_chip["game"]
    d_obj = abs(g4["objective"] - g1["objective"]) / abs(g1["objective"])
    d_w = rel_l2(g4["w_g"], g1["w_g"])
    d_t = rel_l2(g4["table"], g1["table"])
    check(
        "multichip.game_vs_one_chip",
        d_obj <= SHARDED_OBJECTIVE_RTOL and d_w <= SHARDED_COEF_RTOL
        and d_t <= SHARDED_COEF_RTOL,
        f"objective rel {d_obj:.2e} (tol {SHARDED_OBJECTIVE_RTOL}); "
        f"fixed rel-L2 {d_w:.2e}, table rel-L2 {d_t:.2e} "
        f"(tol {SHARDED_COEF_RTOL})",
    )
    out["game"] = dict(
        objective_rel=d_obj, fixed_rel_l2=d_w, table_rel_l2=d_t,
        bytes_in_use=seen,
    )

    with run.phase("5b_glm_mesh_data2_feature2"), device_memory_watch(
        devs
    ) as seen:
        m4 = run_sparse_glm(
            sparse_fx, "mesh", mesh_shape={"data": 2, "feature": 2}
        )
    require_all_devices_used(seen, "('data','feature') mesh GLM")
    m1 = one_chip["glm"]
    rows = {}
    for lam in (10.0, 1.0):
        d_v = abs(m4["values"][lam][1] - m1["values"][lam][1]) / abs(
            m1["values"][lam][1]
        )
        d_c = rel_l2(m4["coefs"][lam], m1["coefs"][lam])
        rows[lam] = (d_v, d_c)
    check(
        "multichip.glm_vs_one_chip",
        all(
            v <= SHARDED_OBJECTIVE_RTOL and c <= SHARDED_COEF_RTOL
            for v, c in rows.values()
        ),
        "; ".join(
            f"lambda={lam:g}: value rel {v:.2e}, coef rel-L2 {c:.2e}"
            for lam, (v, c) in rows.items()
        )
        + f" (tols {SHARDED_OBJECTIVE_RTOL}, {SHARDED_COEF_RTOL})",
    )
    out["glm"] = dict(
        by_lambda={f"{lam:g}": r for lam, r in rows.items()},
        bytes_in_use=seen,
    )

    with run.phase("5c_serve_shards_4"), device_memory_watch(devs) as seen:
        s4 = run_server(
            game_fx, g1["out"], sizes, one_chip["batch_margins"],
            extra_argv=("--serving-shards", "4"),
        )
    require_all_devices_used(seen, "entity-sharded serving")
    out["serve"] = dict(**s4, bytes_in_use=seen)
    return out


# ---- main ----------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearsal", action="store_true",
        help="run off-TPU at toy sizes (debugging only; the summary "
        "says rehearsal: true)",
    )
    args = ap.parse_args(argv)
    t_start = time.time()

    import jax
    import jaxlib

    from photon_ml_tpu import obs
    from photon_ml_tpu.obs.xla_cost import require_device_peaks
    from photon_ml_tpu.utils import enable_compilation_cache
    from photon_ml_tpu.utils.compile_cache import CACHE_DIR_ENV

    dev = jax.devices()[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": jax.device_count(),
    }
    log(f"device: {device}")
    if dev.platform != "tpu" and not args.rehearsal:
        log(
            "no TPU: jax reports "
            f"{dev.platform!r}. This smoke proves the chip path and "
            "refuses to run elsewhere (--rehearsal debugs on CPU at toy "
            "sizes)."
        )
        return 2
    if dev.platform == "tpu":
        require_device_peaks()  # an unlisted chip is an error here
    # first: programs compiled before a directory is known are not cached
    cache_dir = enable_compilation_cache()
    obs.install_compile_listener()
    sizes = REHEARSAL_SIZES if args.rehearsal else FULL_SIZES
    log(f"sizes: {sizes}")
    log(f"compile cache: {cache_dir} (from {CACHE_DIR_ENV}: "
        f"{bool(os.environ.get(CACHE_DIR_ENV))})")

    run = Run()
    if os.path.isdir(WORK):
        shutil.rmtree(WORK)
    os.makedirs(WORK)
    os.makedirs(REPORT, exist_ok=True)
    try:
        with run.phase("1_native_reader"):
            native_so = phase_native(t_start)

        with run.phase("1b_fixtures"):
            rng = np.random.default_rng(20260926)
            game_fx = make_game_fixture(sizes, rng)
            sparse_fx = make_sparse_fixture(sizes, rng)
            n_train = game_fx["train"]["y"].size
            check(
                "fixtures.rows_per_entity",
                game_fx["rows_per_entity"] >= 4.0,
                f"{n_train} rows / {sizes['entities']} entities = "
                f"{game_fx['rows_per_entity']:.2f} (max "
                f"{game_fx['max_rows']})",
            )
        sizes_used = dict(
            sizes, game_train_rows=int(n_train),
            game_rows_per_entity=round(game_fx["rows_per_entity"], 2),
            game_max_rows_per_entity=game_fx["max_rows"],
        )

        with run.phase("2_game_train"):
            game = run_game_trainer(game_fx, sizes, "one")

        with run.phase("3a_batch_score"):
            batch_margins = run_batch_scoring(game_fx, game["out"], sizes)
            ref = game_margins(game["w_g"], game["table"], game_fx["held"])
            margin_err = float(np.max(np.abs(batch_margins - ref)))
            check(
                "score.matches_float64", margin_err <= MARGIN_ATOL,
                f"max |device - numpy f64| {margin_err:.2e} over "
                f"{ref.size} rows, max |z| {np.abs(ref).max():.2f} "
                f"(tol {MARGIN_ATOL})",
            )

        with run.phase("3b_serve"):
            served = run_server(game_fx, game["out"], sizes, batch_margins)

        with run.phase("4_sparse_glm"):
            glm = run_sparse_glm(sparse_fx, "one")
            grad_rel = check_sparse_gradient(sparse_fx, glm, 1.0)

        with run.phase("4b_glm_streamed_dense"):
            streamed_rel = run_streamed_dense_glm(game_fx, sizes)

        one_chip = dict(game=game, glm=glm, batch_margins=batch_margins)
        if jax.device_count() >= 4:
            multichip = phase_multichip(
                run, game_fx, sparse_fx, sizes, one_chip
            )
        else:
            multichip = {
                "skipped": f"needs >= 4 devices, jax reports "
                f"{jax.device_count()}"
            }
            log(f"5_multichip skipped: {multichip['skipped']}")
    finally:
        # keep the drivers' logs (small); drop fixtures and models
        for root, _, files in os.walk(WORK):
            for name in files:
                if name in ("log-message.txt", "convergence-report.json"):
                    dst = os.path.join(
                        REPORT, os.path.basename(root) + "." + name
                    )
                    shutil.copyfile(os.path.join(root, name), dst)
        shutil.rmtree(WORK)

    from importlib import metadata

    try:
        libtpu_version = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu_version = None
    totals = {
        k: sum(p[k] for p in run.phases.values())
        for k in ("compile_requests", "cache_hits", "backend_compiles")
    }
    summary = {
        "ok": True,
        "device": device,
        "rehearsal": bool(args.rehearsal),
        "versions": {
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu_version,
            "python": sys.version.split()[0],
        },
        "seconds_total": round(time.time() - t_start, 1),
        "phases": run.phases,
        "compiles": totals,
        "cache_dir": cache_dir,
        "cache_dir_from_env": bool(os.environ.get(CACHE_DIR_ENV)),
        "jax_compilation_cache_dir": jax.config.jax_compilation_cache_dir,
        "native_available": True,
        "native_library": os.path.relpath(native_so, HERE),
        "sizes": sizes_used,
        "checks": {
            "game_objective": game["objective"],
            "game_objective_rel_err_vs_float64":
                game["objective_rel_err_vs_f64"],
            "game_train_auc": round(game["train_auc"], 4),
            "game_convergence_reasons": game["reasons"],
            "batch_margin_max_abs_err_vs_float64": margin_err,
            "served_vs_batch_max_abs_diff":
                served["max_abs_diff_vs_batch"],
            "served_rows": served["answered"],
            "sparse_gradient_rel_err_vs_csr": grad_rel,
            "streamed_glm_objective_rel_err_vs_float64": streamed_rel,
            "glm_values": {
                f"{lam:g}": v for lam, v in glm["values"].items()
            },
        },
        "multichip": multichip,
        "claim": None,
    }
    write_json(os.path.join(REPORT, "summary.json"), summary)
    print(json.dumps(summary), flush=True)
    # the result line: exactly these keys, last on stdout
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
