"""Benchmarks vs CPU baselines on the BASELINE.json configs.

Five measurements covering BASELINE.json's five configs — dense logistic
(Criteo proxy), linear + elastic net, GAME fixed + one random effect,
GAME fixed + multi random effects + MF interaction (fixed-effect-only is
the degenerate single-coordinate case of those two) — plus a sparse
wide-feature configuration:

1. HEADLINE — L2 logistic regression, dense 1M x 256 (the Criteo-logistic
   wall-clock proxy): one full TRON solve to the reference's convergence
   profile (tol 1e-5, maxIter 20, <=20 CG/step — ``TRON.scala:230-237``),
   features stored bfloat16 on device (f32 solver state), timed as the
   median of 3 solves at distinct lambdas on resident data. Baseline:
   sklearn LogisticRegression (lbfgs, CPU) at matched (+-0.002) held-out
   AUC. Also reports achieved FLOP/s and MFU from the exact value/grad +
   CG Hessian-vector counts the solver tracks.

2. GAME — fixed-effect (d=64) + one random effect (30k entities, d=16)
   coordinate descent on 1.2M rows (BASELINE.json north star #2, at a
   cluster-scale shape): iterations/sec after a warmup pass, vs the SAME
   code on CPU (subprocess with JAX_PLATFORMS=cpu — the stand-in for the
   reference's Spark-CPU executor math, identical convergence criteria
   by construction).

3. GAME MULTI — fixed + per-user random effect + factored (latent-dim-4)
   per-item interaction at 600k rows / 10k users / 5k items: CD
   iterations/sec on device vs the same code on CPU (measured r4: 0.94
   vs 0.34 iters/s, 2.8x at matched objective).

4. LINEAR + ELASTIC NET — 500k x 256 linear regression via OWL-QN vs
   sklearn ElasticNet at the exactly-mapped objective
   (``bench_linear_elastic_net``).

5. SPARSE — L2 logistic at 200k x 120k (nnz 32/row), the >100k-feature
   regime of ``util/PalDBIndexMap.scala:43``, in two configurations:
   (a) HEADLINE, Zipf-distributed columns (the CTR/Criteo reality):
   hybrid dense-hot/sparse-cold split + the reference's scale-by-std
   normalization algebra vs sklearn on the identically-scaled CSR —
   matched-or-better AUC required. (b) uniform-random columns (no head, perfect
   conditioning): the XLA gather/scatter bound (~130M elem/s) lets the
   cache-friendly CPU CSR win on ONE chip — reported honestly; the
   'feature' mesh axis divides exactly that bound (the
   `sparse_fs_scaling` curve below).

6. GAME WIDE-SPARSE — CD iters/sec with a 60k-column SPARSE fixed-effect
   shard (24 GB dense — infeasible; padded-ELL + coordinate-local hybrid
   MXU split) plus a 2k-user random effect: the capability regime of the
   reference's off-heap index, measured rather than claimed.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"}
where extra carries the transfer time, MFU, and the GAME/sparse numbers.
"""

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time

import numpy as np


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# per-phase wall clock of the full bench run (seconds) — lands in the
# BENCH record's extra so the perf trajectory records where the time
# went, not just totals
_PHASE_S = {}


def _phase(label, fn, *args, **kwargs):
    t0 = time.perf_counter()
    try:
        return fn(*args, **kwargs)
    finally:
        _PHASE_S[label] = round(time.perf_counter() - t0, 3)


def _device_record():
    """Where this process runs, as jax reports it — every record this
    file prints carries it."""
    import jax

    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "count": jax.device_count(),
    }


def _emit(out, device):
    print(json.dumps({**out, "device": device}))


# Roofline peaks live in the shared cost book
# (photon_ml_tpu.obs.xla_cost.DEVICE_PEAKS, keyed by device_kind): bench,
# training spans, and serving all divide by the SAME peaks. Imported
# lazily inside the benches — this module must stay importable before
# backend selection (--cpu).


def _dense_click_data(n, n_test, d, seed=42):
    rng = np.random.default_rng(seed)
    w_true = (
        rng.standard_normal(d).astype(np.float32)
        * (rng.uniform(size=d) < 0.3)
    )
    x = rng.standard_normal((n + n_test, d), dtype=np.float32)
    p = 1.0 / (1.0 + np.exp(-(x @ w_true) - 0.5))
    y = (rng.uniform(size=n + n_test) < p).astype(np.float32)
    return x[:n], y[:n], x[n:], y[n:]


def measure_fetch_latency(samples: int = 12):
    """Device->host VALUE-FETCH latency of a tiny chained computation:
    the comparability pin for cross-round wall-clocks. Materializing a
    VALUE on the host is what every solve wall-clock in this file ends
    with, so its cost is reported beside them (keys ``rtt_ms*``, kept
    for the BENCH history). The chain (each input depends on the
    previous output, with a drift that survives f32 rounding and has no
    fixed point) keeps every step a real dispatch."""
    import jax
    import jax.numpy as jnp

    x = jnp.full((8,), 0.5)

    @jax.jit
    def step(v):
        return v * 1.001 + 0.0005

    x = step(x)
    float(x[0])  # compile + first fetch
    times = []
    for _ in range(samples):
        t0 = time.perf_counter()
        x = step(x)
        float(x[0])  # host materialization = the fetch
        times.append(time.perf_counter() - t0)
    times.sort()
    med = times[len(times) // 2]
    return {
        "rtt_ms": round(med * 1e3, 2),
        "rtt_ms_min": round(times[0] * 1e3, 2),
        "rtt_ms_max": round(times[-1] * 1e3, 2),
    }


def chained_vg_pass_ms(obj, batch, w0, steps=10, rtt_s=None):
    """THE methodology for irregular pass-cost measurements (shared by
    bench_sparse's ceiling decomposition): a fori_loop chain of
    value_and_grad passes (w <- w - 1e-6 g) inside one jit, warmed once,
    with the value-fetch latency subtracted. Chaining keeps every pass
    a real, data-dependent execution."""
    import jax
    from jax import lax

    @jax.jit
    def run(w, b):
        def step(i, w):
            _, g = obj.value_and_grad(w, b)
            return w - 1e-6 * g

        return lax.fori_loop(0, steps, step, w)

    out = run(w0, batch)
    out.block_until_ready()
    if rtt_s is None:
        rtt_s = measure_fetch_latency(4)["rtt_ms"] / 1e3
    t0 = time.perf_counter()
    out = run(out, batch)
    float(out[0])
    return max((time.perf_counter() - t0 - rtt_s) / steps * 1e3, 0.0)


def bench_glm_dense():
    import jax
    import jax.numpy as jnp
    import ml_dtypes

    from photon_ml_tpu.core.types import LabeledBatch
    from photon_ml_tpu.models import (
        GLMTrainingConfig,
        OptimizerType,
        TaskType,
        train_glm,
    )
    from photon_ml_tpu.ops import RegularizationContext
    from photon_ml_tpu.ops.metrics import area_under_roc_curve

    n, n_test, d = 1_000_000, 100_000, 256
    lam = 1.0
    log(f"backend={jax.default_backend()} devices={jax.devices()}")
    jnp.zeros((8, 8)).block_until_ready()  # backend warmup outside timers

    log(f"generating synthetic click data: n={n} d={d}")
    xtr, ytr, xte, yte = _dense_click_data(n, n_test, d)

    # features ship and live as bf16 (half the transfer bytes + HBM traffic;
    # solver state stays f32 via solve_dtype) — AUC match asserted below
    t0 = time.perf_counter()
    x_bf16 = xtr.astype(ml_dtypes.bfloat16)
    cast_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    xd = jax.device_put(x_bf16)
    xd.block_until_ready()
    transfer_s = time.perf_counter() - t0
    gb = x_bf16.nbytes / 1e9
    log(
        f"host cast f32->bf16: {cast_s:.1f}s; transfer {gb:.2f} GB: "
        f"{transfer_s:.1f}s ({gb / transfer_s * 1e3:.0f} MB/s)"
    )
    yd = jax.device_put(ytr)
    ones = jnp.ones((n,), jnp.float32)
    batch = LabeledBatch(xd, yd, jnp.zeros((n,), jnp.float32), ones, ones)

    # ONE objective pass's cost record from the shared cost book (XLA's
    # own FLOPs + bytes for the fused value/grad — the 2-matmul unit of
    # the solver pass counts below). The analytic fallbacks reproduce
    # the former hand arithmetic (4nd FLOPs; two bf16 design reads) on
    # backends without a cost analysis, so MFU/hbm_util stay comparable
    # across rounds either way.
    from photon_ml_tpu import obs
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.ops.objective import GLMObjective

    _obj_cost = GLMObjective(
        loss=loss_for_task(TaskType.LOGISTIC_REGRESSION), l2_weight=lam
    )
    pass_rec = obs.cost_book().record(
        "glm.objective_pass",
        jax.jit(lambda w_, b_: _obj_cost.value_and_grad(w_, b_)).lower(
            jnp.zeros((d,), jnp.float32), batch
        ),
        bucket=f"{n}x{d}",
        analytic_flops=4.0 * n * d,
        analytic_bytes=2.0 * x_bf16.nbytes,
        # roofline traffic = two bf16 design reads per pass (margins +
        # backprojection): XLA's static count includes bf16->f32
        # convert materializations the fused matmul never pays, and the
        # HBM ceiling must be judged on real traffic
        roofline_bytes=2.0 * x_bf16.nbytes,
    )
    log(
        f"cost book glm.objective_pass[{n}x{d}]: "
        f"{pass_rec.flops / 1e9:.2f} GFLOP, "
        f"{(pass_rec.bytes_accessed or 0) / 1e9:.2f} GB accessed/pass "
        f"({pass_rec.source})"
    )

    def config(lam_):
        return GLMTrainingConfig(
            task=TaskType.LOGISTIC_REGRESSION,
            optimizer=OptimizerType.TRON,
            regularization=RegularizationContext("L2"),
            reg_weights=(lam_,),
            tolerance=1e-5,
            max_iters=20,
            track_states=False,
        )

    # compile + warm at a different lambda (identical repeated calls could
    # be served from caches and would not measure a real solve)
    t0 = time.perf_counter()
    (warm,) = train_glm(batch, config(10.0 * lam))
    np.asarray(warm.result.w)
    log(f"first solve (compile+run): {time.perf_counter() - t0:.2f}s")

    times, aucs = [], []
    for rep in range(3):
        t0 = time.perf_counter()
        (tm,) = train_glm(batch, config(lam + 0.01 * rep))
        w_dev = np.asarray(tm.model.coefficients.means)
        dt = time.perf_counter() - t0
        iters = int(tm.result.iterations)
        cg = int(tm.result.cg_iterations)
        # counted design passes in the cost record's unit (one fused
        # value/grad = 2 matmuls; each CG Hessian-vector product rides
        # the vgc acceptance path) — solvers.common.design_passes, the
        # SAME accounting traced solves attach to their spans
        from photon_ml_tpu.solvers import design_passes

        passes = design_passes(tm.result)
        fl = passes * pass_rec.flops
        auc = float(
            area_under_roc_curve(
                jnp.asarray(yte),
                jnp.asarray(xte @ w_dev.astype(np.float32)),
                jnp.ones(n_test),
            )
        )
        log(
            f"device solve {rep}: {dt:.3f}s iters={iters} cg={cg} "
            f"auc={auc:.4f} achieved={fl / dt / 1e12:.2f} TFLOP/s"
        )
        times.append(dt)
        aucs.append(auc)
    tpu_wall_s = float(np.median(times))
    med = times.index(sorted(times)[1])
    auc_dev = aucs[med]

    # Pipelined device time: each wall above includes ONE device->host
    # value fetch. Enqueue K independent solves without materializing
    # between them and block once: total = fetch + K * device_time.
    import jax as _jax

    k_pipe = 5
    rtt_probe = measure_fetch_latency(6)
    t0 = time.perf_counter()
    pipe = [
        train_glm(batch, config(lam + 0.02 + 0.001 * i))[0]
        for i in range(k_pipe)
    ]
    for tm_ in pipe:
        _jax.block_until_ready(tm_.model.coefficients.means)
    # end with a VALUE materialization: that is the round trip the probe
    # measures (block_until_ready alone completes without one here), so
    # the subtraction below removes exactly what this wall paid once
    np.asarray(pipe[-1].model.coefficients.means)
    pipe_total = time.perf_counter() - t0
    tpu_s = max(pipe_total - rtt_probe["rtt_ms"] / 1e3, 1e-9) / k_pipe
    # FLOP numerator from the SAME solves the time denominator measures
    # (different lambdas can take different iteration/CG counts)
    pipe_passes = [design_passes(tm_.result) for tm_ in pipe]
    passes_per_solve = float(np.mean(pipe_passes))
    log(
        f"pipelined {k_pipe} solves: {pipe_total:.3f}s total "
        f"(rtt {rtt_probe['rtt_ms']:.0f} ms) -> {tpu_s:.4f}s/solve device "
        f"({passes_per_solve:.1f} passes/solve)"
    )
    # MFU / HBM utilization from the shared cost book: counted passes x
    # the pass record's FLOPs/bytes over device time, against the ONE
    # set of roofline peaks (obs.xla_cost) traced training spans use
    hw = pass_rec.achieved(tpu_s, passes=passes_per_solve)
    pipe_fl = hw.get("flops", 0.0)
    mfu = hw.get("mfu", 0.0)
    hbm_util = hw.get("hbm_util", 0.0)

    # Device-resident regularization path (ROADMAP item 1): N lambdas
    # execute as ONE lax.scan program — one dispatch + one RTT for the
    # whole warm-started path, where the host loop paid one of each per
    # lambda. Two numbers gate it: path wall per lambda (the amortized
    # win; compare tpu_wall_incl_rtt_s, which pays a full RTT for ONE
    # solve) and the counted solver dispatches per path (the
    # platform-invariant proof, sentinel-tracked lower-is-better).
    from photon_ml_tpu.obs.dispatch_count import count_dispatches

    def path_config(lams_):
        return GLMTrainingConfig(
            task=TaskType.LOGISTIC_REGRESSION,
            optimizer=OptimizerType.TRON,
            regularization=RegularizationContext("L2"),
            reg_weights=lams_,
            tolerance=1e-5,
            max_iters=20,
            track_states=False,
        )

    n_path = 4
    warm_path = train_glm(batch, path_config((11.0, 3.3, 1.1, 0.37)))
    np.asarray(warm_path[-1].model.coefficients.means)  # compile + warm
    t0 = time.perf_counter()
    path = train_glm(
        batch, path_config((10.0 * lam, 3.0 * lam, lam, 0.3 * lam))
    )
    for tm_ in path:
        _jax.block_until_ready(tm_.model.coefficients.means)
    np.asarray(path[-1].model.coefficients.means)
    path_wall = time.perf_counter() - t0
    with count_dispatches() as dc:
        train_glm(batch, path_config((9.0, 2.9, 0.95, 0.29)))
    dispatches_per_path = float(dc.for_program("solve_path"))
    log(
        f"regularization path: {n_path} lambdas in {path_wall:.3f}s "
        f"({path_wall / n_path:.4f}s/lambda, "
        f"{dispatches_per_path:.0f} solver dispatch(es))"
    )

    from sklearn.linear_model import LogisticRegression

    t0 = time.perf_counter()
    skl = LogisticRegression(
        C=1.0 / lam, fit_intercept=False, tol=1e-5, max_iter=100
    ).fit(xtr, ytr)
    cpu_s = time.perf_counter() - t0
    auc_cpu = float(
        area_under_roc_curve(
            jnp.asarray(yte),
            jnp.asarray(xte @ skl.coef_.ravel().astype(np.float32)),
            jnp.ones(n_test),
        )
    )
    log(f"sklearn baseline: {cpu_s:.3f}s auc={auc_cpu:.4f}")
    if abs(auc_dev - auc_cpu) > 2e-3:
        log(f"WARNING: AUC mismatch device={auc_dev} cpu={auc_cpu}")

    return {
        "tpu_s": tpu_s,
        "tpu_wall_incl_rtt_s": tpu_wall_s,
        "passes_per_solve": passes_per_solve,
        "cpu_s": cpu_s,
        "transfer_s": transfer_s,
        "transfer_gb": gb,
        "mfu": mfu,
        "hbm_util": hbm_util,
        "achieved_tflops": pipe_fl / tpu_s / 1e12,
        "auc_device": auc_dev,
        "auc_cpu": auc_cpu,
        "dispatches_per_path": dispatches_per_path,
        "path_wall_per_lambda_s": path_wall / n_path,
    }


def _build_game_cd(
    n_rows, d_fixed, n_entities, d_user, seed=7,
    fuse_passes="coordinate",
):
    import jax.numpy as jnp

    from photon_ml_tpu.core.tasks import TaskType
    from photon_ml_tpu.game import (
        CoordinateConfig,
        CoordinateDescent,
        FixedEffectCoordinate,
        GameData,
        RandomEffectCoordinate,
        build_bucketed_random_effect_design,
    )
    from photon_ml_tpu.models.training import OptimizerType

    rng = np.random.default_rng(seed)
    # +test rows for a held-out AUC: logits scaled to
    # std 1.5 so the Bayes optimum sits near AUC ~0.85 and the metric is
    # informative (raw logits at this shape are near-separable)
    n_test = 50_000
    nt = n_rows + n_test
    user_all = rng.integers(0, n_entities, size=nt).astype(np.int32)
    xg_all = rng.standard_normal((nt, d_fixed), dtype=np.float32)
    xu_all = rng.standard_normal((nt, d_user), dtype=np.float32)
    w_g = rng.standard_normal(d_fixed).astype(np.float32) * 0.5
    w_u = rng.standard_normal((n_entities, d_user)).astype(np.float32) * 0.5
    logits = xg_all @ w_g + np.einsum("nd,nd->n", xu_all, w_u[user_all])
    logits *= 1.5 / max(float(logits.std()), 1e-12)
    y_all = (rng.uniform(size=nt) < 1.0 / (1.0 + np.exp(-logits))).astype(
        np.float32
    )
    user, xg, xu, y = (
        user_all[:n_rows], xg_all[:n_rows], xu_all[:n_rows],
        y_all[:n_rows],
    )
    # materialized copies: the test slices outlive this function inside
    # the heldout_auc closure, and numpy views would pin the full
    # train+test *_all arrays (hundreds of MB) alongside them
    user_te = np.ascontiguousarray(user_all[n_rows:])
    xg_te = np.ascontiguousarray(xg_all[n_rows:])
    xu_te = np.ascontiguousarray(xu_all[n_rows:])
    y_te = np.ascontiguousarray(y_all[n_rows:])

    data = GameData.create(
        features={"global": xg, "per_user": xu},
        labels=y,
        entity_ids={"userId": user},
    )
    # NEWTON (exact Hessian + Cholesky, one MXU pass per iteration) is the
    # TPU-native choice for these small-d coordinates: measured ~15%
    # faster CD than the reference-default TRON at an equal-or-better
    # objective. The CPU baseline runs the identical config, so the
    # comparison stays convergence-matched.
    fe_cfg = CoordinateConfig(
        shard="global",
        task=TaskType.LOGISTIC_REGRESSION,
        optimizer=OptimizerType.NEWTON,
        reg_weight=1.0,
        max_iters=10,
        tolerance=1e-5,
    )
    re_cfg = CoordinateConfig(
        shard="per_user",
        task=TaskType.LOGISTIC_REGRESSION,
        optimizer=OptimizerType.NEWTON,
        reg_weight=10.0,
        max_iters=10,
        tolerance=1e-5,
        random_effect="userId",
    )
    fixed = FixedEffectCoordinate(data.fixed_effect_batch("global"), fe_cfg)
    # num_buckets=1: this shape's entity sizes are near-uniform, and each
    # bucket costs one SEQUENTIAL vmapped while_loop on device (~250ms of
    # step overhead regardless of bucket size — measured on chip, r4);
    # bucketing pays only under row-count skew
    design = build_bucketed_random_effect_design(
        data, "userId", "per_user", n_entities, num_buckets=1
    )
    random = RandomEffectCoordinate(
        design=design,
        row_features=jnp.asarray(xu),
        row_entities=jnp.asarray(user, jnp.int32),
        full_offsets_base=jnp.zeros((n_rows,), jnp.float32),
        config=re_cfg,
    )
    cd = CoordinateDescent(
        coordinates={"fixed": fixed, "per-user": random},
        labels=jnp.asarray(y),
        base_offsets=jnp.zeros((n_rows,), jnp.float32),
        weights=jnp.ones((n_rows,), jnp.float32),
        task=TaskType.LOGISTIC_REGRESSION,
        # at this scale the one-dispatch-per-pass program did not
        # finish compiling in r4 (~25 min, not re-measured since); the
        # chunked per-coordinate mode keeps 2 dispatches/pass with the
        # rescore + objective fused into each. bench_game_superpass
        # overrides to True at a compact shape.
        fuse_passes=fuse_passes,
    )

    def heldout_auc(model) -> float:
        """AUC of the trained GAME model on the UNSEEN test rows."""
        from photon_ml_tpu.ops.metrics import area_under_roc_curve

        w = np.asarray(model.params["fixed"])
        table = np.asarray(model.params["per-user"])
        margins = xg_te @ w + np.einsum(
            "nd,nd->n", xu_te, table[user_te]
        )
        return float(
            area_under_roc_curve(
                jnp.asarray(y_te),
                jnp.asarray(margins),
                jnp.ones(y_te.shape[0]),
            )
        )

    return cd, heldout_auc


# Cluster-scale shape (the north star is a 64-executor Spark cluster
# workload, BASELINE.json): 1.2M rows / 30k entities. At the former toy
# shape (200k rows / 5k entities) dispatch+tiny-batch overheads dominate
# BOTH platforms and a single CPU core keeps pace; at this scale the
# device's throughput expresses (measured r4: TPU 0.95 s/pass vs CPU
# 9.9 s/pass, identical config and objective -> 10.4x).
GAME_SHAPE = dict(
    n_rows=1_200_000, d_fixed=64, n_entities=30_000, d_user=16
)
GAME_ITERS = 3


def _warm_disjoint(cd):
    """Compile+warm run whose dispatches CANNOT be replayed into the timed
    run: a runtime may short-circuit bit-identical dispatches (the
    r3-r5 one did), and a fresh run()'s FIRST iteration starts from the
    same zero params as a plain warm-up's would — so warm up from a
    perturbed initial model instead, making every timed dispatch novel."""
    import jax

    from photon_ml_tpu.game.descent import GameModel

    params = {
        name: jax.tree_util.tree_map(
            lambda a: a + 1e-3, c.initial_params()
        )
        for name, c in cd.coordinates.items()
    }
    cd.run(num_iterations=1, initial_model=GameModel(params=params))


def bench_game():
    cd, heldout_auc = _build_game_cd(**GAME_SHAPE)
    t0 = time.perf_counter()
    _warm_disjoint(cd)
    log(f"GAME warmup (compile+run): {time.perf_counter() - t0:.2f}s")
    # convergence-health decode (obs.convergence): the per-entity
    # (reason, iterations, final |grad|) trackers ride the run's one
    # batched stats drain regardless; the tracker makes materialize()
    # fold them into fleet summaries, from which the sentinel-tracked
    # convergence.{median_iters,nonconverged_frac} derive. Host numpy
    # over already-fetched arrays — no extra device syncs in the timed
    # window.
    from photon_ml_tpu import obs

    tracker = obs.install_convergence_tracker()
    try:
        t0 = time.perf_counter()
        model, history = cd.run(num_iterations=GAME_ITERS)
        dt = time.perf_counter() - t0
        conv = tracker.report()
    finally:
        obs.uninstall_convergence_tracker()
    iters_per_s = GAME_ITERS / dt
    obj = float(history[-1].objective)
    auc = heldout_auc(model)
    log(
        f"GAME CD: {GAME_ITERS} iterations in {dt:.2f}s "
        f"({iters_per_s:.3f} iters/s) objective={obj:.5f} "
        f"held-out auc={auc:.4f} "
        f"median_iters={conv['median_iters']:g} "
        f"nonconverged_frac={conv['nonconverged_frac']:.4f}"
    )
    out = {
        "iters_per_s": iters_per_s,
        "objective": obj,
        "auc": auc,
        "convergence_median_iters": conv["median_iters"],
        "convergence_nonconverged_frac": conv["nonconverged_frac"],
    }
    return out


# Compact fused-mode shape for the multi-pass dispatch-economy probe:
# big enough that a pass does real work, small enough that the fused
# whole-pass program compiles everywhere the bench runs.
GAME_SUPER_SHAPE = dict(
    n_rows=100_000, d_fixed=32, n_entities=5_000, d_user=8
)
GAME_SUPER_PASSES, GAME_SUPER_K = 6, 3


def bench_game_superpass():
    """Device-resident multi-pass GAME descent (ROADMAP item 1): P
    coordinate-descent passes at K passes per dispatch must execute as
    ceil(P/K) XLA dispatches — counted, not inferred from wall clocks
    (sentinel-tracked lower-is-better ``game_dispatches_per_run``)."""
    import jax

    from photon_ml_tpu.game.descent import GameModel
    from photon_ml_tpu.obs.dispatch_count import count_dispatches

    cd, _ = _build_game_cd(**GAME_SUPER_SHAPE, fuse_passes=True)

    def perturbed(eps):
        return GameModel(
            params={
                name: jax.tree_util.tree_map(
                    lambda a: a + eps, c.initial_params()
                )
                for name, c in cd.coordinates.items()
            }
        )

    t0 = time.perf_counter()
    cd.run(
        num_iterations=GAME_SUPER_K,
        passes_per_dispatch=GAME_SUPER_K,
        initial_model=perturbed(1e-3),
    )
    log(f"superpass warmup (compile+run): {time.perf_counter() - t0:.2f}s")
    t0 = time.perf_counter()
    model, history = cd.run(
        num_iterations=GAME_SUPER_PASSES,
        passes_per_dispatch=GAME_SUPER_K,
    )
    wall = time.perf_counter() - t0
    # counted run from a perturbed start so the runtime cannot replay
    # bit-identical dispatches (_warm_disjoint rationale)
    with count_dispatches() as dc:
        cd.run(
            num_iterations=GAME_SUPER_PASSES,
            passes_per_dispatch=GAME_SUPER_K,
            initial_model=perturbed(2e-3),
        )
    dispatches = float(dc.for_program("superpass"))
    iters_per_s = GAME_SUPER_PASSES / wall
    log(
        f"GAME superpass: {GAME_SUPER_PASSES} passes @ K={GAME_SUPER_K} "
        f"in {wall:.2f}s ({iters_per_s:.3f} iters/s, "
        f"{dispatches:.0f} dispatches) objective="
        f"{float(history[-1].objective):.5f}"
    )
    out = {
        "game_dispatches_per_run": dispatches,
        "superpass_iters_per_s": iters_per_s,
        "objective": float(history[-1].objective),
    }
    return out


def _cpu_subprocess(flag: str, label: str):
    """Run ``bench.py <flag> --cpu`` in a subprocess. The child is
    CPU-FORCED (``JAX_PLATFORMS=cpu`` in its environment plus ``--cpu``):
    this parent has touched jax and holds the chip, which belongs to one
    process at a time. Runs SEQUENTIALLY on purpose: a baseline
    overlapped with device benches would time-share the host cores and
    distort the comparison. A failed child is fatal — a bench record
    with a silently missing baseline is not a record."""
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), flag, "--cpu"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
        timeout=3600,
    )
    sys.stderr.write(proc.stderr)
    if proc.returncode != 0:
        raise RuntimeError(
            f"{label} CPU baseline child failed rc={proc.returncode}"
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _game_cpu_baseline():
    return _cpu_subprocess("--game-only", "GAME")


def _game_multi_cpu_baseline():
    return _cpu_subprocess("--game-multi-only", "GAME multi-RE")


def _sparse_scaling_cpu():
    """The feature-sharded sparse scaling curve in a CPU subprocess
    (8 virtual devices; the live platform may hold a single chip)."""
    return _cpu_subprocess("--sparse-scaling", "sparse scaling")


def bench_linear_elastic_net():
    """BASELINE config #2: linear regression + elastic net (OWL-QN) vs
    sklearn ElasticNet on identical data. Objective mapping: sklearn
    minimizes 1/(2n)||y-Xw||^2 + a*(r|w|_1 + (1-r)/2 ||w||^2); ours is the
    unnormalized sum, so lambda_1 = n a r and lambda_2 = n a (1-r)."""
    import jax.numpy as jnp

    from photon_ml_tpu.core.types import LabeledBatch
    from photon_ml_tpu.models import (
        GLMTrainingConfig,
        OptimizerType,
        TaskType,
        train_glm,
    )
    from photon_ml_tpu.ops import RegularizationContext

    n, d = 500_000, 256
    alpha, ratio = 0.001, 0.5
    rng = np.random.default_rng(5)
    x = rng.standard_normal((n, d), dtype=np.float32)
    w_true = rng.standard_normal(d).astype(np.float32) * (
        rng.uniform(size=d) < 0.2
    )
    y = x @ w_true + rng.standard_normal(n).astype(np.float32)

    batch = LabeledBatch.create(x, y, dtype=jnp.float32)
    cfg = lambda lam: GLMTrainingConfig(
        task=TaskType.LINEAR_REGRESSION,
        optimizer=OptimizerType.LBFGS,
        regularization=RegularizationContext("ELASTIC_NET", alpha=ratio),
        reg_weights=(lam,),
        tolerance=1e-7,
        max_iters=100,
        track_states=False,
    )
    lam = n * alpha
    (warm,) = train_glm(batch, cfg(10.0 * lam))
    np.asarray(warm.result.w)
    t0 = time.perf_counter()
    (tm,) = train_glm(batch, cfg(lam))
    w_dev = np.asarray(tm.model.coefficients.means)
    tpu_s = time.perf_counter() - t0

    from sklearn.linear_model import ElasticNet

    t0 = time.perf_counter()
    skl = ElasticNet(
        alpha=alpha, l1_ratio=ratio, fit_intercept=False, tol=1e-6
    ).fit(x, y)
    cpu_s = time.perf_counter() - t0
    rmse_dev = float(np.sqrt(np.mean((x @ w_dev - y) ** 2)))
    rmse_cpu = float(np.sqrt(np.mean((x @ skl.coef_ - y) ** 2)))
    nnz_dev = int((np.abs(w_dev) > 1e-6).sum())
    nnz_cpu = int((np.abs(skl.coef_) > 1e-6).sum())
    log(
        f"linear+EN 500kx256: device {tpu_s:.3f}s (rmse={rmse_dev:.4f} "
        f"nnz={nnz_dev}) vs sklearn {cpu_s:.3f}s (rmse={rmse_cpu:.4f} "
        f"nnz={nnz_cpu})"
    )
    return {"tpu_s": tpu_s, "cpu_s": cpu_s}


def bench_game_multi_re():
    """BASELINE config #5: fixed effect + TWO random effects with a
    factored (matrix-factorization-style) item interaction, at a
    cluster-scale shape (600k rows, 10k users, 5k items), vs the SAME
    code on CPU (subprocess, identical convergence criteria)."""
    import jax.numpy as jnp

    from photon_ml_tpu.core.tasks import TaskType
    from photon_ml_tpu.game import (
        CoordinateConfig,
        CoordinateDescent,
        FactoredConfig,
        FactoredRandomEffectCoordinate,
        FixedEffectCoordinate,
        GameData,
        RandomEffectCoordinate,
        build_bucketed_random_effect_design,
    )
    from photon_ml_tpu.models.training import OptimizerType

    n_rows, d_fixed, n_users, d_user, n_items, d_item, k = (
        600_000, 32, 10_000, 8, 5_000, 16, 4
    )
    rng = np.random.default_rng(13)
    nt = n_rows + 50_000  # +held-out rows for an informative AUC
    user_a = rng.integers(0, n_users, size=nt).astype(np.int32)
    item_a = rng.integers(0, n_items, size=nt).astype(np.int32)
    xg_a = rng.standard_normal((nt, d_fixed), dtype=np.float32)
    xu_a = rng.standard_normal((nt, d_user), dtype=np.float32)
    xi_a = rng.standard_normal((nt, d_item), dtype=np.float32)
    logits = 0.5 * xg_a[:, 0] + 0.3 * xu_a[:, 0] + 0.2 * xi_a[:, 0]
    y_a = (rng.uniform(size=nt) < 1.0 / (1.0 + np.exp(-logits))).astype(
        np.float32
    )
    user, user_te = user_a[:n_rows], user_a[n_rows:]
    item, item_te = item_a[:n_rows], item_a[n_rows:]
    xg, xg_te = xg_a[:n_rows], xg_a[n_rows:]
    xu, xu_te = xu_a[:n_rows], xu_a[n_rows:]
    xi, xi_te = xi_a[:n_rows], xi_a[n_rows:]
    y, y_te = y_a[:n_rows], y_a[n_rows:]
    data = GameData.create(
        features={"global": xg, "per_user": xu, "per_item": xi},
        labels=y,
        entity_ids={"userId": user, "itemId": item},
    )
    base = dict(
        task=TaskType.LOGISTIC_REGRESSION,
        max_iters=5,
        tolerance=1e-5,
    )
    # NEWTON for the per-entity solves (r5): with the unrolled small-d
    # Cholesky (solvers/newton.py) each vmapped Newton step is pure
    # elementwise work — the lax batched Cholesky that made optimizer
    # choice irrelevant in r4 is gone. The CPU baseline runs the
    # identical config, so the comparison stays convergence-matched.
    fixed = FixedEffectCoordinate(
        data.fixed_effect_batch("global"),
        CoordinateConfig(
            shard="global", optimizer=OptimizerType.NEWTON, reg_weight=1.0,
            **base,
        ),
    )
    # num_buckets=1: near-uniform entity sizes; each bucket is a
    # sequential device cost
    u_design = build_bucketed_random_effect_design(
        data, "userId", "per_user", n_users, num_buckets=1
    )
    users = RandomEffectCoordinate(
        design=u_design,
        row_features=jnp.asarray(xu),
        row_entities=jnp.asarray(user),
        full_offsets_base=jnp.zeros((n_rows,), jnp.float32),
        config=CoordinateConfig(
            shard="per_user", optimizer=OptimizerType.NEWTON,
            reg_weight=10.0, random_effect="userId", **base,
        ),
    )
    i_design = build_bucketed_random_effect_design(
        data, "itemId", "per_item", n_items, num_buckets=1
    )
    items = FactoredRandomEffectCoordinate(
        design=i_design,
        row_features=jnp.asarray(xi),
        row_entities=jnp.asarray(item),
        full_offsets_base=jnp.zeros((n_rows,), jnp.float32),
        re_config=CoordinateConfig(
            shard="per_item", optimizer=OptimizerType.NEWTON,
            reg_weight=10.0, random_effect="itemId", **base,
        ),
        factored=FactoredConfig(
            latent_dim=k,
            num_inner_iterations=1,
            # the shared-projection B solve stays LBFGS: it is ONE
            # moderate-dim GLM (d*k vec), not a batched per-entity solve
            latent_factor_config=CoordinateConfig(
                shard="per_item", optimizer=OptimizerType.LBFGS,
                reg_weight=10.0, random_effect="itemId", **base,
            ),
        ),
    )
    cd = CoordinateDescent(
        coordinates={"fixed": fixed, "per-user": users, "per-item": items},
        labels=jnp.asarray(y),
        base_offsets=jnp.zeros((n_rows,), jnp.float32),
        weights=jnp.ones((n_rows,), jnp.float32),
        task=TaskType.LOGISTIC_REGRESSION,
        # chunked per-coordinate dispatches at this scale, like
        # bench_game (whole-pass fusion did not finish compiling there)
        fuse_passes="coordinate",
    )
    t0 = time.perf_counter()
    _warm_disjoint(cd)
    log(f"GAME multi-RE warmup (compile+run): {time.perf_counter() - t0:.2f}s")
    iters = 2
    t0 = time.perf_counter()
    model, history = cd.run(num_iterations=iters)
    dt = time.perf_counter() - t0
    from photon_ml_tpu.ops.metrics import area_under_roc_curve

    w_f = np.asarray(model.params["fixed"])
    tab_u = np.asarray(model.params["per-user"])
    fp = model.params["per-item"]
    margins_te = (
        xg_te @ w_f
        + np.einsum("nd,nd->n", xu_te, tab_u[user_te])
        + np.einsum(
            "nk,nk->n",
            xi_te @ np.asarray(fp.projection),
            np.asarray(fp.gamma)[item_te],
        )
    )
    auc = float(
        area_under_roc_curve(
            jnp.asarray(y_te),
            jnp.asarray(margins_te),
            jnp.ones(y_te.shape[0]),
        )
    )
    out = {
        "iters_per_s": iters / dt,
        "objective": float(history[-1].objective),
        "auc": auc,
    }
    log(
        f"GAME multi-RE+MF CD: {iters} iterations in {dt:.2f}s "
        f"({iters / dt:.3f} iters/s) objective={history[-1].objective:.4f} "
        f"held-out auc={auc:.4f}"
    )
    return out


def bench_game_wide_sparse():
    """GAME in the regime a dense fixed shard cannot reach: 100k rows x
    60k-column sparse fixed effect (24 GB dense — infeasible; 17 MB as
    padded ELL) + a 2k-user random effect, with the hybrid MXU split
    applied coordinate-locally. Reports CD iters/sec (capability metric —
    no same-shape CPU/dense baseline exists)."""
    import jax.numpy as jnp

    from photon_ml_tpu.core.tasks import TaskType
    from photon_ml_tpu.game import (
        CoordinateConfig,
        CoordinateDescent,
        FixedEffectCoordinate,
        GameData,
        RandomEffectCoordinate,
        build_bucketed_random_effect_design,
    )
    from photon_ml_tpu.models.training import OptimizerType
    from photon_ml_tpu.ops.sparse import from_coo

    n_rows, d_wide, nnz, n_users, d_user = 100_000, 60_000, 24, 2_000, 8
    rng = np.random.default_rng(17)
    cols = ((rng.zipf(1.1, size=(n_rows, nnz)) - 1) % d_wide).astype(np.int32)
    vals = rng.standard_normal((n_rows, nnz), dtype=np.float32)
    user = rng.integers(0, n_users, size=n_rows).astype(np.int32)
    xu = rng.standard_normal((n_rows, d_user), dtype=np.float32)
    logits = 0.4 * vals[:, 0] + 0.3 * xu[:, 0]
    y = (rng.uniform(size=n_rows) < 1.0 / (1.0 + np.exp(-logits))).astype(
        np.float32
    )
    # dedup-by-sum through from_coo (duplicate Zipf draws within a row)
    wide = from_coo(
        np.repeat(np.arange(n_rows), nnz),
        cols.reshape(-1),
        vals.reshape(-1),
        n_rows,
        d_wide,
        dtype=jnp.float32,
    )
    data = GameData.create(
        features={"wide": wide, "per_user": xu},
        labels=y,
        entity_ids={"userId": user},
    )
    base = dict(task=TaskType.LOGISTIC_REGRESSION, max_iters=5, tolerance=1e-5)
    fixed = FixedEffectCoordinate(
        data.fixed_effect_batch("wide"),
        CoordinateConfig(
            shard="wide", optimizer=OptimizerType.LBFGS, reg_weight=1.0,
            **base,
        ),
        hot_columns=-1,
    )
    # num_buckets=1: near-uniform entity sizes; each bucket is a
    # sequential device cost
    u_design = build_bucketed_random_effect_design(
        data, "userId", "per_user", n_users, num_buckets=1
    )
    users = RandomEffectCoordinate(
        design=u_design,
        row_features=jnp.asarray(xu),
        row_entities=jnp.asarray(user),
        full_offsets_base=jnp.zeros((n_rows,), jnp.float32),
        config=CoordinateConfig(
            shard="per_user", optimizer=OptimizerType.LBFGS,
            reg_weight=10.0, random_effect="userId", **base,
        ),
    )
    cd = CoordinateDescent(
        coordinates={"wide": fixed, "per-user": users},
        labels=jnp.asarray(y),
        base_offsets=jnp.zeros((n_rows,), jnp.float32),
        weights=jnp.ones((n_rows,), jnp.float32),
        task=TaskType.LOGISTIC_REGRESSION,
    )
    t0 = time.perf_counter()
    _warm_disjoint(cd)
    log(f"GAME wide-sparse warmup (compile+run): {time.perf_counter() - t0:.2f}s")
    iters = 2
    t0 = time.perf_counter()
    _, history = cd.run(num_iterations=iters)
    dt = time.perf_counter() - t0
    log(
        f"GAME wide-sparse (60k-col hybrid fixed + 2k-user RE) CD: "
        f"{iters} iterations in {dt:.2f}s ({iters / dt:.3f} iters/s) "
        f"objective={history[-1].objective:.4f}"
    )
    return {"iters_per_s": iters / dt}


def bench_sparse():
    import jax.numpy as jnp

    from photon_ml_tpu.core.types import LabeledBatch
    from photon_ml_tpu.models import (
        GLMTrainingConfig,
        OptimizerType,
        TaskType,
        train_glm,
    )
    from photon_ml_tpu.ops import RegularizationContext
    from photon_ml_tpu.ops.metrics import area_under_roc_curve
    from photon_ml_tpu.ops.sparse import SparseFeatures

    # Train/held-out split with CALIBRATED label noise:
    # raw logits at these shapes are near-separable, so "matched AUC"
    # degenerates to 1.0 == 1.0 and cannot distinguish a correct solver
    # from a sloppy one. The true model must put signal where rows LAND
    # (a sparse w_true leaves ~87% of 32-nnz rows with zero informative
    # features — pure coin flips, AUC ~0.55 no matter the solver), so
    # w_true is dense and logits scale to std 2.5: Bayes AUC ~0.89,
    # best-estimable held-out AUC ~0.75 at this n/d ratio (measured with
    # sklearn); solver quality shows as a gap below that.
    n, n_te, d, nnz = 200_000, 25_000, 120_000, 32
    nt = n + n_te
    rng = np.random.default_rng(11)
    idx = rng.integers(0, d, size=(nt, nnz)).astype(np.int32)
    vals = rng.standard_normal((nt, nnz)).astype(np.float32)
    w_true = rng.standard_normal(d).astype(np.float32)
    logits = np.einsum("nk,nk->n", vals, w_true[idx])
    logits *= 2.5 / max(float(logits.std()), 1e-12)
    y = (rng.uniform(size=nt) < 1.0 / (1.0 + np.exp(-logits))).astype(
        np.float32
    )
    idx, idx_te = idx[:n], idx[n:]
    vals, vals_te = vals[:n], vals[n:]
    y, y_te = y[:n], y[n:]

    sf = SparseFeatures(
        indices=jnp.asarray(idx), values=jnp.asarray(vals), d=d
    )
    batch = LabeledBatch.create(sf, y, dtype=jnp.float32)
    cfg = lambda lam: GLMTrainingConfig(
        task=TaskType.LOGISTIC_REGRESSION,
        optimizer=OptimizerType.LBFGS,
        regularization=RegularizationContext("L2"),
        reg_weights=(lam,),
        tolerance=1e-7,
        max_iters=60,
        track_states=False,
    )
    t0 = time.perf_counter()
    (warm,) = train_glm(batch, cfg(10.0))
    np.asarray(warm.result.w)
    log(f"sparse first solve (compile+run): {time.perf_counter() - t0:.2f}s")
    t0 = time.perf_counter()
    (tm,) = train_glm(batch, cfg(1.0))
    w_dev = np.asarray(tm.model.coefficients.means)
    tpu_s = time.perf_counter() - t0

    # Ceiling decomposition for the single-chip uniform loss: wall ~=
    # counted value+grad passes x the measured irregular pass cost.
    # Layout experiments in r5 (row sort by column locality, in-row
    # column sort, bf16 values) all landed on the same ~87 ms/pass XLA
    # gather/scatter rate (BENCH_r05 sparse_uniform_ceiling), and TRON
    # needs MORE passes than LBFGS here (55 vs 50), so the pass cost IS
    # the single-chip frontier; the remaining lever is the 'feature'
    # mesh axis dividing slots per chip.
    uniform_passes = int(np.asarray(tm.result.evals))
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.ops.objective import GLMObjective

    _obj = GLMObjective(
        loss=loss_for_task(TaskType.LOGISTIC_REGRESSION), l2_weight=1.0
    )
    pass_ms = chained_vg_pass_ms(_obj, batch, jnp.zeros((d,), jnp.float32))
    uniform_predicted_s = uniform_passes * pass_ms / 1e3
    log(
        f"uniform ceiling: {uniform_passes} passes x {pass_ms:.1f} ms "
        f"= {uniform_predicted_s:.2f}s predicted vs {tpu_s:.2f}s observed "
        f"({uniform_predicted_s / max(tpu_s, 1e-9):.0%})"
    )

    # hybrid dense-hot/sparse-cold split (ops.sparse.HybridFeatures).
    # The split targets POWER-LAW columns — the uniform
    # config above has no head to densify — so it gets its own
    # Zipf-distributed dataset (CTR-like) with a paired ELL control on
    # identical data.
    from photon_ml_tpu.ops.sparse import to_hybrid

    from photon_ml_tpu.ops.sparse import (
        cold_padded_slots,
        from_coo,
        stored_cold_entries,
    )

    from scipy.sparse import csr_matrix

    zranks = rng.zipf(1.1, size=(nt, nnz))
    zidx = ((zranks - 1) % d).astype(np.int32)
    zvals = rng.standard_normal((nt, nnz)).astype(np.float32)
    zrows_all = np.repeat(np.arange(nt), nnz)
    zcsr_all = csr_matrix(
        (zvals.ravel(), (zrows_all, zidx.ravel())), shape=(nt, d)
    )
    zcsr_all.sum_duplicates()
    # calibrated overlap like the uniform config, with the signal on the
    # HEAD columns (Zipf rows always hit the head, and head columns have
    # thousands of observations each, so the model is estimable)
    w_true_z = np.zeros(d, np.float32)
    w_true_z[:500] = rng.standard_normal(500).astype(np.float32)
    zlogits = zcsr_all @ w_true_z
    zlogits *= 2.5 / max(float(zlogits.std()), 1e-12)
    zy_all = (rng.uniform(size=nt) < 1.0 / (1.0 + np.exp(-zlogits))).astype(
        np.float32
    )
    zy, zy_te = zy_all[:n], zy_all[n:]
    # dedup-by-sum through from_coo (to_hybrid's invariant; every ingest
    # path guarantees it the same way)
    zsf = from_coo(
        np.repeat(np.arange(n), nnz),
        zidx[:n].reshape(-1),
        zvals[:n].reshape(-1),
        n,
        d,
        dtype=jnp.float32,
    )
    zell = LabeledBatch.create(zsf, zy, dtype=jnp.float32)
    zhf = to_hybrid(zsf, hot_columns=-1)
    zperm = np.asarray(zhf.row_perm)
    zhyb = LabeledBatch.create(zhf, zy[zperm], dtype=jnp.float32)
    h_cols = int(zhf.dense.shape[1])
    ell_slots = int(np.prod(zsf.indices.shape))
    log(
        f"zipf hybrid split: {h_cols} hot cols densified; "
        f"{stored_cold_entries(zhf) / (n * nnz):.0%} of entries stay "
        f"sparse in {len(zhf.cold_segments)} row buckets "
        f"({cold_padded_slots(zhf) / 1e6:.1f}M padded slots vs "
        f"{ell_slots / 1e6:.1f}M ELL)"
    )
    t0 = time.perf_counter()
    (ze,) = train_glm(zell, cfg(10.0))
    np.asarray(ze.result.w)
    (zh,) = train_glm(zhyb, cfg(10.0))
    np.asarray(zh.result.w)
    log(f"zipf compiles: {time.perf_counter() - t0:.2f}s")
    t0 = time.perf_counter()
    (ze,) = train_glm(zell, cfg(1.0))
    w_zell = np.asarray(ze.model.coefficients.means)
    zipf_ell_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    (zh,) = train_glm(zhyb, cfg(1.0))
    w_zhyb = np.asarray(zh.model.coefficients.means)
    hybrid_s = time.perf_counter() - t0
    # compare OBJECTIVES, not coefficients: rarely-observed tail columns
    # leave near-flat directions where equally-converged solves differ
    obj_gap = abs(
        float(np.asarray(zh.result.value))
        - float(np.asarray(ze.result.value))
    ) / max(abs(float(np.asarray(ze.result.value))), 1e-9)
    log(
        f"zipf 200kx120k: hybrid {hybrid_s:.3f}s vs ELL {zipf_ell_s:.3f}s "
        f"({zipf_ell_s / hybrid_s:.2f}x, rel objective gap={obj_gap:.2e})"
    )

    # --- Zipf HEADLINE: matched-or-better AUC vs sklearn's best shot ----
    # Zipf column counts make the raw problem badly conditioned (hot
    # columns dominate the Hessian spectrum): NEITHER plain-LBFGS path
    # converges in its iteration budget. The cure is the reference's own
    # normalization algebra (``ValueAndGradientAggregator.scala:87-118``:
    # factors fold into the kernels, nothing densifies) — and sklearn gets
    # the same cure (StandardScaler on the CSR, with_mean=False) so the
    # comparison is scaled-vs-scaled at matched conditions.
    from photon_ml_tpu.core.normalization import NormalizationType

    cfg_norm = lambda lam: GLMTrainingConfig(
        task=TaskType.LOGISTIC_REGRESSION,
        optimizer=OptimizerType.LBFGS,
        regularization=RegularizationContext("L2"),
        reg_weights=(lam,),
        normalization=NormalizationType.SCALE_WITH_STANDARD_DEVIATION,
        tolerance=1e-7,
        max_iters=60,
        track_states=False,
    )
    t0 = time.perf_counter()
    (zn,) = train_glm(zhyb, cfg_norm(10.0))
    np.asarray(zn.result.w)
    log(f"zipf normalized compile: {time.perf_counter() - t0:.2f}s")
    t0 = time.perf_counter()
    (zn,) = train_glm(zhyb, cfg_norm(1.0))
    w_znorm = np.asarray(zn.model.coefficients.means)  # RAW space
    zipf_norm_s = time.perf_counter() - t0

    from sklearn.linear_model import LogisticRegression
    from sklearn.preprocessing import StandardScaler

    zcsr, zcsr_te = zcsr_all[:n], zcsr_all[n:]
    t0 = time.perf_counter()
    zscaler = StandardScaler(with_mean=False).fit(zcsr)
    zxs = zscaler.transform(zcsr)
    zskl = LogisticRegression(
        C=1.0, fit_intercept=False, tol=1e-7, max_iter=200
    ).fit(zxs, zy)
    zipf_skl_s = time.perf_counter() - t0
    # HELD-OUT AUCs: both models score the same unseen
    # rows; our coefficients are already mapped back to raw space, so
    # test margins are one raw-CSR product on each side
    auc_znorm = float(
        area_under_roc_curve(
            jnp.asarray(zy_te), jnp.asarray(zcsr_te @ w_znorm),
            jnp.ones(n_te),
        )
    )
    auc_zskl = float(
        area_under_roc_curve(
            jnp.asarray(zy_te),
            jnp.asarray(zscaler.transform(zcsr_te) @ zskl.coef_.ravel()),
            jnp.ones(n_te),
        )
    )
    log(
        f"zipf HEADLINE 200kx120k (normalized): device {zipf_norm_s:.3f}s "
        f"held-out auc={auc_znorm:.4f} vs sklearn-scaled {zipf_skl_s:.3f}s "
        f"auc={auc_zskl:.4f} -> {zipf_skl_s / zipf_norm_s:.2f}x"
    )

    rows = np.repeat(np.arange(n), nnz)
    csr = csr_matrix(
        (vals.ravel(), (rows, idx.ravel())), shape=(n, d)
    )
    t0 = time.perf_counter()
    skl = LogisticRegression(
        C=1.0, fit_intercept=False, tol=1e-7, max_iter=200
    ).fit(csr, y)
    cpu_s = time.perf_counter() - t0

    margins_dev = np.einsum("nk,nk->n", vals_te, w_dev[idx_te])
    margins_cpu = np.einsum(
        "nk,nk->n", vals_te, skl.coef_.ravel()[idx_te]
    )
    auc_dev = float(
        area_under_roc_curve(
            jnp.asarray(y_te), jnp.asarray(margins_dev), jnp.ones(n_te)
        )
    )
    auc_cpu = float(
        area_under_roc_curve(
            jnp.asarray(y_te), jnp.asarray(margins_cpu), jnp.ones(n_te)
        )
    )
    log(
        f"sparse 200kx120k: device {tpu_s:.3f}s (held-out auc="
        f"{auc_dev:.4f}) vs sklearn {cpu_s:.3f}s (auc={auc_cpu:.4f})"
    )
    return {
        "tpu_s": tpu_s,
        "cpu_s": cpu_s,
        "auc_device": auc_dev,
        "auc_cpu": auc_cpu,
        "uniform_passes": uniform_passes,
        "uniform_pass_ms": pass_ms,
        "uniform_predicted_s": uniform_predicted_s,
        "hybrid_s": hybrid_s,
        "zipf_ell_s": zipf_ell_s,
        "hybrid_hot_columns": h_cols,
        "zipf_norm_s": zipf_norm_s,
        "zipf_skl_s": zipf_skl_s,
        "auc_zipf_device": auc_znorm,
        "auc_zipf_cpu": auc_zskl,
    }


def _fs_scaling_batch():
    """The d=120k sparse logistic workload shared by the scaling and
    overlap phases (one builder: the two curves must measure the SAME
    dataset)."""
    import jax.numpy as jnp

    from photon_ml_tpu.core.types import LabeledBatch
    from photon_ml_tpu.ops import sparse as sparse_ops

    n, d, nnz = 60_000, 120_000, 32
    rng = np.random.default_rng(13)
    rows = np.repeat(np.arange(n), nnz)
    cols = rng.integers(0, d, size=n * nnz)
    vals = rng.standard_normal(n * nnz).astype(np.float32)
    sf = sparse_ops.from_coo(rows, cols, vals, n, d, dtype=jnp.float32)
    w_true = np.zeros(d, np.float32)
    hot = rng.choice(d, 2000, replace=False)
    w_true[hot] = rng.standard_normal(2000).astype(np.float32)
    logits = np.asarray(sparse_ops.matvec(sf, jnp.asarray(w_true)))
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-logits))).astype(
        np.float32
    )
    return LabeledBatch.create(sf, y, dtype=jnp.float32), n


def _fs_compiled_pass(batch, f_shards, mode):
    """Compile one objective value+grad pass at width ``f_shards`` under
    ``PHOTON_COLLECTIVE_MODE=mode`` (fused = flat blocked layout +
    single trailing all-reduce, the PR-5 oracle; overlap = row-balanced
    layout + chunked reduce-scatter/all-gather pipeline). Returns
    (compiled, w0, placed batch, blocked container)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from photon_ml_tpu.ops import sparse as sparse_ops
    from photon_ml_tpu.ops.losses import LOGISTIC_LOSS
    from photon_ml_tpu.ops.objective import GLMObjective
    from photon_ml_tpu.parallel import make_feature_mesh
    from photon_ml_tpu.parallel.mesh import DATA_AXIS, FEATURE_AXIS
    from photon_ml_tpu.parallel.overlap import COLLECTIVE_MODE_ENV

    prev_mode = os.environ.get(COLLECTIVE_MODE_ENV)
    os.environ[COLLECTIVE_MODE_ENV] = mode
    try:
        mesh = make_feature_mesh(1, f_shards)
        blocked = sparse_ops.shard_columns(
            batch.features,
            f_shards,
            balance_rows=(mode == "overlap" and f_shards > 1),
        )
        spec3 = NamedSharding(mesh, P(DATA_AXIS, FEATURE_AXIS, None))
        spec2 = NamedSharding(mesh, P(None, FEATURE_AXIS))
        placed = dataclasses.replace(
            blocked,
            indices=jax.device_put(blocked.indices, spec3),
            values=jax.device_put(blocked.values, spec3),
            row_map=(
                None
                if blocked.row_map is None
                else jax.device_put(blocked.row_map, spec2)
            ),
        )
        w0 = jax.device_put(
            jnp.zeros((f_shards * blocked.d_shard,), jnp.float32),
            NamedSharding(mesh, P(FEATURE_AXIS)),
        )
        pb = dataclasses.replace(batch, features=placed)
        obj = GLMObjective(loss=LOGISTIC_LOSS, l2_weight=1.0)
        with jax.set_mesh(mesh):
            comp = (
                jax.jit(lambda w, b: obj.value_and_grad(w, b))
                .lower(w0, pb)
                .compile()
            )
        return comp, w0, pb, blocked
    finally:
        if prev_mode is None:
            os.environ.pop(COLLECTIVE_MODE_ENV, None)
        else:
            os.environ[COLLECTIVE_MODE_ENV] = prev_mode


def _best_pass_wall(comp, w0, pb, repeats=3):
    import jax

    walls = []
    for _ in range(repeats):
        tp = time.perf_counter()
        jax.block_until_ready(comp(w0, pb))
        walls.append(time.perf_counter() - tp)
    return min(walls)


def bench_overlap(batch=None, floor_wall=None):
    """Fused-vs-overlap objective-pass walls + ``collective_wall_frac``
    per mesh width (ISSUE 14): the DIRECT overlap gate. Per width, the
    pass compiles under both PHOTON_COLLECTIVE_MODE strategies;
    ``collective_wall_frac`` is the share of the sharded pass wall NOT
    explained by the width-1 single-device compute floor — partition
    overhead plus exposed collective wall, exactly what the overlap
    strategy (row-balanced blocking + chunked reduce-scatter/all-gather)
    exists to remove. Both series land in the metrics registry as
    ``collective.overlap.objective_pass.w<W>.wall_frac`` /
    ``collective.fused.objective_pass.w<W>.wall_frac`` gauges
    (obs.collectives.record_collective_share) and in the record as
    sentinel-gated lower-is-better numbers."""
    from photon_ml_tpu.obs import collectives as obs_coll

    if batch is None:
        batch, _ = _fs_scaling_batch()
    if floor_wall is None:
        comp, w0, pb, _ = _fs_compiled_pass(batch, 1, "overlap")
        floor_wall = _best_pass_wall(comp, w0, pb)
    out = {
        "1": {
            "floor_pass_ms": round(floor_wall * 1e3, 3),
        }
    }
    for f_shards in (2, 4, 8):
        row = {}
        for mode in ("fused", "overlap"):
            comp, w0, pb, blocked = _fs_compiled_pass(
                batch, f_shards, mode
            )
            wall = _best_pass_wall(comp, w0, pb)
            frac = obs_coll.record_collective_share(
                f"{mode}.objective_pass",
                mesh_width=f_shards,
                collective_wall_s=max(wall - floor_wall, 0.0),
                pass_wall_s=wall,
            )
            row[f"{mode}_pass_ms"] = round(wall * 1e3, 3)
            row[
                "collective_wall_frac"
                if mode == "overlap"
                else "collective_wall_frac_fused"
            ] = round(frac, 4)
            row[
                f"slots_m_{mode}"
            ] = round(int(np.prod(blocked.indices.shape)) / 1e6, 3)
        log(
            f"overlap F={f_shards}: fused {row['fused_pass_ms']:.0f}ms "
            f"(frac {row['collective_wall_frac_fused']}) -> overlap "
            f"{row['overlap_pass_ms']:.0f}ms "
            f"(frac {row['collective_wall_frac']})"
        )
        out[str(f_shards)] = row
    return out


def bench_sparse_feature_scaling():
    """Feature-sharded sparse solve at d=120k over 1/2/4/8-way 'feature'
    meshes (virtual CPU devices — the multichip stand-in),
    solved under the production overlap strategy
    (PHOTON_COLLECTIVE_MODE=overlap: row-balanced blocked layout +
    chunked reduce-scatter/all-gather — docs/PARALLEL.md).

    The bench host exposes ONE physical core, so virtual devices
    timeshare it and WALL-CLOCK cannot speed up; the honest evidence is
    (a) wall-clock stays near-flat as the mesh widens (r06's INVERSE
    curve — 3.8s at width 1, 10.4s at width 8 — was the flat blocked
    layout's padding inflation plus the trailing fused all-reduce),
    (b) per-device solver state shrinks ~1/F, and (c) the compiled
    pass's collective structure is the chunked pipeline whose exposed
    wall ``bench_overlap`` gates directly via collective_wall_frac.
    Returns {"widths": per-width rows, "overlap": bench_overlap rows}.
    """
    import jax

    from photon_ml_tpu.models import (
        GLMTrainingConfig,
        OptimizerType,
        TaskType,
    )
    from photon_ml_tpu.ops import RegularizationContext
    from photon_ml_tpu.parallel import (
        feature_sharded_train_glm,
        make_feature_mesh,
    )

    batch, n = _fs_scaling_batch()
    cfg = GLMTrainingConfig(
        task=TaskType.LOGISTIC_REGRESSION,
        optimizer=OptimizerType.LBFGS,
        regularization=RegularizationContext("L2"),
        reg_weights=(1.0,),
        tolerance=1e-7,
        max_iters=40,
        track_states=False,
    )
    out = {}
    w_ref = None
    floor_wall = None
    for f_shards in (1, 2, 4, 8):
        mesh = make_feature_mesh(1, f_shards)
        # the PRODUCTION pass: overlap strategy (balanced layout +
        # chunked pipeline); per-device footprint + collectives via the
        # shared cost book
        comp, w0, pb, blocked = _fs_compiled_pass(
            batch, f_shards, "overlap"
        )
        from photon_ml_tpu import obs

        rec = obs.cost_book().record(
            "sparse.objective_pass", comp, bucket=f"F{f_shards}"
        )
        colls = rec.collectives
        pass_wall = _best_pass_wall(comp, w0, pb)
        if f_shards == 1:
            floor_wall = pass_wall
        # the FUSED oracle's collective structure (the PR-5 single
        # bucketed all-reduce over the flat layout) rides along so the
        # before/after is machine-readable in the record — and the
        # legacy unfused (one-collective-per-contraction) count next to
        # it, as every round since r05 recorded
        comp_f, w0_f, pb_f, _ = _fs_compiled_pass(
            batch, f_shards, "fused"
        )
        rec_fused = obs.cost_book().record(
            "sparse.objective_pass_fused", comp_f, bucket=f"F{f_shards}"
        )
        from photon_ml_tpu.ops.losses import LOGISTIC_LOSS
        from photon_ml_tpu.ops.objective import GLMObjective
        obj_unfused = GLMObjective(
            loss=LOGISTIC_LOSS, l2_weight=1.0,
            fuse_feature_reductions=False,
        )
        with jax.set_mesh(mesh):
            comp_unfused = (
                jax.jit(lambda w, b: obj_unfused.value_and_grad(w, b))
                .lower(w0_f, pb_f)
                .compile()
            )
        rec_unfused = obs.cost_book().record(
            "sparse.objective_pass_unfused",
            comp_unfused,
            bucket=f"F{f_shards}",
        )
        from photon_ml_tpu.obs import collectives as obs_coll

        obs_coll.record_collective(
            "sparse.objective_pass",
            mesh_width=f_shards,
            count=sum(colls.values()) or 1,
            nbytes=n * 4,  # the (n,) f32 margin-partials payload
            wall_s=pass_wall,
        )
        # the solve itself (compile incl.), overlap strategy
        from photon_ml_tpu.parallel.overlap import COLLECTIVE_MODE_ENV

        prev_mode = os.environ.get(COLLECTIVE_MODE_ENV)
        os.environ[COLLECTIVE_MODE_ENV] = "overlap"
        try:
            t0 = time.perf_counter()
            (tm,) = feature_sharded_train_glm(batch, cfg, mesh)
            w_sol = np.asarray(tm.model.coefficients.means)
            wall = time.perf_counter() - t0
        finally:
            if prev_mode is None:
                os.environ.pop(COLLECTIVE_MODE_ENV, None)
            else:
                os.environ[COLLECTIVE_MODE_ENV] = prev_mode
        if w_ref is None:
            w_ref = w_sol
        drift = float(np.max(np.abs(w_sol - w_ref)))
        per_dev_slots = int(np.prod(blocked.indices.shape)) // f_shards
        out[str(f_shards)] = {
            "wall_s": round(wall, 3),
            "per_device_arg_mb": round(
                (rec.argument_bytes or 0) / 1e6, 2
            ),
            "per_device_temp_mb": round((rec.temp_bytes or 0) / 1e6, 2),
            "per_device_coef_kb": round(
                f_shards * blocked.d_shard / f_shards * 4 / 1e3, 1
            ),
            "per_device_slots_m": round(per_dev_slots / 1e6, 3),
            # the fused oracle's count (the PR-5 single all-reduce) keeps
            # its historical key; the overlap pipeline's richer structure
            # (C reduce-scatter-shaped chunk reductions + gathers) is
            # DELIBERATE and recorded separately
            "collectives": dict(rec_fused.collectives),
            "collectives_overlap": dict(colls),
            "collectives_unfused": dict(rec_unfused.collectives),
            "collective_count": int(sum(rec_fused.collectives.values())),
            "collective_wall_ms": round(pass_wall * 1e3, 3),
            "max_dw_vs_1dev": round(drift, 8),
        }
        log(
            f"sparse scaling F={f_shards}: wall {wall:.2f}s "
            f"(compile incl.), per-dev arg "
            f"{out[str(f_shards)]['per_device_arg_mb']} MB, "
            f"slots {out[str(f_shards)]['per_device_slots_m']}M, "
            f"overlap colls {dict(colls)} (fused oracle: "
            f"{dict(rec_fused.collectives)}), "
            f"pass {pass_wall * 1e3:.1f}ms, max|dw|={drift:.1e}"
        )
    # sentinel-gated scaling efficiency (ROADMAP item 1):
    # wall_1dev / (N * wall_Ndev) — 1.0 is perfect linear scaling; on
    # this timeshared-CPU stand-in wall stays ~flat so ~1/N is the
    # honest ceiling. The sentinel holds RAISED absolute floors per
    # width (obs.sentinel._SCALING_FLOORS) on top of the history band.
    wall_1 = out["1"]["wall_s"]
    for f_str, row in out.items():
        f = int(f_str)
        row["scaling_efficiency"] = round(
            wall_1 / (f * row["wall_s"]), 4
        )
    # fused-vs-overlap pass walls + collective_wall_frac per width (the
    # bench_overlap phase, sharing this phase's dataset + floor)
    overlap = bench_overlap(batch=batch, floor_wall=floor_wall)
    result = {"widths": out, "overlap": overlap}
    return result


def bench_ingest():
    """Avro ingest throughput: native C++ decoder vs the Python codec on
    the same file (records/s, decode + vocab join to COO triplets)."""
    import shutil
    import tempfile

    from photon_ml_tpu.io.avro import write_avro_file
    from photon_ml_tpu.io.ingest import make_training_example
    from photon_ml_tpu.io.native import native_available
    from photon_ml_tpu.io.schemas import TRAINING_EXAMPLE_SCHEMA
    from photon_ml_tpu.io.vocab import FeatureVocabulary

    if not native_available():
        log("ingest: native reader unavailable; skipping")
        return None

    n, d, per = 20_000, 20_000, 30
    rng = np.random.default_rng(3)
    cols = rng.integers(0, d, size=(n, per))
    vals = rng.standard_normal((n, per))
    records = [
        make_training_example(
            label=float(i % 2),
            features={
                (f"f{c}", "t"): float(v)
                for c, v in zip(cols[i], vals[i])
            },
            uid=f"u{i}",
        )
        for i in range(n)
    ]
    tmp = tempfile.mkdtemp(prefix="pml_ingest_bench_")
    try:
        path = os.path.join(tmp, "part-0.avro")
        write_avro_file(path, TRAINING_EXAMPLE_SCHEMA, records, codec="deflate")
        vocab = FeatureVocabulary(
            [f"f{i}\x01t" for i in range(d)], add_intercept=True
        )
        # decode + vocab join only — the representation/device costs after
        # it are identical for both paths
        from photon_ml_tpu.io.avro import read_avro_file
        from photon_ml_tpu.io.ingest import _scalar_columns_and_triplets
        from photon_ml_tpu.io.native import read_columnar

        t0 = time.perf_counter()
        read_columnar([path], [vocab])
        native_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        _, recs = read_avro_file(path)
        _scalar_columns_and_triplets(recs, vocab)
        python_s = time.perf_counter() - t0
        log(
            f"ingest {n} records: native {native_s:.2f}s "
            f"({n / native_s:,.0f} rec/s) vs python codec {python_s:.2f}s "
            f"({n / python_s:,.0f} rec/s) -> {python_s / native_s:.1f}x"
        )
        return {
            "native_rec_per_s": n / native_s,
            "python_rec_per_s": n / python_s,
            "speedup": python_s / native_s,
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_ingest_pipeline():
    """Streaming ingest->device pipeline (docs/INGEST.md): parallel
    decode throughput on the r05 ingest smoke workload (same record
    shape, sharded across part files so the decode pool has work),
    host->device staging bandwidth with counted-stage overlap, and an
    out-of-core epoch drill. Sentinel-tracked: ``ingest_native_rec_per_s``
    (higher), ``host_to_device_gbps`` (higher), ``transfer_overlap_frac``
    (higher), ``epoch_stall_frac`` (lower)."""
    import shutil
    import tempfile

    from photon_ml_tpu.io.avro import write_avro_file
    from photon_ml_tpu.io.ingest import make_training_example
    from photon_ml_tpu.io.native import native_available, read_columnar
    from photon_ml_tpu.io.pipeline import (
        IngestPipeline,
        PipelineConfig,
        StreamedDesign,
        StreamingObjective,
    )
    from photon_ml_tpu.io.schemas import TRAINING_EXAMPLE_SCHEMA
    from photon_ml_tpu.io.vocab import FeatureVocabulary

    if not native_available():
        log("ingest pipeline: native reader unavailable; skipping")
        return None

    rng = np.random.default_rng(3)

    def write_parts(tmp, n, d, per, nfiles, seed):
        r = np.random.default_rng(seed)
        cols = r.integers(0, d, size=(n, per))
        vals = r.standard_normal((n, per))
        paths = []
        rows = np.array_split(np.arange(n), nfiles)
        for fi, idx in enumerate(rows):
            records = [
                make_training_example(
                    label=float(i % 2),
                    features={
                        (f"f{c}", "t"): float(v)
                        for c, v in zip(cols[i], vals[i])
                    },
                    uid=f"u{i}",
                )
                for i in idx
            ]
            p = os.path.join(tmp, f"part-{fi}.avro")
            write_avro_file(
                p, TRAINING_EXAMPLE_SCHEMA, records, codec="deflate"
            )
            paths.append(p)
        return paths

    tmp = tempfile.mkdtemp(prefix="pml_ingest_pipe_bench_")
    try:
        # --- leg 1: decode+join throughput, the r05 smoke workload ----
        n, d, per = 20_000, 20_000, 30
        paths = write_parts(tmp, n, d, per, nfiles=8, seed=3)
        vocab = FeatureVocabulary(
            [f"f{i}\x01t" for i in range(d)], add_intercept=True
        )
        # sequential baseline: one reader, one thread, no overlap
        t0 = time.perf_counter()
        read_columnar(paths, [vocab], max_workers=1, decode_threads=1)
        seq_s = time.perf_counter() - t0
        # pipelined: bounded pool, every part file a decode unit
        with IngestPipeline(
            paths, [vocab], config=PipelineConfig(chunk_mb=1.0)
        ) as pipe:
            t0 = time.perf_counter()
            for _ in pipe.parts():
                pass
            pipe_s = time.perf_counter() - t0
            decode_workers = pipe.decode_workers
        rec_per_s = n / pipe_s
        log(
            f"ingest pipeline: {n} records in {pipe_s:.2f}s "
            f"({rec_per_s:,.0f} rec/s, {decode_workers} workers) vs "
            f"sequential {seq_s:.2f}s ({n / seq_s:,.0f} rec/s) -> "
            f"{seq_s / pipe_s:.2f}x"
        )

        # --- leg 2: staged device assembly (deposit path) -------------
        import jax
        import jax.numpy as jnp

        n2, d2, per2 = 40_000, 512, 16
        paths2 = write_parts(tmp, n2, d2, per2, nfiles=4, seed=7)
        vocab2 = FeatureVocabulary(
            [f"f{i}\x01t" for i in range(d2)], add_intercept=True
        )
        # warm pass: compiles the deposit/copy executables for these
        # chunk shapes so the timed pass measures the PIPELINE, not XLA
        # compile (the same convention every other bench here uses)
        # chunk_mb sized so the smoke files plan into MULTIPLE decode
        # groups — one group would serialize the pool and hide the
        # overlap this bench exists to measure
        pipe_cfg = PipelineConfig(chunk_mb=0.5)
        with IngestPipeline(paths2, [vocab2], config=pipe_cfg) as warm:
            b0, _, _ = warm.labeled_batch(dtype=jnp.float32)
            jax.block_until_ready(b0.features)
            del b0
        with IngestPipeline(paths2, [vocab2], config=pipe_cfg) as pipe2:
            t0 = time.perf_counter()
            batch, _, _ = pipe2.labeled_batch(dtype=jnp.float32)
            jax.block_until_ready(batch.features)
            assemble_s = time.perf_counter() - t0
            stats = pipe2.stats.snapshot()
        gbps = (
            stats["bytes_to_device"] / max(stats["transfer_s"], 1e-9) / 1e9
        )
        overlap = stats["overlap_frac"]
        log(
            f"ingest pipeline staging: {n2}x{d2 + 1} assembled in "
            f"{assemble_s:.2f}s, host->device "
            f"{stats['bytes_to_device'] / 1e6:.0f} MB at {gbps:.2f} GB/s, "
            f"transfer_overlap_frac {overlap:.3f} "
            f"(busy decode {stats['decode_s']:.2f}s stage "
            f"{stats['stage_s']:.2f}s transfer {stats['transfer_s']:.2f}s "
            f"consume {stats['consume_s']:.2f}s vs wall "
            f"{stats['wall_s']:.2f}s)"
        )

        # --- leg 3: out-of-core epochs --------------------------------
        from photon_ml_tpu.models.glm import TaskType
        from photon_ml_tpu.ops.losses import loss_for_task

        with IngestPipeline(paths2, [vocab2], config=pipe_cfg) as pipe3:
            # out-of-core chunks sized for device math, not decode
            # groups: ~8 MB per streamed block
            design = StreamedDesign.from_pipeline(
                pipe3, dtype=np.float32, rows_per_chunk=4096
            )
        sobj = StreamingObjective(
            design,
            loss_for_task(TaskType.LOGISTIC_REGRESSION),
            l2_weight=1.0,
        )
        w = np.zeros((design.d,), np.float32)
        sobj._host_value_and_grad(w)  # compile the chunk passes
        sobj.stats = type(sobj.stats)()  # fresh accumulators
        epochs = 3
        t0 = time.perf_counter()
        for _ in range(epochs):
            sobj._host_value_and_grad(w)
        epoch_s = (time.perf_counter() - t0) / epochs
        estats = sobj.stats.snapshot()
        # fraction of the epoch wall NOT covered by chunk-pass compute:
        # the feed-bound residue an overlapped pipeline should shrink
        epoch_stall_frac = max(
            0.0, 1.0 - estats["consume_s"] / max(estats["wall_s"], 1e-9)
        )
        log(
            f"ingest pipeline out-of-core: {design.num_chunks} chunks/"
            f"epoch, {epoch_s:.3f}s/epoch "
            f"({design.bytes_per_epoch / 1e9:.2f} GB streamed), "
            f"epoch_stall_frac {epoch_stall_frac:.3f}"
        )
        return {
            "rec_per_s": rec_per_s,
            "sequential_rec_per_s": n / seq_s,
            "vs_sequential": seq_s / pipe_s,
            "decode_workers": decode_workers,
            "host_to_device_gbps": gbps,
            "transfer_overlap_frac": overlap,
            "assemble_s": assemble_s,
            "epoch_s": epoch_s,
            "epoch_stall_frac": epoch_stall_frac,
            "oocore_chunks": design.num_chunks,
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_overload():
    """Serving under deliberate overload (docs/ROBUSTNESS.md): an
    open-loop submitter floods a bounded micro-batcher whose service
    rate is capped, with per-request deadlines and a priority sprinkle.
    Sentinel-tracked: ``serving_shed_frac`` (lower — less load turned
    away for the same offered load), ``p99_under_overload_ms`` (lower —
    what DID score met its promise), ``breaker_recovery_s`` (lower —
    open -> probe -> reclosed wall for the reload circuit breaker).
    The hard invariants (zero lost requests, shed only expired/
    over-budget) are asserted by the drill, not just recorded."""
    from photon_ml_tpu.resilience.drills import breaker_drill, overload_run

    out = overload_run(total=1200)
    assert out["lost"] == 0, f"overload run lost requests: {out}"
    assert out["errors"] == 0, f"overload run errored: {out}"
    log(
        f"serving overload: {out['submitted']} submitted -> "
        f"{out['scored']} scored / {out['expired']} expired / "
        f"{out['shed']} shed / {out['rejected']} rejected "
        f"(shed_frac {out['serving_shed_frac']:.3f}), p99 "
        f"{out['p99_under_overload_ms']:.2f}ms vs unloaded "
        f"{out['unloaded_p99_ms']:.2f}ms (deadline "
        f"{out['deadline_ms']:.1f}ms), degraded_batches "
        f"{out['degraded_batches']}"
    )
    brk = breaker_drill(threshold=2, backoff_s=0.25)
    log(
        f"serving breaker: opened after {brk['reload_failures']} failed "
        f"reloads, recovered in {brk['breaker_recovery_s']:.2f}s with "
        f"{brk['client_scores']} in-flight scores and "
        f"{brk['client_errors']} errors"
    )
    return {
        "serving_shed_frac": out["serving_shed_frac"],
        "p99_under_overload_ms": out["p99_under_overload_ms"],
        "unloaded_p99_ms": out["unloaded_p99_ms"],
        "deadline_ms": out["deadline_ms"],
        "scored": out["scored"],
        "expired": out["expired"],
        "shed": out["shed"],
        "rejected": out["rejected"],
        "degraded_batches": out["degraded_batches"],
        "breaker_recovery_s": brk["breaker_recovery_s"],
        "breaker_reload_failures": brk["reload_failures"],
    }


def bench_serving_sharded():
    """Entity-sharded serving + tiered entity cache (docs/SERVING.md)
    under the Zipf multi-tenant load the subsystems exist for, on the
    8-virtual-device CPU mesh. Sentinel-tracked: ``serving_sharded_qps``
    / ``serving_cached_qps`` / ``serving_unsharded_qps`` (higher — the
    routed and cache-hit paths must sustain the unsharded rate),
    ``cache_hit_frac`` (higher — the HBM tier must keep absorbing the
    Zipf head), and ``resident_re_bytes_per_process`` (lower — the ~P x
    per-process footprint drop mesh partitioning buys). The hard
    invariants (sharded == unsharded <= 1e-10, zero lost requests under
    a shard fault) are asserted by tests and the ``shard_fault`` chaos
    drill, not just recorded."""
    import jax

    from benchmarks import serving_lab

    common = [
        "--clients", "8", "--requests", "1600",
        "--baseline-requests", "40", "--zipf-alpha", "1.1",
        "--tenants", "2",
    ]
    base = serving_lab.run(common)
    cached = serving_lab.run(common + ["--hbm-cache-entities", "128"])
    shards = min(8, jax.device_count())
    sharded = serving_lab.run(
        common + ["--serving-shards", str(shards)]
    )
    out = {
        "serving_shards": shards,
        "zipf_alpha": 1.1,
        "serving_unsharded_qps": base["extra"]["qps"],
        "serving_cached_qps": cached["extra"]["qps"],
        "serving_sharded_qps": sharded["extra"]["qps"],
        "cache_hit_frac": cached["extra"]["cache_hit_frac"],
        "cache_promotions": cached["extra"]["cache"]["promotions"],
        "unsharded_p99_ms": base["extra"]["p99_ms"],
        "cached_p99_ms": cached["extra"]["p99_ms"],
        "sharded_p99_ms": sharded["extra"]["p99_ms"],
        "resident_re_bytes_per_process": sharded["extra"][
            "resident_re_bytes_per_process"
        ],
        "resident_re_bytes_unsharded": base["extra"][
            "resident_re_bytes_per_process"
        ],
        "sharded_steady_state_compiles": sharded["extra"][
            "steady_state_compiles"
        ],
        "cached_steady_state_compiles": cached["extra"][
            "steady_state_compiles"
        ],
    }
    log(
        f"serving sharded: {out['serving_unsharded_qps']} qps unsharded "
        f"-> {out['serving_cached_qps']} qps cache-tier (hit_frac "
        f"{out['cache_hit_frac']:.3f}) / {out['serving_sharded_qps']} "
        f"qps @ {shards} shards (resident "
        f"{out['resident_re_bytes_unsharded']} -> "
        f"{out['resident_re_bytes_per_process']} B/process, "
        f"{out['sharded_steady_state_compiles']} steady compiles)"
    )
    return out


def _serving_sharded_cpu():
    """The serving-sharded bench in a CPU subprocess (needs the
    8-virtual-device mesh; the live platform may hold a single
    chip)."""
    return _cpu_subprocess("--serving-sharded", "serving sharded")


def bench_frontend():
    """Production serving fabric (docs/FRONTEND.md): T tenants x R
    replicas behind the async multiplexed front end, driven closed-loop
    over real sockets, vs the single-connection old-protocol baseline
    on the SAME hardware. Sentinel-tracked: ``frontend_qps`` (higher —
    the multiplexing + shared-queue win must hold),
    ``tenant_p99_ms.<t>`` (lower — per-tenant tail under the shared
    admission queue) and ``replica_failover_s`` (lower — wall from a
    replica dying mid-batch to the next replica's answer). The hard
    invariant — ZERO lost requests across the mid-run whole-replica
    kill — is asserted here and by the ``replica_loss`` chaos drill."""
    from benchmarks import serving_lab

    rec = serving_lab.run([
        "--frontend", "--clients", "8", "--requests", "2000",
        "--baseline-requests", "200", "--tenants", "2",
        "--frontend-replicas", "2", "--zipf-alpha", "1.1",
    ])
    ex = rec["extra"]
    assert ex["lost_requests"] == 0, (
        f"front end lost {ex['lost_requests']} requests across the "
        "replica kill — failover must answer every accepted request"
    )
    out = {
        "frontend_qps": ex["frontend_qps"],
        "single_conn_qps": ex["single_conn_qps"],
        "frontend_vs_single_conn": rec["vs_baseline"],
        "frontend_p99_ms": ex["p99_ms"],
        "tenant_p99_ms": ex["tenant_p99_ms"],
        "replica_failover_s": ex["replica_failover_s"],
        "lost_requests": ex["lost_requests"],
        "steady_state_compiles": ex["steady_state_compiles"],
        "shared_compile_hits": ex["shared_compile_hits"],
        "shared_compiles": ex["shared_compiles"],
    }
    log(
        f"frontend: {out['frontend_qps']} qps multiplexed vs "
        f"{out['single_conn_qps']} qps single-conn "
        f"({out['frontend_vs_single_conn']}x), failover "
        f"{out['replica_failover_s']}s, {out['lost_requests']} lost, "
        f"{out['shared_compile_hits']} shared-ladder hits"
    )
    return out


def bench_multihost_resilience():
    """Elastic multi-host resilience (docs/MULTIHOST.md), measured on
    the single-process emulation path. Sentinel-tracked:
    ``ckpt_shard_write_gbps`` (higher — per-process sharded checkpoint
    write bandwidth incl. digests + quorum manifest + atomic swap) and
    ``collective_timeout_recovery_s`` (lower — wall from a stalled
    collective to a clean retried exchange under the watchdog). The
    hard invariants (quorum fallback, bit-identical shrunk restart) are
    asserted by the chaos-lab drills, not just recorded."""
    import tempfile

    import numpy as np

    from photon_ml_tpu.io.checkpoint import (
        latest_checkpoint,
        save_checkpoint_sharded,
    )
    from photon_ml_tpu.parallel import multihost
    from photon_ml_tpu.resilience.faults import FaultSpec, inject

    rng = np.random.default_rng(59)
    # a serving-scale entity table: 50k entities x 64 dims f64 (~26MB)
    # + a replicated fixed slab — representative of one host's shard mix
    n_entities, d = 50_000, 64
    params = {
        "fixed": rng.normal(size=4096),
        "per-user": rng.normal(size=(n_entities, d)),
    }
    ekeys = {"per-user": [f"u{i}" for i in range(n_entities)]}
    key = np.zeros(2, np.uint32)
    payload_bytes = sum(
        np.asarray(p).nbytes for p in params.values()
    )
    shards = 4
    with tempfile.TemporaryDirectory() as tmp:
        # warm the fs path, then measure
        save_checkpoint_sharded(
            tmp, 1, params, key, entity_keys=ekeys, num_shards=shards
        )
        t0 = time.perf_counter()
        save_checkpoint_sharded(
            tmp, 2, params, key, entity_keys=ekeys, num_shards=shards
        )
        write_s = time.perf_counter() - t0
        ck = latest_checkpoint(tmp)
        assert ck is not None and ck.step == 2 and ck.shards == shards
    gbps = payload_bytes / write_s / 1e9
    # collective watchdog recovery: one stalled attempt -> timeout ->
    # retried exchange succeeds; the recovery wall is deadline + backoff
    prev = multihost.configure_collective_resilience(
        timeout_s=0.1, retries=2
    )
    try:
        t0 = time.perf_counter()
        with inject(
            FaultSpec("collective.stall", "delay", nth=1, delay=2.0)
        ):
            out = multihost.allgather_host(np.arange(1024))
        recovery_s = time.perf_counter() - t0
        assert out.shape[0] == 1024
        assert recovery_s < 1.9, "watchdog failed to abandon the stall"
    finally:
        multihost.configure_collective_resilience(
            prev.timeout_s, prev.retries
        )
    log(
        f"multihost resilience: sharded ckpt {payload_bytes / 1e6:.0f}MB "
        f"x{shards} shards in {write_s:.3f}s ({gbps:.2f} GB/s); "
        f"stalled collective recovered in {recovery_s:.3f}s"
    )
    return {
        # gbps is the ONE tracked write metric (its wall complement
        # would double-gate the same measurement in the other direction)
        "ckpt_shard_write_gbps": round(gbps, 4),
        "shards": shards,
        "collective_timeout_recovery_s": round(recovery_s, 4),
    }


def bench_quality():
    """Model-quality observability (docs/OBSERVABILITY.md "Quality &
    drift"). Sentinel-tracked: ``sketch_rows_per_s`` (higher — the
    per-chunk fingerprint accumulation rate the ingest paths pay),
    ``quality_overhead_ratio`` (lower — the serving path with the
    DriftMonitor sampling vs without, same batches), and
    ``drift_alarm_latency_requests`` / ``drift_alarm_latency_ms``
    (lower — offered requests / wall from the first shifted batch to
    ``drift.alarm``). The hard invariants (quiet unshifted replay,
    flight-recorded alarm, fault-degraded baseline) are asserted by the
    ``drift_alarm`` chaos drill, not just recorded."""
    import numpy as _np

    from photon_ml_tpu.obs.quality import BaselineFingerprint, DriftMonitor
    from photon_ml_tpu.resilience.drills import build_drill_engine

    rng = _np.random.default_rng(20260805)

    # 1) sketch throughput: the fingerprint-collector hot path over
    # pipeline-shaped staged chunks
    d = 32
    rows = 200_000
    X = rng.standard_normal((rows, d), dtype=_np.float32)
    y = (rng.uniform(size=rows) < 0.3).astype(_np.float32)
    fp = BaselineFingerprint(max_features=d)
    t0 = time.perf_counter()
    for lo in range(0, rows, 8192):
        fp.observe_batch(
            X[lo : lo + 8192], y[lo : lo + 8192], shard="features"
        )
    sketch_s = time.perf_counter() - t0
    sketch_rows_per_s = rows / sketch_s

    # 2) serving overhead: the same END-TO-END request batches
    # (featurize + padded device score — the real serving path) with
    # and without a DriftMonitor at default sampling on the engine
    from photon_ml_tpu.resilience.drills import make_drill_request

    d_fixed, d_user, n_users = 16, 6, 64
    engine = build_drill_engine(rng, d_fixed, d_user, n_users)
    req_batches = [
        [
            make_drill_request(rng, d_fixed, d_user, n_users)
            for _ in range(64)
        ]
        for _ in range(48)
    ]
    arr_batches = [
        {
            "g": rng.standard_normal((256, d_fixed)),
            "u": rng.standard_normal((256, d_user)),
        }
        for _ in range(16)
    ]
    baseline = BaselineFingerprint(max_features=24)
    for b in arr_batches:
        baseline.observe_batch(b["g"], _np.zeros(256), shard="g")
        baseline.observe_rows("u", b["u"])
    # request featurization is sparse (most columns 0), so the live
    # window must compare against a baseline of the SAME featurized
    # traffic — sketch what the engine actually sees
    for reqs in req_batches[:8]:
        feats, _, _ = engine.featurize(reqs)
        baseline.observe_batch(feats["g"], _np.zeros(64), shard="g")
        baseline.observe_rows("u", feats["u"])
    baseline.observe_margins(engine.score(req_batches[0]))

    def score_all():
        t0 = time.perf_counter()
        for reqs in req_batches:
            engine.score(reqs)
        return time.perf_counter() - t0

    engine.drift = None
    score_all()  # warm every bucket outside the timers
    base_wall = min(score_all() for _ in range(3))
    engine.drift = DriftMonitor(
        baseline, registry=engine.stats.registry, check_every_rows=512
    )
    quality_wall = min(score_all() for _ in range(3))
    overhead_ratio = quality_wall / base_wall

    # 3) alarm latency: offered requests + wall from the first shifted
    # batch until drift.alarm fires (sample_every=1: the tightest the
    # monitor can answer; production sampling multiplies it by N)
    engine.drift = DriftMonitor(
        baseline,
        registry=engine.stats.registry,
        check_every_rows=512,
        min_rows=256,
        sample_every=1,
    )
    offered = 0
    t0 = time.perf_counter()
    while engine.drift.alarms == 0:
        assert offered < 65536, "drift alarm never fired under shift"
        engine.score_arrays(
            {
                "g": rng.standard_normal((256, d_fixed)) + 3.0,
                "u": rng.standard_normal((256, d_user)) + 3.0,
            }
        )
        offered += 256
    alarm_wall_ms = (time.perf_counter() - t0) * 1e3

    log(
        f"quality: sketch {sketch_rows_per_s / 1e6:.2f}M rows/s "
        f"({d} cols), drift-monitor overhead {overhead_ratio:.3f}x, "
        f"alarm after {offered} shifted requests "
        f"({alarm_wall_ms:.1f}ms, psi_max "
        f"{engine.drift.last_report['psi_max']:.2f})"
    )
    return {
        "sketch_rows_per_s": round(sketch_rows_per_s),
        "sketch_cols": d,
        "quality_overhead_ratio": round(overhead_ratio, 4),
        "drift_alarm_latency_requests": offered,
        "drift_alarm_latency_ms": round(alarm_wall_ms, 2),
        "psi_max_at_alarm": engine.drift.last_report["psi_max"],
    }


def bench_lifecycle():
    """Self-healing retrain loop (docs/LIFECYCLE.md). Sentinel-tracked:
    ``retrain_cycle_s`` (lower — alarm-to-reload wall for one full
    orchestrator cycle: plan → entity-keyed warm-started refit →
    manifest-gated export → reload) and ``post_retrain_auc`` /
    ``auc_recovery`` (higher — ranking quality on the drifted holdout
    after the cycle vs the stale model's degraded score). The hard
    invariants (zero dropped requests, breaker quarantine, fault-site
    degraded outcomes) are asserted by the ``lifecycle`` chaos drill,
    not just recorded here."""
    import tempfile

    import numpy as _np

    from photon_ml_tpu.io.vocab import FeatureVocabulary, feature_key
    from photon_ml_tpu.lifecycle.orchestrator import (
        RetrainOrchestrator,
        export_retrained_model,
        load_warm_start,
        next_version_dir,
    )
    from photon_ml_tpu.obs.quality import exact_auc

    rng = _np.random.default_rng(20260806)
    d = 16
    rows = 8192

    def draw(w, mu):
        X = rng.normal(size=(rows, d)) + mu
        y = (
            rng.uniform(size=rows) < 1.0 / (1.0 + _np.exp(-(X @ w)))
        ).astype(float)
        return X, y

    def fit(X, y, warm, steps=60):
        w = _np.array(warm, dtype=float)
        for _ in range(steps):
            p = 1.0 / (1.0 + _np.exp(-(X @ w)))
            w -= 0.5 * (X.T @ (p - y)) / len(X)
        return w

    # phase 0: train + export on the original concept
    w0 = rng.normal(size=d)
    X0, y0 = draw(w0, 0.0)
    g0 = fit(X0, y0, _np.zeros(d))
    # concept drift: the label-generating weights rotate, so the stale
    # model's RANKING degrades (covariate-only shift would leave AUC
    # untouched — that axis is bench_quality's subject)
    w1 = -0.5 * w0 + rng.normal(size=d)
    Xh, yh = draw(w1, 0.5)  # drifted holdout, fixed for both models
    Xr, yr = draw(w1, 0.5)  # drifted retrain set

    with tempfile.TemporaryDirectory() as tmp:
        watch = os.path.join(tmp, "watch")
        vocab = FeatureVocabulary(
            [feature_key(f"f{j}", "") for j in range(d)]
        )
        users = {f"u{i}": i for i in range(8)}
        export_retrained_model(
            os.path.join(watch, "v0001"),
            params={
                "global": g0,
                "per-user": rng.normal(size=(len(users), d)),
            },
            shards={"global": "s", "per-user": "s"},
            vocabs={"global": vocab, "per-user": vocab},
            entity_vocabs={"per-user": users},
            random_effects={"global": None, "per-user": "userId"},
        )
        degraded_auc = exact_auc(yh, Xh @ g0)

        def retrain(plan):
            params, shards, res, shard_vocabs, re_vocabs = (
                load_warm_start(plan.warm_start_dir)
            )
            g = fit(Xr, yr, _np.asarray(params["global"]))
            old_vocab = re_vocabs["userId"]
            old_table = _np.asarray(params["per-user"])
            new_vocab = {
                k: i for i, k in enumerate(sorted(old_vocab))
            }
            table = _np.zeros((len(new_vocab), d))
            for k, i in new_vocab.items():  # carried BY KEY
                table[i] = old_table[old_vocab[k]]
            return export_retrained_model(
                next_version_dir(watch),
                params={"global": g, "per-user": table},
                shards=shards,
                vocabs={n: shard_vocabs[shards[n]] for n in shards},
                entity_vocabs={"per-user": new_vocab},
                random_effects=res,
            )

        reloaded = []
        orch = RetrainOrchestrator(
            trigger=lambda: {"source": "bench"},
            retrain_fn=retrain,
            reload_fn=lambda exp: reloaded.append(exp) or "v0002",
            watch_root=watch,
        )
        result = orch.run_cycle()
        assert result.ok, f"bench lifecycle cycle failed: {result}"
        assert reloaded, "reload stage never ran"

        g1 = _np.asarray(load_warm_start(reloaded[0])[0]["global"])
        post_auc = exact_auc(yh, Xh @ g1)

    log(
        f"lifecycle: retrain cycle {result.cycle_s:.3f}s, holdout AUC "
        f"{degraded_auc:.3f} (stale) -> {post_auc:.3f} (retrained)"
    )
    return {
        "retrain_cycle_s": round(float(result.cycle_s), 4),
        "degraded_holdout": round(float(degraded_auc), 4),
        "post_retrain_auc": round(float(post_auc), 4),
        "auc_recovery": round(float(post_auc - degraded_auc), 4),
    }


def bench_lint():
    """photon-lint over the full package (docs/ANALYSIS.md). Sentinel-
    tracked: ``lint_wall_s`` (lower — the gate must stay cheap enough
    for tier-1 and pre-commit; the acceptance bound is <10s on this
    box) and ``lint_findings_total`` (lower — finding creep means the
    ratchet is loosening: new baselined debt or a noisy rule). The
    zero-NEW-findings invariant itself is asserted here, not just
    recorded — a bench round must not publish numbers for a tree that
    fails its own gate."""
    import os as _os

    from photon_ml_tpu import obs
    from photon_ml_tpu.analysis import (
        Analyzer,
        Baseline,
        default_baseline_path,
    )

    root = _os.path.dirname(_os.path.abspath(__file__))
    package = _os.path.join(root, "photon_ml_tpu")
    analyzer = Analyzer(base=root)
    result = analyzer.run([package])
    new, grandfathered, stale = Baseline.load(
        default_baseline_path()
    ).split(result.findings)
    assert not new, (
        f"photon-lint: {len(new)} non-baselined findings — fix them "
        f"before benching: {[f.location() for f in new]}"
    )
    reg = obs.registry()
    reg.set_gauge("lint.wall_s", result.wall_s)
    reg.set_gauge("lint.findings_total", len(result.findings))
    log(
        f"lint: {result.files} files in {result.wall_s:.2f}s, "
        f"{len(result.findings)} findings ({len(grandfathered)} "
        f"baselined, {result.suppressed} suppressed, {len(stale)} stale)"
    )
    return {
        "lint_wall_s": round(result.wall_s, 4),
        "lint_findings_total": len(result.findings),
        "lint_files": result.files,
        "lint_suppressed": result.suppressed,
        "lint_stale_baseline_entries": len(stale),
    }


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument(
        "--game-only", action="store_true",
        help="run only the GAME benchmark (used by the CPU baseline)",
    )
    parser.add_argument(
        "--cpu", action="store_true",
        help="force the CPU backend (must precede any jax use)",
    )
    parser.add_argument(
        "--game-multi-only", action="store_true",
        help="run only the multi-RE GAME benchmark (CPU baseline use)",
    )
    parser.add_argument(
        "--sparse-scaling", action="store_true",
        help="run only the feature-sharded sparse scaling curve "
        "(used with --cpu: 8 virtual devices)",
    )
    parser.add_argument(
        "--sparse-only", action="store_true",
        help="run only the sparse benchmark (iteration aid)",
    )
    parser.add_argument(
        "--serving-sharded", action="store_true",
        help="run only the entity-sharded serving bench (used with "
        "--cpu: 8 virtual devices)",
    )
    parser.add_argument(
        "--sentinel", action="store_true",
        help="after printing the record, gate it against the repo's "
        "BENCH_r*.json history (benchmarks/regression_sentinel.py "
        "semantics; exit nonzero on regression). Also enabled by "
        "PHOTON_BENCH_SENTINEL=1.",
    )
    args = parser.parse_args()
    if args.cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")
        # the scaling curve and the sharded-serving bench need the
        # 8-device mesh
        if args.sparse_scaling or args.serving_sharded:
            jax.config.update("jax_num_cpu_devices", 8)
    # persistent XLA compilation cache: re-runs load compiled programs
    # from disk instead of re-JITting; warmup lines below report the
    # cold-vs-warm difference
    from photon_ml_tpu.utils import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    log(f"compilation cache: {cache_dir}")
    device = _device_record()
    log(f"device: {device}")
    if not args.cpu:
        if device["platform"] != "tpu":
            log(
                "bench.py measures the TPU; jax found "
                f"{device['platform']!r}. Pass --cpu for an explicit CPU "
                "run (counts and correctness only, never a device time)."
            )
            sys.exit(2)
        # an unlisted chip would otherwise get another chip's peaks
        from photon_ml_tpu.obs.xla_cost import require_device_peaks

        require_device_peaks()
    if args.game_only:
        _emit(bench_game(), device)
        return
    if args.game_multi_only:
        _emit(bench_game_multi_re(), device)
        return
    if args.sparse_scaling:
        _emit(bench_sparse_feature_scaling(), device)
        return
    if args.sparse_only:
        _emit(bench_sparse(), device)
        return
    if args.serving_sharded:
        _emit(bench_serving_sharded(), device)
        return

    rtt = _phase("fetch_latency", measure_fetch_latency)
    log(f"value-fetch latency: {rtt}")
    glm = _phase("glm_dense", bench_glm_dense)
    game = _phase("game", bench_game)
    game_super = _phase("game_superpass", bench_game_superpass)
    game_cpu = _phase("game_cpu_baseline", _game_cpu_baseline)
    game_multi = _phase("game_multi", bench_game_multi_re)
    game_multi_cpu = _phase(
        "game_multi_cpu_baseline", _game_multi_cpu_baseline
    )
    game_wide = _phase("game_wide_sparse", bench_game_wide_sparse)
    linear_en = _phase("linear_elastic_net", bench_linear_elastic_net)
    sparse = _phase("sparse", bench_sparse)
    sparse_scaling = _phase("sparse_scaling_cpu", _sparse_scaling_cpu)
    ingest = _phase("ingest", bench_ingest)
    ingest_pipe = _phase("ingest_pipeline", bench_ingest_pipeline)
    overload = _phase("serving_overload", bench_overload)
    serving_sharded = _phase("serving_sharded", _serving_sharded_cpu)
    frontend = _phase("frontend", bench_frontend)
    multihost_res = _phase(
        "multihost_resilience", bench_multihost_resilience
    )
    quality = _phase("quality", bench_quality)
    lifecycle = _phase("lifecycle", bench_lifecycle)
    lint = _phase("lint", bench_lint)

    extra = {
        **rtt,
        "transfer_s": round(glm["transfer_s"], 2),
        "dense_wall_incl_rtt_s": round(glm["tpu_wall_incl_rtt_s"], 4),
        # counted work: design passes per dense solve (each = 2 design
        # reads) — the platform-invariant comparator across rounds
        "dense_passes_per_solve": round(glm["passes_per_solve"], 1),
        "transfer_gb": round(glm["transfer_gb"], 3),
        "mfu": round(glm["mfu"], 5),
        "hbm_util": round(glm["hbm_util"], 4),
        "achieved_tflops": round(glm["achieved_tflops"], 2),
        # HEADLINE sparse: Zipf (Criteo-realistic) columns, normalized
        # hybrid vs sklearn on the identically scaled CSR, AUC-checked
        "sparse_zipf_s": round(sparse["zipf_norm_s"], 3),
        "sparse_vs_sklearn": round(
            sparse["zipf_skl_s"] / sparse["zipf_norm_s"], 3
        ),
        "sparse_zipf_auc_device": round(sparse["auc_zipf_device"], 4),
        "sparse_zipf_auc_cpu": round(sparse["auc_zipf_cpu"], 4),
        # secondary: uniform columns (kept honest — CPU CSR wins 1-chip)
        "sparse_uniform_s": round(sparse["tpu_s"], 3),
        "sparse_uniform_vs_sklearn": round(
            sparse["cpu_s"] / sparse["tpu_s"], 3
        ),
        "sparse_uniform_auc_device": round(sparse["auc_device"], 4),
        "sparse_uniform_auc_cpu": round(sparse["auc_cpu"], 4),
        # measured single-chip ceiling: counted passes x irregular-op
        # pass cost (the feature mesh axis is the lever)
        "sparse_uniform_ceiling": {
            "passes": sparse["uniform_passes"],
            "pass_ms": round(sparse["uniform_pass_ms"], 1),
            "predicted_s": round(sparse["uniform_predicted_s"], 2),
            "observed_s": round(sparse["tpu_s"], 2),
            "predicted_over_observed": round(
                sparse["uniform_predicted_s"] / max(sparse["tpu_s"], 1e-9),
                3,
            ),
        },
        "sparse_zipf_hybrid_s": round(sparse["hybrid_s"], 3),
        "sparse_zipf_hybrid_vs_ell": round(
            sparse["zipf_ell_s"] / sparse["hybrid_s"], 3
        ),
        "game_cd_iters_per_s": round(game["iters_per_s"], 3),
        "game_heldout_auc": round(game["auc"], 4),
        # dispatch economy (ROADMAP item 1, sentinel lower-is-better):
        # counted XLA dispatches per N-lambda GLM path / per multi-pass
        # GAME run, plus the path's amortized per-lambda wall
        "dispatches_per_path": glm["dispatches_per_path"],
        "path_wall_per_lambda_s": round(
            glm["path_wall_per_lambda_s"], 4
        ),
        "game_dispatches_per_run": game_super["game_dispatches_per_run"],
        "game_superpass_iters_per_s": round(
            game_super["superpass_iters_per_s"], 3
        ),
        # convergence health of the flagship GAME run (sentinel-tracked,
        # lower-is-better: obs.sentinel's convergence.* direction rules)
        "convergence": {
            "median_iters": round(game["convergence_median_iters"], 3),
            "nonconverged_frac": round(
                game["convergence_nonconverged_frac"], 5
            ),
        },
        "game_multi_re_mf_iters_per_s": round(
            game_multi["iters_per_s"], 3
        ),
        "game_multi_heldout_auc": round(game_multi["auc"], 4),
        "game_wide_sparse_iters_per_s": round(
            game_wide["iters_per_s"], 3
        ),
        "linear_en_s": round(linear_en["tpu_s"], 3),
        "linear_en_vs_sklearn": round(
            linear_en["cpu_s"] / linear_en["tpu_s"], 3
        ),
    }
    if game_cpu:
        extra["game_vs_cpu"] = round(
            game["iters_per_s"] / game_cpu["iters_per_s"], 3
        )
    if game_multi_cpu:
        extra["game_multi_vs_cpu"] = round(
            game_multi["iters_per_s"] / game_multi_cpu["iters_per_s"], 3
        )
    if sparse_scaling:
        # {"widths": per-width rows, "overlap": bench_overlap rows} since
        # r07 (bare per-width rows before)
        if "widths" in sparse_scaling:
            extra["sparse_fs_scaling"] = sparse_scaling["widths"]
            extra["bench_overlap"] = sparse_scaling["overlap"]
        else:
            extra["sparse_fs_scaling"] = sparse_scaling
    if ingest_pipe:
        # the HEADLINE ingest number is now the pipelined decode on the
        # same smoke workload (sharded across part files); the one-shot
        # reader's codec comparison stays below
        extra["ingest_native_rec_per_s"] = round(ingest_pipe["rec_per_s"])
        extra["ingest_pipeline"] = {
            "sequential_rec_per_s": round(
                ingest_pipe["sequential_rec_per_s"]
            ),
            "vs_sequential": round(ingest_pipe["vs_sequential"], 2),
            "decode_workers": ingest_pipe["decode_workers"],
            "host_to_device_gbps": round(
                ingest_pipe["host_to_device_gbps"], 3
            ),
            "transfer_overlap_frac": round(
                ingest_pipe["transfer_overlap_frac"], 4
            ),
            "assemble_s": round(ingest_pipe["assemble_s"], 3),
            "epoch_s": round(ingest_pipe["epoch_s"], 3),
            "epoch_stall_frac": round(
                ingest_pipe["epoch_stall_frac"], 4
            ),
            "oocore_chunks": ingest_pipe["oocore_chunks"],
        }
    elif ingest:
        extra["ingest_native_rec_per_s"] = round(
            ingest["native_rec_per_s"]
        )
    if ingest:
        extra["ingest_vs_python_codec"] = round(ingest["speedup"], 1)
    if overload:
        # chaos-hardened serving (docs/ROBUSTNESS.md): shed fraction and
        # loaded p99 under a fixed offered overload, breaker recovery
        # wall — all sentinel-tracked (shed_frac/_ms/_s direction rules)
        extra["serving_overload"] = {
            k: (round(v, 4) if isinstance(v, float) else v)
            for k, v in overload.items()
        }
    if serving_sharded:
        # entity-sharded serving + tiered entity cache (docs/SERVING.md):
        # routed/cache-hit/unsharded throughput, the Zipf cache hit
        # fraction, and the per-process resident RE footprint (sentinel:
        # _qps/hit_frac higher, resident bytes lower)
        extra["serving_sharded"] = serving_sharded
    if frontend:
        # production serving fabric (docs/FRONTEND.md): multiplexed
        # front-end throughput vs the single-connection old protocol,
        # per-tenant tails under the shared queue, and the router's
        # whole-replica failover wall (sentinel: frontend_qps higher,
        # tenant_p99_ms.* lower, replica_failover_s lower)
        extra["frontend"] = frontend
    if multihost_res:
        # elastic multi-host resilience (docs/MULTIHOST.md): sharded
        # checkpoint write bandwidth + watchdogged collective recovery
        # wall (sentinel: _gbps higher, recovery_s lower)
        extra["multihost_resilience"] = multihost_res
    if quality:
        # model-quality observability (docs/OBSERVABILITY.md "Quality &
        # drift"): sketch throughput, DriftMonitor serving overhead, and
        # covariate-shift alarm latency (sentinel: per_s higher,
        # overhead_ratio + drift_alarm_latency_* lower)
        extra["quality"] = quality
    if lifecycle:
        # self-healing retrain loop (docs/LIFECYCLE.md): alarm-to-reload
        # cycle wall + post-retrain ranking recovery on the drifted
        # holdout (sentinel: retrain_cycle_s lower, auc higher)
        extra["lifecycle"] = lifecycle
    if lint:
        # photon-lint self-hosting gate (docs/ANALYSIS.md): analyzer
        # wall (sentinel: the generic _s lower-is-better rule) and
        # total finding count (explicit lint_findings_total rule —
        # finding creep is ratchet debt, tracked like any regression)
        extra["lint_wall_s"] = lint["lint_wall_s"]
        extra["lint_findings_total"] = lint["lint_findings_total"]
        extra["lint"] = lint
    # where the bench run's own wall clock went + the final metrics
    # registry (solver iteration counters, ingest/checkpoint bytes,
    # recompiles when the compile listener was installed) + the XLA
    # cost book every MFU/HBM/collective number above came from
    from photon_ml_tpu import obs
    from photon_ml_tpu.obs.sentinel import host_fingerprint

    extra["phase_s"] = dict(_PHASE_S)
    extra["metrics"] = obs.registry().snapshot()
    extra["cost_book"] = obs.cost_book().snapshot()
    # environment fingerprint: the sentinel (obs/sentinel.py) treats
    # host.* as identity, never a tracked metric — but uses it to
    # annotate regressions that coincide with an environment change
    # (new jax, different core count) vs the history being compared
    extra["host"] = host_fingerprint()
    record = {
        "metric": "logreg_1Mx256_tron_wallclock",
        "value": round(glm["tpu_s"], 4),
        "unit": "s",
        "vs_baseline": round(glm["cpu_s"] / glm["tpu_s"], 3),
        "device": device,
        "extra": extra,
    }
    print(json.dumps(record))
    if args.sentinel or os.environ.get("PHOTON_BENCH_SENTINEL"):
        # opt-in regression gate: the record just produced vs the
        # committed BENCH history (same fit as the standalone
        # benchmarks/regression_sentinel.py — median + MAD-widened
        # band, direction-aware)
        import glob

        from photon_ml_tpu.obs.sentinel import run_sentinel

        hist = sorted(
            glob.glob(
                os.path.join(
                    os.path.dirname(os.path.abspath(__file__)),
                    "BENCH_r*.json",
                )
            )
        )
        regs, baselines, n_hist = run_sentinel(hist, record)
        if regs:
            for r in regs:
                log(f"SENTINEL REGRESSION: {r.describe()}")
            log(
                f"sentinel: {len(regs)}/{len(baselines)} tracked "
                f"metrics regressed vs {n_hist} history records"
            )
            sys.exit(1)
        log(
            f"sentinel: {len(baselines)} tracked metrics within "
            f"tolerance vs {n_hist} history records"
        )


if __name__ == "__main__":
    main()
