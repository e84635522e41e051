"""Task ``game_cd``: back-to-back identical GAME coordinate-descent runs
through ``photon_ml_tpu.game.descent.CoordinateDescent.run`` (fixed effect,
then the vmapped per-user solves over a bucketed design), each from zero
parameters through the configuration's number of CD iterations, ending with
both parameter sets fetched to the host.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import datagen, reference, train_jobs


def _program(run, xg, xu, user, labels):
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
    from photon_ml_tpu.models.training import OptimizerType

    cfg = run.config
    if run.fault == "half_batch":  # the other half never reaches the program
        half = xg.shape[0] // 2
        xg, xu, user, labels = xg[:half], xu[:half], user[:half], labels[:half]
    n = xg.shape[0]
    users = run.size("num_users")
    zeros, ones = jnp.zeros((n,), jnp.float32), jnp.ones((n,), jnp.float32)
    stop = cfg["stopping_rule"]
    common = dict(
        task=TaskType.LOGISTIC_REGRESSION,
        optimizer=OptimizerType[cfg["optimizer"]],
        max_iters=int(stop["solver_max_iters"]),
        tolerance=float(stop["solver_tolerance"]),
    )
    fixed = FixedEffectCoordinate(
        LabeledBatch(features=xg, labels=labels, offsets=zeros, weights=ones,
                     mask=ones),
        CoordinateConfig(shard="global", reg_weight=float(cfg["l2_fixed"]),
                         **common),
    )
    # the bucketed per-user design is the program's own host-side layout step
    with run.phase("bucketed_design_host"):
        data = GameData.create(
            features={"per_user": np.asarray(xu)},
            labels=np.asarray(labels),
            entity_ids={"userId": np.asarray(user)},
        )
        design = build_bucketed_random_effect_design(
            data, "userId", "per_user", users,
            num_buckets=int(cfg["num_buckets"]),
        )
    run.counts["active_slots"] = int(design.active_slots)
    per_user = RandomEffectCoordinate(
        design=design,
        row_features=xu,
        row_entities=user,
        full_offsets_base=zeros,
        config=CoordinateConfig(
            shard="per_user", reg_weight=float(cfg["l2_user"]),
            random_effect="userId", **common,
        ),
    )
    cd = CoordinateDescent(
        coordinates={"fixed": fixed, "per-user": per_user},
        labels=labels,
        base_offsets=zeros,
        weights=ones,
        task=TaskType.LOGISTIC_REGRESSION,
        fuse_passes=cfg["fuse_passes"],
    )
    iters = int(stop["cd_iterations"])
    return lambda: cd.run(num_iterations=iters)


def one_job(run, train):
    with run.span("job"):
        with run.span("cd_run"):
            model, history = train()
        with run.span("fetch_model"):
            params = {k: np.asarray(v) for k, v in model.params.items()}
    if run.fault == "state_unchanged":
        params = {k: np.zeros_like(v) for k, v in params.items()}
    elif run.fault == "answer_altered":
        params = {k: v * np.float32(1.01) for k, v in params.items()}
    return params, history


def setup(run):
    with run.phase("data_on_device"):
        rows = datagen.game_rows(
            run.config, run.seed, run.size("train_rows"),
            run.size("num_users"), "train",
        )
        jax.block_until_ready(rows)
    train = _program(run, *rows)
    with run.phase("warm_up"):
        one_job(run, train)
    run.spans.clear()
    return {"train": train, "rows": rows, "jobs": [], "last": None}


def window(state, run):
    train_jobs.window(state, run, one_job)


def count(state, run):
    train_jobs.count(state, run, one_job)


def release(state):
    history = state["last"]
    state["program_says"] = {
        "value": float(history[-1].objective),
        "updates": len(history),
        "solver_iterations": [
            (h.coordinate, float(h.solver_iterations)) for h in history
        ],
    }
    state["last"] = state["train"] = None


def compare(run, rows, params, says, dtype=jnp.float32):
    """The numbers that decide ``correct`` for one fetched model and the
    objective the program reported for it; with a lower ``dtype`` the
    reference stands in the program's place (the control)."""
    cfg = run.config
    xg, xu, user, labels = rows
    l2f, l2u = float(cfg["l2_fixed"]), float(cfg["l2_user"])
    w_f, table = params["fixed"], params["per-user"]
    value, g_f, g_t, _ = reference.game_value_grads(
        xg, xu, user, labels, w_f, table, l2f, l2u
    )
    if dtype != jnp.float32:
        low = reference.game_value_grads(
            xg, xu, user, labels, w_f, table, l2f, l2u, dtype
        )
        says = dict(says, value=float(low[0]))
    _, g_f0, g_t0, _ = reference.game_value_grads(
        xg, xu, user, labels, jnp.zeros_like(g_f), jnp.zeros_like(g_t),
        l2f, l2u,
    )
    return {
        "value_gap": reference.rel_gap(says["value"], value),
        "grad_left_fixed": float(
            jnp.linalg.norm(g_f) / jnp.linalg.norm(g_f0)
        ),
        "grad_left_user": float(
            jnp.linalg.norm(g_t.ravel()) / jnp.linalg.norm(g_t0.ravel())
        ),
    }


def control(state, run):
    return compare(run, state["rows"], state["jobs"][-1],
                   state["program_says"], jnp.bfloat16)


def check(state, run):
    cfg, lim = run.config, run.config["limits"]
    says, jobs = state["program_says"], state["jobs"]
    last = jobs[-1]
    got = compare(run, state["rows"], last, says)
    jobs_gap = max(
        [
            max(reference.rel_l2(other[k], last[k]) for k in last)
            for other in jobs[:-1]
        ]
        or [0.0]
    )
    hg, hu, huser, hy = (
        np.asarray(a) for a in datagen.game_rows(
            cfg, run.seed, run.size("heldout_rows"), run.size("num_users"),
            "heldout",
        )
    )
    aucs = [
        reference.auc(
            hy, hg @ job["fixed"] + np.sum(hu * job["per-user"][huser], axis=1)
        )
        for job in jobs
    ]
    target = float(run.param("auc_target"))
    run.failed = sum(1 for a in aucs if not a >= target)
    run.counts.update(
        evals_per_job=says["updates"],
        solver_iterations=says["solver_iterations"],
        heldout_auc_min=min(aucs),
        jobs_gap=jobs_gap,
        rows=int(state["rows"][0].shape[0]),
    )
    for name in ("value_gap", "grad_left_fixed", "grad_left_user"):
        run.compared.append((name, got[name], float(lim[name])))
    run.compared.append(("auc_short", target - min(aucs), 0.0))
