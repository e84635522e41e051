"""Task ``glm_solve``: back-to-back identical GLM solves through
``photon_ml_tpu.models.training.train_glm`` (scan path, one lambda), each from
zero coefficients to the configuration's stopping rule, ending with the model
fetched to the host.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import datagen, reference, train_jobs


def _program(run, indices, values, labels):
    from photon_ml_tpu.core.tasks import TaskType
    from photon_ml_tpu.core.types import LabeledBatch
    from photon_ml_tpu.models.training import (
        GLMTrainingConfig,
        OptimizerType,
        train_glm,
    )
    from photon_ml_tpu.ops.objective import RegularizationContext
    from photon_ml_tpu.ops.sparse import SparseFeatures

    cfg = run.config
    if run.fault == "half_batch":  # the other half never reaches the program
        half = indices.shape[0] // 2
        indices, values, labels = indices[:half], values[:half], labels[:half]
    n = indices.shape[0]
    ones = jnp.ones((n,), jnp.float32)
    batch = LabeledBatch(
        features=SparseFeatures(indices, values, int(cfg["num_coefficients"])),
        labels=labels,
        offsets=jnp.zeros((n,), jnp.float32),
        weights=ones,
        mask=ones,
    )
    stop = cfg["stopping_rule"]
    tcfg = GLMTrainingConfig(
        task=TaskType.LOGISTIC_REGRESSION,
        optimizer=OptimizerType[cfg["optimizer"]],
        regularization=RegularizationContext("L2"),
        reg_weights=(float(cfg["l2"]),),
        max_iters=int(stop["max_iters"]),
        tolerance=float(stop["tolerance"]),
        num_corrections=int(cfg["num_corrections"]),
        track_models=False,
        path_mode="scan",
    )
    return lambda: train_glm(batch, tcfg)[0]


def one_job(run, train):
    """Dispatch, wait, fetch: the owner of a job has a model only after the
    fetch.  Returns (host coefficients, the program's TrainedModel)."""
    with run.span("job"):
        with run.span("dispatch"):
            trained = train()
        with run.span("run"):
            jax.block_until_ready(trained.model.coefficients.means)
        with run.span("fetch_model"):
            w = np.asarray(trained.model.coefficients.means)
    if run.fault == "state_unchanged":
        w = np.zeros_like(w)
    elif run.fault == "answer_altered":
        w = w * np.float32(1.01)
    return w, trained


def setup(run):
    with run.phase("data_on_device"):
        train_rows = datagen.glm_rows(
            run.config, run.seed, run.size("train_rows"), "train"
        )
        jax.block_until_ready(train_rows)
    with run.phase("warm_up"):
        train = _program(run, *train_rows)
        one_job(run, train)  # compiles, or loads from the cache, and runs
    run.spans.clear()
    return {"train": train, "rows": train_rows, "jobs": [], "last": None}


def window(state, run):
    train_jobs.window(state, run, one_job)


def count(state, run):
    train_jobs.count(state, run, one_job)


def release(state):
    """Keep what the comparison needs of the last job, on the host; drop the
    program's device state."""
    res = state["last"].result
    state["program_says"] = {
        "value": float(np.asarray(res.value)),
        "grad": np.asarray(res.grad),
        "evals": int(np.asarray(res.evals)),
        "iterations": int(np.asarray(res.iterations)),
        "reason": int(np.asarray(res.reason)),
    }
    state["last"] = state["train"] = None


def compare(run, rows, w, says, dtype=jnp.float32):
    """The numbers that decide ``correct`` for one fetched model ``w`` and
    what the program said of it.  With a lower ``dtype`` the reference itself
    stands in the program's place: the control."""
    cfg = run.config
    indices, values, labels = rows
    l2 = float(cfg["l2"])
    value, grad, _ = reference.glm_value_grad(indices, values, labels, w, l2)
    if dtype != jnp.float32:
        v_low, g_low, _ = reference.glm_value_grad(
            indices, values, labels, w, l2, dtype
        )
        says = dict(says, value=float(v_low), grad=g_low)
    _, grad0, _ = reference.glm_value_grad(
        indices, values, labels, jnp.zeros_like(grad), l2
    )
    return {
        "value_gap": reference.rel_gap(says["value"], value),
        "grad_gap": reference.rel_l2(says["grad"], grad),
        "grad_left": float(jnp.linalg.norm(grad) / jnp.linalg.norm(grad0)),
    }


def control(state, run):
    return compare(run, state["rows"], state["jobs"][-1],
                   state["program_says"], jnp.bfloat16)


def check(state, run):
    cfg, lim = run.config, run.config["limits"]
    says = state["program_says"]
    jobs = state["jobs"]
    w = jobs[-1]
    got = compare(run, state["rows"], w, says)
    # the last job is compared in full; every job of the window is held to
    # the held-out AUC target (jobs_gap, how far the others lie from the one
    # compared, is kept as a count: it has read 0.0 in every run)
    jobs_gap = max(
        [reference.rel_l2(other, w) for other in jobs[:-1]] or [0.0]
    )
    h_idx, h_val, h_y = datagen.glm_rows(
        cfg, run.seed, run.size("heldout_rows"), "heldout"
    )
    h_idx, h_val, h_y = np.asarray(h_idx), np.asarray(h_val), np.asarray(h_y)
    aucs = [
        reference.auc(h_y, np.sum(h_val * job[h_idx], axis=1)) for job in jobs
    ]
    target = float(run.param("auc_target"))
    run.failed = sum(1 for a in aucs if not a >= target)
    run.counts.update(
        evals_per_job=says["evals"],
        iterations_per_job=says["iterations"],
        reason=says["reason"],
        heldout_auc_min=min(aucs),
        jobs_gap=jobs_gap,
        rows=int(state["rows"][0].shape[0]),
        slots=int(state["rows"][0].shape[1]),
    )
    for name in ("value_gap", "grad_gap", "grad_left"):
        run.compared.append((name, got[name], float(lim[name])))
    run.compared.append(("auc_short", target - min(aucs), 0.0))
