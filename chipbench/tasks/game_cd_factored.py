"""Task ``game_cd_factored``: back-to-back identical GAME coordinate-descent
runs through ``photon_ml_tpu.game.descent.CoordinateDescent.run`` over the
configuration's own list of coordinates, in its order: a fixed effect, plain
random effects and FACTORED random effects (``game/factored.py``: w_e = B
gamma_e, the per-entity solves in the latent dimension alternating with a
solve of the shared B), every random effect a bucketed design under the
configuration's ``active_cap``.  Each run starts from the coordinates'
``initial_params()`` (zeros; a factored coordinate's B the configuration's
B0) and ends with every parameter set fetched to the host, both leaves of a
factored coordinate.

The active samples, the window, the counted extra job and the planted
faults are ``game_cd_multi``'s and ``game_cd``'s, reading a factored
coordinate's two leaves where those read one array.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import datagen_music_lowrank, reference, reference_factored
from chipbench import train_jobs, work_factored
from chipbench.tasks.game_cd_multi import _design_spans, active_sample


def _program(run, rows):
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
    from photon_ml_tpu.game.factored import (
        FactoredConfig,
        FactoredRandomEffectCoordinate,
    )
    from photon_ml_tpu.models.training import OptimizerType

    cfg = run.config
    feats, ents, labels = rows["features"], rows["entities"], rows["labels"]
    n_all = labels.shape[0]
    if run.fault == "half_batch":  # the other half never reaches the program
        half = n_all // 2
        feats = {k: v[:half] for k, v in feats.items()}
        ents = {k: v[:half] for k, v in ents.items()}
        labels = labels[:half]
    n = labels.shape[0]
    zeros, ones = jnp.zeros((n,), jnp.float32), jnp.ones((n,), jnp.float32)
    stop = cfg["stopping_rule"]
    common = dict(
        task=TaskType.LOGISTIC_REGRESSION,
        optimizer=OptimizerType[cfg["optimizer"]],
        max_iters=int(stop["solver_max_iters"]),
        tolerance=float(stop["solver_tolerance"]),
    )
    projection = cfg["projection_solve"]
    per_entity = [c for c in run.param("coordinates") if c["kind"] != "fixed"]
    host_ids = {c["entity"]: np.asarray(ents[c["entity"]])
                for c in per_entity}
    with run.phase("bucketed_design_host"):
        data = GameData.create(
            features={c["shard"]: np.asarray(feats[c["shard"]])
                      for c in per_entity},
            labels=np.asarray(labels),
            entity_ids=host_ids,
        )
    coordinates, work, train_weight = {}, [], {}
    for c in run.param("coordinates"):
        x = feats[c["shard"]]
        if c["kind"] == "fixed":
            coordinates[c["name"]] = FixedEffectCoordinate(
                LabeledBatch(features=x, labels=labels, offsets=zeros,
                             weights=ones, mask=ones),
                CoordinateConfig(shard=c["shard"],
                                 reg_weight=float(c["l2"]), **common),
            )
            work.append({"name": c["name"], "kind": "fixed",
                         "dim": int(x.shape[1]), "active_slots": 0,
                         "entities": 0})
            continue
        # the bucketed design is the program's own host-side layout step
        with run.phase("bucketed_design_host"):
            design = build_bucketed_random_effect_design(
                data, c["entity"], c["shard"], run.size(c["entities"]),
                num_buckets=int(cfg["num_buckets"]),
                active_cap=int(cfg["active_cap"]),
            )
        with run.phase("sample_check"):
            weight = active_sample(design, host_ids[c["entity"]],
                                   int(cfg["active_cap"]), c["name"])
            train_weight[c["name"]] = np.concatenate(
                [weight, np.zeros(n_all - n, np.float32)])
        re_config = CoordinateConfig(
            shard=c["shard"], reg_weight=float(c["l2"]),
            random_effect=c["entity"], **common,
        )
        shape = {
            "name": c["name"], "kind": c["kind"], "dim": int(x.shape[1]),
            "active_slots": int(design.active_slots),
            "entities": sum(len(e) for e in design.entity_index),
        }
        views = dict(design=design, row_features=x,
                     row_entities=ents[c["entity"]], full_offsets_base=zeros)
        if c["kind"] == "random":
            coordinates[c["name"]] = RandomEffectCoordinate(
                config=re_config, **views)
        else:
            coordinates[c["name"]] = FactoredRandomEffectCoordinate(
                re_config=re_config,
                factored=FactoredConfig(
                    latent_dim=int(cfg["latent_dim"]),
                    num_inner_iterations=int(cfg["num_inner_iterations"]),
                    latent_factor_config=CoordinateConfig(
                        shard=c["shard"],
                        task=TaskType.LOGISTIC_REGRESSION,
                        optimizer=OptimizerType[projection["optimizer"]],
                        reg_weight=float(c["l2_projection"]),
                        max_iters=int(projection["max_iters"]),
                        tolerance=float(projection["tolerance"]),
                        tron_max_cg=int(projection["max_cg"]),
                    ),
                ),
                initial_projection=_b0(run),
                **views,
            )
            shape["latent_dim"] = int(cfg["latent_dim"])
            shape["lanes_with_an_entity"] = int(sum(
                np.count_nonzero(np.asarray(e) < design.num_entities)
                for e in design.entity_index))
        work.append(shape)
    run.counts["coordinates_work"] = work
    cd = CoordinateDescent(
        coordinates=coordinates,
        labels=labels,
        base_offsets=zeros,
        weights=ones,
        task=TaskType.LOGISTIC_REGRESSION,
        fuse_passes=cfg["fuse_passes"],
    )
    iters = int(stop["cd_iterations"])
    return (lambda: cd.run(num_iterations=iters)), train_weight


def _b0(run):
    return datagen_music_lowrank.initial_projection(run.config, run.seed)


def _factored(run):
    return [c["name"] for c in run.param("coordinates")
            if c["kind"] == "factored"]


def _leaves(fn, params):
    """``fn`` over every array of a parameter set, a factored coordinate's
    dict of two included."""
    return {
        name: ({k: fn(v) for k, v in p.items()} if isinstance(p, dict)
               else fn(p))
        for name, p in params.items()
    }


def _initial(run, params):
    """The parameters a job starts from, in the fetched model's shapes:
    zeros, and B0 for a factored coordinate's projection."""
    start = _leaves(np.zeros_like, params)
    for name in _factored(run):
        start[name]["projection"] = _b0(run)
    return start


def one_job(run, train):
    with run.span("job"):
        with run.span("cd_run"):
            model, history = train()
        with run.span("fetch_model"):
            params = {
                k: ({"gamma": np.asarray(v.gamma),
                     "projection": np.asarray(v.projection)}
                    if hasattr(v, "projection") else np.asarray(v))
                for k, v in model.params.items()
            }
    if run.fault == "state_unchanged":
        params = _initial(run, params)
    elif run.fault == "answer_altered":
        params = _leaves(lambda v: v * np.float32(1.01), params)
    return params, history


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
        "solver_work": [
            (h.coordinate, float(h.solver_iterations), h.inner_iterations)
            for h in history
        ],
    }
    state["last"] = state["train"] = None


def setup(run):
    from photon_ml_tpu.game import factored

    if not hasattr(factored, "FactoredUpdateTracker"):
        # before any data is made: a checkout from before the tracker
        # cannot say what its projection solves did, so it has no reading
        raise SystemExit(
            "game_cd_factored: this checkout's game/factored.py has no "
            "FactoredUpdateTracker; the cell cannot be read on it")
    with run.phase("data_on_device"):
        rows = datagen_music_lowrank.music_rows(
            run.config, run.param, run.seed, run.size("train_rows"), "train")
        jax.block_until_ready(rows)
    train, train_weight = _program(run, rows)
    # program_spans cuts the ring to the window; set-up's spans stay here
    run.counts["design_spans"] = _design_spans()
    with run.phase("warm_up"):
        one_job(run, train)
    run.spans.clear()
    return {"train": train, "rows": rows, "train_weight": train_weight,
            "jobs": [], "last": None}


def _reference_coordinates(run, state, params):
    rows = state["rows"]
    out = []
    for c in run.param("coordinates"):
        part = {"kind": c["kind"], "x": rows["features"][c["shard"]],
                "params": params[c["name"]], "l2": float(c["l2"])}
        if c["kind"] != "fixed":
            part["ids"] = rows["entities"][c["entity"]]
            part["train_weight"] = jnp.asarray(
                state["train_weight"][c["name"]])
        if c["kind"] == "factored":
            part["l2_projection"] = float(c["l2_projection"])
        out.append(part)
    return out


def _norm(g):
    return float(jnp.linalg.norm(jnp.ravel(g)))


def compare(run, state, params, says, dtype=jnp.float32):
    """The numbers that decide ``correct`` for one fetched model and the
    objective the program reported for it; with a lower ``dtype`` the
    reference stands in the program's place (the control).

    A gradient is read against the same coordinate's at the job's start
    (``initial_params()``: with gamma zero the bilinear term gives B no
    data gradient, so B is read against its gradient at (B0, the fetched
    gamma), every other coordinate as fetched)."""
    labels = state["rows"]["labels"]

    def grads_at(p):
        return reference_factored.value_grads(
            _reference_coordinates(run, state, p), labels)[1]

    at_model = _reference_coordinates(run, state, params)
    value, grads, _ = reference_factored.value_grads(at_model, labels)
    if dtype != jnp.float32:
        low = reference_factored.value_grads(at_model, labels, dtype)
        says = dict(says, value=float(low[0]))
    start = _initial(run, params)
    grads0 = grads_at(start)
    before_projection = {
        name: (dict(p, projection=start[name]["projection"])
               if isinstance(p, dict) else p)
        for name, p in params.items()
    }
    grads_b0 = grads_at(before_projection)
    got = {"value_gap": reference.rel_gap(says["value"], value)}
    for c, g, g0, gb in zip(run.param("coordinates"), grads, grads0,
                            grads_b0):
        if c["kind"] == "factored":
            got["grad_left_gamma"] = _norm(g["gamma"]) / _norm(g0["gamma"])
            got["grad_left_B"] = (
                _norm(g["projection"]) / _norm(gb["projection"]))
        else:
            got["grad_left_" + c["name"].split("-")[-1]] = (
                _norm(g) / _norm(g0))
    return got


def control(state, run):
    return compare(run, state, state["jobs"][-1], state["program_says"],
                   jnp.bfloat16)


def _heldout_aucs(run, jobs):
    """Held-out AUC of every job's model, and of the last job's with each
    random effect's table (a factored one's gamma) zeroed in turn."""
    cfg = run.config
    held = datagen_music_lowrank.music_rows(
        cfg, run.param, run.seed, run.size("heldout_rows"), "heldout")
    y = np.asarray(held["labels"])
    x = {k: np.asarray(v) for k, v in held["features"].items()}
    ids = {k: np.asarray(v) for k, v in held["entities"].items()}

    def score(c, p):
        if c["kind"] == "fixed":
            return x[c["shard"]] @ p
        if c["kind"] == "random":
            return np.sum(x[c["shard"]] * p[ids[c["entity"]]], axis=1)
        return np.sum((x[c["shard"]] @ p["projection"])
                      * p["gamma"][ids[c["entity"]]], axis=1)

    def auc(model, without=None):
        z = np.zeros(y.shape, np.float64)
        for c in run.param("coordinates"):
            if c["name"] != without:
                z += score(c, model[c["name"]])
        return reference.auc(y, z)

    without = {
        c["name"]: auc(jobs[-1], c["name"])
        for c in run.param("coordinates") if c["kind"] != "fixed"
    }
    return [auc(job) for job in jobs], without


def _rel_l2(a, b):
    if isinstance(a, dict):
        return max(reference.rel_l2(a[k], b[k]) for k in a)
    return reference.rel_l2(a, b)


def check(state, run):
    lim = run.param("limits")  # of the model this size reaches
    says, jobs = state["program_says"], state["jobs"]
    last = jobs[-1]
    got = compare(run, state, last, says)
    jobs_gap = max(
        [max(_rel_l2(other[k], last[k]) for k in last)
         for other in jobs[:-1]] or [0.0]
    )
    aucs, without = _heldout_aucs(run, jobs)
    target = float(run.param("auc_target"))
    run.failed = sum(1 for a in aucs if not a >= target)
    run.counts.update(
        evals_per_job=says["updates"],
        solver_iterations=says["solver_iterations"],
        solver_work=says["solver_work"],
        heldout_auc_min=min(aucs),
        jobs_gap=jobs_gap,
        rows=int(state["rows"]["labels"].shape[0]),
    )
    job = work_factored.job(run.counts)
    run.counts["projection_passes_per_job"] = job["projection_passes"]
    # every real lane of every bucket, in every inner iteration of every
    # factored update, has to be in the program's own record
    lanes = {c["name"]: c["lanes_with_an_entity"]
             for c in run.counts["coordinates_work"]
             if c["kind"] == "factored"}
    missing = sum(
        abs(lanes[name] - it["lanes"]["count"])
        for name, _, inner in says["solver_work"] if name in lanes
        for it in inner
    )
    solves = [
        [(it["projection"]["iterations"], it["projection"]["cg_iterations"],
          it["projection"]["reason"]) for it in inner]
        for name, _, inner in says["solver_work"] if name in lanes
    ]
    print(f"heldout_auc: {min(aucs)!r} .. {max(aucs)!r}; with a table "
          f"zeroed: {without!r}; jobs_gap {jobs_gap!r}", file=sys.stderr)
    print(f"projection_solves (outer, cg, reason) an update: {solves!r}; "
          f"passes a job {job['projection_passes']!r}", file=sys.stderr)
    print("jobs_s:", [round(t1 - t0, 4) for name, t0, t1 in run.spans
                      if name == "job"], file=sys.stderr)
    for name in got:
        run.compared.append((name, got[name], float(lim[name])))
    run.compared.append(("tracker_lanes_missing", float(missing), 0.0))
    run.compared.append(("auc_short", target - min(aucs), 0.0))
