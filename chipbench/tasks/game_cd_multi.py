"""Task ``game_cd_multi``: back-to-back identical GAME coordinate-descent
runs through ``photon_ml_tpu.game.descent.CoordinateDescent.run`` over the
configuration's own list of coordinates, in its order (a fixed effect and
any number of random effects, each random effect a bucketed design under
the configuration's ``active_cap``), each run from zero parameters through
the configuration's number of CD iterations, ending with every parameter
set fetched to the host.

What each random effect was trained on (its active sample: row ids and
weights) is read from its design once at set-up, checked there by plain
numpy against the rule, and handed to the reference.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import datagen_music, program_spans, reference
from chipbench import reference_multi
from chipbench.tasks import game_cd


class SampleBreaksTheRule(RuntimeError):
    """A bucketed design's active sample is not what the cap rule says."""


def active_sample(design, ids, cap, name):
    """(n,) float32 train weight of every row under this design: 1 for a
    row of an entity with at most ``cap`` rows, count / cap for a sampled
    row of an entity with more, 0 for a passive row.  Raises where the
    design departs from the rule: every held row on a lane of its own
    entity and held once, exactly min(count, cap) rows an entity, the
    weights as above."""
    def require(ok, what):
        if not ok:
            raise SampleBreaksTheRule(f"{name}: {what}")

    entities = design.num_entities
    weight = np.zeros(ids.size, np.float32)
    times_held = np.zeros(ids.size, np.int64)
    for bucket, lanes in zip(design.buckets, design.entity_index):
        rows = np.asarray(bucket.row_index)
        held = rows >= 0
        lane_entity = np.broadcast_to(
            np.asarray(lanes)[:, None], rows.shape)[held]
        r = rows[held]
        require(np.array_equal(ids[r], lane_entity),
                "a row on another entity's lane")
        times_held += np.bincount(r, minlength=ids.size)
        weight[r] = np.asarray(bucket.weights)[held]
    require(times_held.max(initial=0) <= 1, "a row held twice")
    held_rows = times_held > 0
    counts = np.bincount(ids, minlength=entities)
    require(
        np.array_equal(np.bincount(ids[held_rows], minlength=entities),
                       np.minimum(counts, cap)),
        "an entity's active rows are not min(count, cap)")
    want = np.where(counts > cap, counts / cap, 1.0)[ids]
    require(np.allclose(weight[held_rows], want[held_rows], rtol=1e-6),
            "a weight is not count / cap")
    return weight


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
    random = [c for c in cfg["coordinates"] if c["kind"] == "random"]
    host_ids = {c["entity"]: np.asarray(ents[c["entity"]]) for c in random}
    with run.phase("bucketed_design_host"):
        data = GameData.create(
            features={c["shard"]: np.asarray(feats[c["shard"]])
                      for c in random},
            labels=np.asarray(labels),
            entity_ids=host_ids,
        )
    coordinates, work, train_weight = {}, [], {}
    for c in cfg["coordinates"]:
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
        coordinates[c["name"]] = RandomEffectCoordinate(
            design=design,
            row_features=x,
            row_entities=ents[c["entity"]],
            full_offsets_base=zeros,
            config=CoordinateConfig(
                shard=c["shard"], reg_weight=float(c["l2"]),
                random_effect=c["entity"], **common,
            ),
        )
        work.append({
            "name": c["name"], "kind": "random", "dim": int(x.shape[1]),
            "active_slots": int(design.active_slots),
            "entities": sum(len(e) for e in design.entity_index),
        })
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


# one job and its harness spans (``job`` > ``cd_run``, ``fetch_model``), the
# faults planted under it, the window, the counted extra job and what the
# program says of the last job are ``game_cd``'s, whatever the coordinates
one_job, window, count, release = (
    game_cd.one_job, game_cd.window, game_cd.count, game_cd.release)


def _design_spans():
    """What the program's ``game.design`` spans of set-up said: a list of
    (seconds, attributes); empty on a checkout without them."""
    got = program_spans.ring()
    if got is None:
        return []
    return [
        (r[program_spans.END] - r[program_spans.START],
         dict(r[program_spans.ATTRS]))
        for r in got[0] if r[program_spans.NAME] == "game.design"
    ]


def setup(run):
    with run.phase("data_on_device"):
        rows = datagen_music.music_rows(
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


def _short(name):
    """``per-user`` -> ``user``: the compared numbers' suffix."""
    return name.split("-")[-1]


def _reference_coordinates(run, state, params):
    rows = state["rows"]
    out = []
    for c in run.config["coordinates"]:
        part = {"kind": c["kind"], "x": rows["features"][c["shard"]],
                "params": params[c["name"]], "l2": float(c["l2"])}
        if c["kind"] == "random":
            part["ids"] = rows["entities"][c["entity"]]
            part["train_weight"] = jnp.asarray(
                state["train_weight"][c["name"]])
        out.append(part)
    return out


def compare(run, state, params, says, dtype=jnp.float32):
    """The numbers that decide ``correct`` for one fetched model and the
    objective the program reported for it; with a lower ``dtype`` the
    reference stands in the program's place (the control)."""
    labels = state["rows"]["labels"]
    at_model = _reference_coordinates(run, state, params)
    value, grads, _ = reference_multi.value_grads(at_model, labels)
    if dtype != jnp.float32:
        low = reference_multi.value_grads(at_model, labels, dtype)
        says = dict(says, value=float(low[0]))
    at_zero = _reference_coordinates(
        run, state, {k: np.zeros_like(v) for k, v in params.items()})
    _, grads0, _ = reference_multi.value_grads(at_zero, labels)
    got = {"value_gap": reference.rel_gap(says["value"], value)}
    for c, g, g0 in zip(run.config["coordinates"], grads, grads0):
        got["grad_left_" + _short(c["name"])] = float(
            jnp.linalg.norm(g.ravel()) / jnp.linalg.norm(g0.ravel()))
    return got


def control(state, run):
    return compare(run, state, state["jobs"][-1], state["program_says"],
                   jnp.bfloat16)


def _heldout_aucs(run, jobs):
    """Held-out AUC of every job's model, and of the last job's with each
    random effect's table zeroed in turn."""
    cfg = run.config
    held = datagen_music.music_rows(
        cfg, run.param, run.seed, run.size("heldout_rows"), "heldout")
    y = np.asarray(held["labels"])
    x = {k: np.asarray(v) for k, v in held["features"].items()}
    ids = {k: np.asarray(v) for k, v in held["entities"].items()}

    def auc(model, without=None):
        z = np.zeros(y.shape, np.float64)
        for c in cfg["coordinates"]:
            if c["name"] == without:
                continue
            p = model[c["name"]]
            z += (x[c["shard"]] @ p if c["kind"] == "fixed" else
                  np.sum(x[c["shard"]] * p[ids[c["entity"]]], axis=1))
        return reference.auc(y, z)

    without = {
        c["name"]: auc(jobs[-1], c["name"])
        for c in cfg["coordinates"] if c["kind"] == "random"
    }
    return [auc(job) for job in jobs], without


def check(state, run):
    lim = run.param("limits")  # of the model this size reaches
    says, jobs = state["program_says"], state["jobs"]
    last = jobs[-1]
    got = compare(run, state, last, says)
    jobs_gap = max(
        [
            max(reference.rel_l2(other[k], last[k]) for k in last)
            for other in jobs[:-1]
        ]
        or [0.0]
    )
    aucs, without = _heldout_aucs(run, jobs)
    target = float(run.param("auc_target"))
    run.failed = sum(1 for a in aucs if not a >= target)
    run.counts.update(
        evals_per_job=says["updates"],
        solver_iterations=says["solver_iterations"],
        heldout_auc_min=min(aucs),
        jobs_gap=jobs_gap,
        rows=int(state["rows"]["labels"].shape[0]),
    )
    print(f"heldout_auc: {min(aucs)!r} .. {max(aucs)!r}; with a table "
          f"zeroed: {without!r}; jobs_gap {jobs_gap!r}", file=sys.stderr)
    print("jobs_s:", [round(t1 - t0, 4) for name, t0, t1 in run.spans
                      if name == "job"], file=sys.stderr)
    for name in got:
        run.compared.append((name, got[name], float(lim[name])))
    run.compared.append(("auc_short", target - min(aucs), 0.0))
