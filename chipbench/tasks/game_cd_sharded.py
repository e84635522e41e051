"""Task ``game_cd_sharded``: ``game_cd_multi``'s back-to-back GAME
coordinate-descent jobs with EVERY random effect entity-sharded over the
cell's chips (``photon_ml_tpu.game.EntityShardedRandomEffectCoordinate``
under ``shard_map``), each in the row partition of its own entity type, the
rows moved between the partitions by the program's on-device exchange.  The
objects are the driver's (``cli/game_train.py``), built by the same
constructors: ``GameData.create`` -> ``entity_shard_layouts`` (one
``entity_partition_game_data`` a random effect, the first one's partition
the canonical row order) -> each layout's ``bucketed_design``
(``build_bucketed_random_effect_design`` over the rows in their own order,
laid out where the program lays a sharded design out: on the host) ->
the sharded coordinates and a row-sharded ``FixedEffectCoordinate`` ->
``CoordinateDescent.run``.  A job ends with every parameter set fetched to
the host, the tables in GLOBAL entity order.

The rows are made a block at a time and kept on the HOST
(``datagen_music_blocked``): no chip ever holds more than its share, and the
reference (``reference_multi_blocked``) sums over the same host rows in
their original order.
"""

from __future__ import annotations

import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import datagen_music_blocked, program_spans, reference
from chipbench import reference_multi_blocked
from chipbench.tasks import game_cd, game_cd_multi


class _StoredTable:
    """A sharded coordinate's table as the program holds it (shard-major,
    padded); ``np.asarray`` fetches it and puts it in global entity order,
    so ``game_cd.one_job`` books both under ``fetch_model``."""

    def __init__(self, table, assignment):
        self.table, self.assignment = table, assignment

    def __array__(self, dtype=None, copy=None):
        return self.assignment.table_to_global(np.asarray(self.table))


def _in_original_rows(design, part):
    """What ``game_cd_multi.active_sample`` reads of a design, every slot's
    row index taken back from the partition's order to the original one."""
    buckets = []
    for bucket in design.buckets:
        rows = np.asarray(bucket.row_index)
        buckets.append(SimpleNamespace(
            row_index=np.where(
                rows >= 0, part.row_perm[np.maximum(rows, 0)], -1),
            weights=np.asarray(bucket.weights),
        ))
    return SimpleNamespace(buckets=buckets, entity_index=design.entity_index,
                           num_entities=design.num_entities)


def _program(run, rows):
    from photon_ml_tpu.core.tasks import TaskType
    from photon_ml_tpu.core.types import LabeledBatch
    from photon_ml_tpu.game import (
        CoordinateConfig,
        CoordinateDescent,
        EntityShardedRandomEffectCoordinate,
        FixedEffectCoordinate,
        GameData,
        entity_shard_layouts,
    )
    from photon_ml_tpu.models.training import OptimizerType
    from photon_ml_tpu.parallel.mesh import batch_sharding, make_entity_mesh

    cfg = run.config
    shards = int(cfg["entity_shards"])
    feats, ents, labels = rows["features"], rows["entities"], rows["labels"]
    n_all = labels.shape[0]
    if run.fault == "half_batch":  # the other half never reaches the program
        half = n_all // 2
        feats = {k: v[:half] for k, v in feats.items()}
        ents = {k: v[:half] for k, v in ents.items()}
        labels = labels[:half]
    n = labels.shape[0]
    stop = cfg["stopping_rule"]
    common = dict(
        task=TaskType.LOGISTIC_REGRESSION,
        optimizer=OptimizerType[cfg["optimizer"]],
        max_iters=int(stop["solver_max_iters"]),
        tolerance=float(stop["solver_tolerance"]),
    )
    mesh = make_entity_mesh(shards, devices=jax.devices()[:shards])
    put = lambda v: jax.device_put(
        np.asarray(v, np.float32), batch_sharding(mesh, np.ndim(v)))
    random = [c for c in cfg["coordinates"] if c["kind"] == "random"]
    data = GameData.create(features=feats, labels=labels, entity_ids=ents)
    # one layout step a random effect: the first one's partition is the
    # canonical row order, the others carry their exchange plan
    with run.phase("entity_layout_host"):
        by_entity = entity_shard_layouts(
            data,
            {c["entity"]: run.size(c["entities"]) for c in random},
            shards,
            {c["entity"]: {c["shard"]} for c in random},
        )
    layouts = {c["name"]: by_entity[c["entity"]] for c in random}
    rows_in_order = next(iter(by_entity.values())).data
    coordinates, work, train_weight = {}, [], {}
    for c in cfg["coordinates"]:
        if c["kind"] == "fixed":
            x = rows_in_order.features[c["shard"]]
            coordinates[c["name"]] = FixedEffectCoordinate(
                LabeledBatch(
                    features=put(x), labels=put(rows_in_order.labels),
                    offsets=put(rows_in_order.offsets),
                    weights=put(rows_in_order.weights),  # a pad row: 0
                    mask=put(np.ones(x.shape[0], np.float32)),
                ),
                CoordinateConfig(shard=c["shard"],
                                 reg_weight=float(c["l2"]), **common),
            )
            work.append({"name": c["name"], "kind": "fixed",
                         "dim": int(x.shape[1]), "active_slots": 0,
                         "entities": 0})
            continue
        own, assignment, part = layouts[c["name"]]
        # the bucketed design is the program's own host-side layout step
        with run.phase("bucketed_design_host"):
            design = layouts[c["name"]].bucketed_design(
                c["entity"], c["shard"],
                num_buckets=int(cfg["num_buckets"]),
                active_cap=int(cfg["active_cap"]),
            )
        with run.phase("sample_check"):
            weight = game_cd_multi.active_sample(
                _in_original_rows(design, part), ents[c["entity"]],
                int(cfg["active_cap"]), c["name"])
            train_weight[c["name"]] = np.concatenate(
                [weight, np.zeros(n_all - n, np.float32)])
        with run.phase("shard_design"):
            coordinates[c["name"]] = EntityShardedRandomEffectCoordinate(
                design=design,
                row_features=own.features[c["shard"]],
                row_entities=own.entity_ids[c["entity"]],
                full_offsets_base=np.asarray(
                    rows_in_order.offsets, np.float32),
                config=CoordinateConfig(
                    shard=c["shard"], reg_weight=float(c["l2"]),
                    random_effect=c["entity"], **common,
                ),
                mesh=mesh,
                assignment=assignment,
                partition=part,
            )
        work.append({
            "name": c["name"], "kind": "random",
            "dim": int(own.features[c["shard"]].shape[1]),
            "active_slots": int(design.active_slots),
            "entities": sum(len(e) for e in design.entity_index),
            "exchange_rows": 0 if part.exchange is None else n,
        })
        del design
    run.counts["coordinates_work"] = work
    cd = CoordinateDescent(
        coordinates=coordinates,
        labels=put(rows_in_order.labels),
        base_offsets=put(rows_in_order.offsets),
        weights=put(rows_in_order.weights),
        task=TaskType.LOGISTIC_REGRESSION,
        fuse_passes=cfg["fuse_passes"],
    )
    iters = int(stop["cd_iterations"])
    tables = {c["name"]: layouts[c["name"]].assignment for c in random}

    def train():
        model, history = cd.run(num_iterations=iters)
        model.params = {
            name: _StoredTable(p, tables[name]) if name in tables else p
            for name, p in model.params.items()
        }
        return model, history

    return train, train_weight


# one job and its harness spans, the faults planted under it, the window,
# the counted extra job and what the program says of the last job are
# ``game_cd``'s, as for ``game_cd_multi``
one_job, window, count, release = (
    game_cd.one_job, game_cd.window, game_cd.count, game_cd.release)


def _spans_of_setup(name):
    """What the program's spans of that name said at set-up: a list of
    (seconds, attributes); empty on a checkout without them."""
    got = program_spans.ring()
    if got is None:
        return []
    return [
        (r[program_spans.END] - r[program_spans.START],
         dict(r[program_spans.ATTRS]))
        for r in got[0] if r[program_spans.NAME] == name
    ]


def setup(run):
    # a checkout whose program cannot lay out two sharded random effects
    # fails here, before 2^24 rows are made
    from photon_ml_tpu.game import entity_shard_layouts  # noqa: F401

    with run.phase("data_on_host"):
        rows = datagen_music_blocked.music_rows_host(
            run.config, run.param, run.seed, run.size("train_rows"), "train")
    train, train_weight = _program(run, rows)
    # program_spans cuts the ring to the window; set-up's spans stay here
    run.counts["design_spans"] = _spans_of_setup("game.design")
    run.counts["layout_spans"] = _spans_of_setup("partition.entity_layout")
    with run.phase("warm_up"):
        one_job(run, train)
    run.spans.clear()
    return {"train": train, "rows": rows, "train_weight": train_weight,
            "jobs": [], "last": None}


def _reference_coordinates(run, state, params):
    rows = state["rows"]
    out = []
    for c in run.config["coordinates"]:
        part = {"kind": c["kind"], "x": rows["features"][c["shard"]],
                "params": params[c["name"]], "l2": float(c["l2"])}
        if c["kind"] == "random":
            part["ids"] = rows["entities"][c["entity"]]
            part["train_weight"] = state["train_weight"][c["name"]]
        out.append(part)
    return out


def compare(run, state, params, says, dtype=jnp.float32):
    """The numbers that decide ``correct`` for one fetched model and the
    objective the program reported for it; with a lower ``dtype`` the
    reference stands in the program's place (the control)."""
    labels = state["rows"]["labels"]
    at_model = _reference_coordinates(run, state, params)
    value, grads = reference_multi_blocked.value_grads(at_model, labels)
    if dtype != jnp.float32:
        low = reference_multi_blocked.value_grads(at_model, labels, dtype)
        says = dict(says, value=float(low[0]))
    at_zero = _reference_coordinates(
        run, state, {k: np.zeros_like(v) for k, v in params.items()})
    _, grads0 = reference_multi_blocked.value_grads(at_zero, labels)
    got = {"value_gap": reference.rel_gap(says["value"], value)}
    for c, g, g0 in zip(run.config["coordinates"], grads, grads0):
        got["grad_left_" + game_cd_multi._short(c["name"])] = float(
            jnp.linalg.norm(g.ravel()) / jnp.linalg.norm(g0.ravel()))
    return got


def control(state, run):
    return compare(run, state, state["jobs"][-1], state["program_says"],
                   jnp.bfloat16)


def _heldout_aucs(run, jobs):
    """Held-out AUC of every job's model, and of the last job's with each
    random effect's table zeroed in turn."""
    cfg = run.config
    held = datagen_music_blocked.music_rows_host(
        cfg, run.param, run.seed, run.size("heldout_rows"), "heldout")
    y, x, ids = held["labels"], held["features"], held["entities"]

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
