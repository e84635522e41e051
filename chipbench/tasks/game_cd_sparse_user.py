"""Task ``game_cd_sparse_user``: back-to-back identical GAME
coordinate-descent runs through
``photon_ml_tpu.game.descent.CoordinateDescent.run`` over the
configuration's list of coordinates, in its order: a fixed effect, plain
random effects over dense features, and random effects over a SPARSE bag
through INDEX_MAP (``game.projected.IndexMapRandomEffectCoordinate``, built
by ``from_sparse_shard`` as ``cli/game_train.py`` builds it: each entity in
the compact columns of its active rows, each bucket at its own width, one
flat ragged table, TRON a lane).  Each run starts from zeros and ends with
every parameter set fetched to the host, a sparse coordinate's as its flat
table; its column map is the coordinate's static one.

The active samples, the window, the counted extra job and the planted
faults are ``game_cd_multi``'s; the reference (``reference_sparse_user``)
joins the rows' (user, original column, value) triples with the fetched
per-user lists.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

# at module top: a checkout without the ragged INDEX_MAP layout fails here,
# at once, before any data is made
from photon_ml_tpu.game.projected import IndexMapRandomEffectCoordinate

from chipbench import (
    datagen_music_hierarchy,
    program_spans,
    reference,
    reference_sparse_user,
    train_jobs,
    work_sparse_user,
)
from chipbench.tasks.game_cd_multi import _design_spans, active_sample


def _bag_shard(bag, width: int):
    """The (8, n) slot-major bag as the program's padded-ELL shard (n, 8),
    on the host."""
    from photon_ml_tpu.ops.sparse import SparseFeatures

    return SparseFeatures(
        indices=np.ascontiguousarray(np.asarray(bag["columns"]).T),
        values=np.ascontiguousarray(np.asarray(bag["values"]).T),
        d=width,
    )


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
    feats, bag = rows["features"], rows["bag"]
    ents, labels = rows["entities"], rows["labels"]
    n_all = labels.shape[0]
    if run.fault == "half_batch":  # the other half never reaches the program
        half = n_all // 2
        feats = {k: v[:half] for k, v in feats.items()}
        bag = {k: v[:, :half] for k, v in bag.items()}
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
    user_solve = cfg["user_solve"]
    per_entity = [c for c in cfg["coordinates"] if c["kind"] != "fixed"]
    width = run.size("song_id_space")
    with run.phase("bucketed_design_host"):
        shards = {}
        for c in per_entity:
            shards[c["shard"]] = (
                _bag_shard(bag, width) if c["kind"] == "sparse"
                else np.asarray(feats[c["shard"]]))
        data = GameData.create(
            features=shards,
            labels=np.asarray(labels),
            entity_ids={c["entity"]: np.asarray(ents[c["entity"]])
                        for c in per_entity},
        )
    coordinates, work, train_weight = {}, [], {}
    for c in cfg["coordinates"]:
        if c["kind"] == "fixed":
            x = feats[c["shard"]]
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
        entities = run.size(c["entities"])
        if c["kind"] == "sparse":
            # the design is the program's own host-side layout step, by the
            # constructor cli/game_train.py takes for a sparse shard
            with run.phase("bucketed_design_host"):
                coord = IndexMapRandomEffectCoordinate.from_sparse_shard(
                    data, c["entity"], c["shard"], entities,
                    CoordinateConfig(
                        shard=c["shard"], task=TaskType.LOGISTIC_REGRESSION,
                        optimizer=OptimizerType[user_solve["optimizer"]],
                        reg_weight=float(c["l2"]),
                        max_iters=int(user_solve["max_iters"]),
                        tolerance=float(user_solve["tolerance"]),
                        tron_max_cg=int(user_solve["max_cg"]),
                        random_effect=c["entity"],
                    ),
                    num_buckets=int(cfg["num_buckets"]),
                    active_cap=int(cfg["active_cap"]),
                )
            design = coord.design
            coordinates[c["name"]] = coord
        else:
            x = feats[c["shard"]]
            with run.phase("bucketed_design_host"):
                design = build_bucketed_random_effect_design(
                    data, c["entity"], c["shard"], entities,
                    num_buckets=int(cfg["num_buckets"]),
                    active_cap=int(cfg["active_cap"]),
                )
            coordinates[c["name"]] = RandomEffectCoordinate(
                design=design, row_features=x,
                row_entities=ents[c["entity"]], full_offsets_base=zeros,
                config=CoordinateConfig(
                    shard=c["shard"], reg_weight=float(c["l2"]),
                    random_effect=c["entity"], **common,
                ),
            )
        with run.phase("sample_check"):
            weight = active_sample(design, data.entity_ids[c["entity"]],
                                   int(cfg["active_cap"]), c["name"])
            train_weight[c["name"]] = np.concatenate(
                [weight, np.zeros(n_all - n, np.float32)])
        shape = {"name": c["name"], "kind": c["kind"],
                 "active_slots": int(design.active_slots),
                 "entities": sum(len(e) for e in design.entity_index)}
        if c["kind"] == "sparse":
            shape.update(_index_map_work(_index_map_spans()[-1][1]))
        else:
            shape["dim"] = int(feats[c["shard"]].shape[1])
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
    maps = {c["name"]: coordinates[c["name"]].design.index_map
            for c in per_entity if c["kind"] == "sparse"}
    iters = int(stop["cd_iterations"])
    return (lambda: cd.run(num_iterations=iters)), train_weight, maps


def _index_map_spans():
    """What the program's ``game.index_map`` spans of set-up said: a list
    of (seconds, attributes)."""
    got = program_spans.ring()
    if got is None:
        return []
    return [
        (r[program_spans.END] - r[program_spans.START],
         dict(r[program_spans.ATTRS]))
        for r in got[0] if r[program_spans.NAME] == "game.index_map"
    ]


def _index_map_work(attrs):
    """The shapes ``work_sparse_user`` counts, from a ``game.index_map``
    span: each bucket's lanes, width, held rows and stored slots; the
    table's size and the rows' stored entries (the rescore's)."""
    return {
        "lanes": attrs["lanes"], "widths": attrs["widths"],
        "rows_by_bucket": attrs["rows_by_bucket"],
        "stored_by_bucket": attrs["stored_by_bucket"],
        "table": attrs["padded_columns"],
        "row_slots_stored": attrs["row_slots_stored"],
    }


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
    with run.phase("data_on_device"):
        rows = datagen_music_hierarchy.music_rows(
            run.config, run.param, run.seed, run.size("train_rows"), "train")
        jax.block_until_ready(rows)
    train, train_weight, maps = _program(run, rows)
    # program_spans cuts the ring to the window; set-up's spans stay here
    run.counts["design_spans"] = _design_spans()
    run.counts["index_map_spans"] = _index_map_spans()
    with run.phase("warm_up"):
        one_job(run, train)
    run.spans.clear()
    return {"train": train, "rows": rows, "train_weight": train_weight,
            "maps": maps, "jobs": [], "last": None, "joined": {}}


def _reference_coordinates(run, state, params):
    """The reference's coordinates at ``params``: a sparse coordinate's
    flat table read as the per-user (user, column, value) lists of its
    static column map, joined with the rows once a run (the lists' pairs
    are the same for every model of a run)."""
    rows = state["rows"]
    width = run.size("song_id_space")
    out = []
    for c in run.config["coordinates"]:
        part = {"kind": c["kind"], "l2": float(c["l2"])}
        p = params[c["name"]]
        if c["kind"] == "sparse":
            ents, cols, vals = state["maps"][c["name"]].lists(p)
            if c["name"] not in state["joined"]:
                joined = reference_sparse_user.join(
                    rows["entities"][c["entity"]], rows["bag"]["columns"],
                    rows["bag"]["values"], state["train_weight"][c["name"]],
                    ents, cols, width)
                joined["entry_pair"] = jnp.asarray(joined["entry_pair"])
                state["joined"][c["name"]] = joined
            joined = state["joined"][c["name"]]
            part.update(
                entry_pair=joined["entry_pair"],
                values=rows["bag"]["values"],
                train_weight=jnp.asarray(state["train_weight"][c["name"]]),
                params=reference_sparse_user.coefficients(joined, vals),
                union_missing=joined["union_missing"],
            )
        else:
            part.update(x=rows["features"][c["shard"]], params=p)
            if c["kind"] == "random":
                part["ids"] = rows["entities"][c["entity"]]
                part["train_weight"] = jnp.asarray(
                    state["train_weight"][c["name"]])
        out.append(part)
    return out


def compare(run, state, params, says, dtype=jnp.float32):
    """The numbers that decide ``correct`` for one fetched model and the
    objective the program reported for it; with a lower ``dtype`` the
    reference stands in the program's place (the control).  Every gradient
    is read against the same coordinate's at zero parameters."""
    labels = state["rows"]["labels"]
    at_model = _reference_coordinates(run, state, params)
    value, grads, _ = reference_sparse_user.value_grads(at_model, labels)
    if dtype != jnp.float32:
        low = reference_sparse_user.value_grads(at_model, labels, dtype)
        says = dict(says, value=float(low[0]))
    at_zero = _reference_coordinates(
        run, state, {k: np.zeros_like(v) for k, v in params.items()})
    _, grads0, _ = reference_sparse_user.value_grads(at_zero, labels)
    got = {"value_gap": reference.rel_gap(says["value"], value)}
    for c, g, g0 in zip(run.config["coordinates"], grads, grads0):
        got["grad_left_" + c["name"].split("-")[-1]] = float(
            jnp.linalg.norm(g.ravel()) / jnp.linalg.norm(g0.ravel()))
    got["union_columns_missing"] = float(sum(
        part["union_missing"] for part in at_model
        if part["kind"] == "sparse"))
    return got


def control(state, run):
    return compare(run, state, state["jobs"][-1], state["program_says"],
                   jnp.bfloat16)


def _heldout_aucs(run, state, jobs):
    """Held-out AUC of every job's model, and of the last job's with each
    random effect zeroed in turn."""
    cfg = run.config
    width = run.size("song_id_space")
    held = datagen_music_hierarchy.music_rows(
        cfg, run.param, run.seed, run.size("heldout_rows"), "heldout")
    y = np.asarray(held["labels"])
    x = {k: np.asarray(v) for k, v in held["features"].items()}
    ids = {k: np.asarray(v) for k, v in held["entities"].items()}
    bag = {k: np.asarray(v) for k, v in held["bag"].items()}

    def score(c, p):
        if c["kind"] == "fixed":
            return x[c["shard"]] @ p
        if c["kind"] == "random":
            return np.sum(x[c["shard"]] * p[ids[c["entity"]]], axis=1)
        return reference_sparse_user.sparse_scores(
            ids[c["entity"]], bag["columns"], bag["values"],
            state["maps"][c["name"]].lists(p), width)

    def auc(model, without=None):
        z = np.zeros(y.shape, np.float64)
        for c in cfg["coordinates"]:
            if c["name"] != without:
                z += score(c, model[c["name"]])
        return reference.auc(y, z)

    without = {
        c["name"]: auc(jobs[-1], c["name"])
        for c in cfg["coordinates"] if c["kind"] != "fixed"
    }
    return [auc(job) for job in jobs], without


def check(state, run):
    lim = run.param("limits")  # of the model this size reaches
    says, jobs = state["program_says"], state["jobs"]
    last = jobs[-1]
    got = compare(run, state, last, says)
    jobs_gap = max(
        [max(reference.rel_l2(other[k], last[k]) for k in last)
         for other in jobs[:-1]] or [0.0]
    )
    aucs, without = _heldout_aucs(run, state, jobs)
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
    job = work_sparse_user.job(run.counts)
    run.counts["sparse_passes_per_job"] = job["sparse_passes"]
    passes = [inner[0]["sparse_re"]["passes"]
              for _, _, inner in says["solver_work"] if inner]
    print(f"heldout_auc: {min(aucs)!r} .. {max(aucs)!r}; with a table "
          f"zeroed: {without!r}; jobs_gap {jobs_gap!r}", file=sys.stderr)
    print(f"sparse_re passes by bucket an update: {passes!r}; a job "
          f"{job['sparse_passes']!r}", file=sys.stderr)
    for seconds, attrs in run.counts["index_map_spans"]:
        print(f"index_map ({seconds!r} s): {attrs!r}", file=sys.stderr)
    print("jobs_s:", [round(t1 - t0, 4) for name, t0, t1 in run.spans
                      if name == "job"], file=sys.stderr)
    for name in got:
        run.compared.append((name, got[name], float(lim[name])))
    run.compared.append(("auc_short", target - min(aucs), 0.0))
