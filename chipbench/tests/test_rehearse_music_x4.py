"""The ``game_music_2re_x4.cd`` cell on the CPU (``--rehearse`` on 4 virtual
devices: rows and entities from the configuration's ``rehearse``, widths, the
active cap and the four shards kept): the last line says ``correct: true``,
each of the three faults says ``correct: false``, the control reads beyond a
limit; the blocked reference against ``reference_multi`` on one block's worth
of rows; the blocked generator's entity counts over seeds; ``work_sharded``
against ``work_multi`` plus the exchange term."""

import json
import os

import numpy as np
import pytest

from chipbench import work_multi, work_sharded
from conftest import ROOT

CELL = "game_music_2re_x4.cd"
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def config():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "game_music_2re_x4.json")) as f:
        return json.load(f)


@pytest.fixture(autouse=True)
def four_virtual_devices(monkeypatch):
    monkeypatch.setenv("JAX_NUM_CPU_DEVICES", "4")


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_last_line(rehearsal, trace):
    result, proc = rehearsal(CELL, trace=trace)
    assert KEYS <= set(result)
    assert list(result)[-1] == "compared"
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == 4
    assert result["correct"] is True and result["attempted"] > 0
    assert result["failed"] == 0
    assert set(result["compared"]) == {
        "value_gap", "grad_left_fixed", "grad_left_user", "grad_left_song",
        "auc_short"}
    # counts only: no time, rate, share or memory reading from a CPU
    for name in result["metrics"]:
        assert name.split(".")[0] in ("solver", "dispatch", "compile"), name
    if trace:
        cfg = config()
        assert result["metrics"]["solver.evals_per_job"]["value"] == (
            cfg["stopping_rule"]["cd_iterations"] * len(cfg["coordinates"]))
    assert "busy_s" not in result["device"]
    for phase in ("data_on_host", "entity_layout_host",
                  "bucketed_design_host", "sample_check", "shard_design"):
        assert phase in result["phases_s"], phase


def test_control_fails_a_number(rehearsal):
    result, _ = rehearsal(CELL, "--control")
    assert result["correct"] is True
    limits = {k: v["limit"] for k, v in result["compared"].items()}
    beyond = [k for k, v in result["control"].items() if not v <= limits[k]]
    assert beyond, (result["control"], limits)


@pytest.mark.parametrize(
    "fault", ["state_unchanged", "half_batch", "answer_altered"])
def test_fault_is_not_correct(rehearsal, fault):
    result, _ = rehearsal(CELL, "--fault", fault)
    assert result["correct"] is False
    beyond = [k for k, v in result["compared"].items()
              if not v["value"] <= v["limit"]]
    assert beyond


def _rows(seed, part="train"):
    from chipbench import datagen_music_blocked

    cfg = config()
    param = lambda key: cfg["rehearse"].get(key, cfg.get(key))
    return cfg, datagen_music_blocked.music_rows_host(
        cfg, param, seed, param("train_rows"), part)


def test_blocked_reference_equals_reference_multi():
    import jax.numpy as jnp

    from chipbench import reference_multi, reference_multi_blocked

    cfg, rows = _rows(2**31 + 5)
    rng = np.random.default_rng(3)
    n = rows["labels"].shape[0]
    coordinates = []
    for c in cfg["coordinates"]:
        x = rows["features"][c["shard"]]
        part = {"kind": c["kind"], "x": x, "l2": float(c["l2"])}
        if c["kind"] == "fixed":
            part["params"] = rng.normal(size=x.shape[1]).astype(np.float32)
        else:
            part["ids"] = rows["entities"][c["entity"]]
            part["train_weight"] = rng.choice(
                [0.0, 1.0, 2.5], size=n).astype(np.float32)
            part["params"] = 0.1 * rng.normal(
                size=(cfg["rehearse"][c["entities"]], x.shape[1])
            ).astype(np.float32)
        coordinates.append(part)
    on_device = [
        {k: (jnp.asarray(v) if isinstance(v, np.ndarray) else v)
         for k, v in c.items()} for c in coordinates]
    want_value, want_grads, _ = reference_multi.value_grads(
        on_device, jnp.asarray(rows["labels"]))
    # one block's worth of rows: the same sums in the same order
    value, grads = reference_multi_blocked.value_grads(
        coordinates, rows["labels"])
    assert float(value) == float(want_value)
    for g, w in zip(grads, want_grads):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # several blocks: the same numbers up to float32 summation order
    value, grads = reference_multi_blocked.value_grads(
        coordinates, rows["labels"], block=4096)
    assert abs(float(value) - float(want_value)) <= 1e-6 * float(want_value)
    for g, w in zip(grads, want_grads):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=0,
            atol=1e-5 * float(np.abs(np.asarray(w)).max()))
    low = reference_multi_blocked.value_grads(
        coordinates, rows["labels"], jnp.bfloat16)
    assert abs(float(low[0]) - float(want_value)) > 1e-4 * float(want_value)


def test_every_seed_gets_the_same_entity_counts():
    counts = []
    for seed in (1, 2**31 + 11, 2**32 + 7):
        cfg, rows = _rows(seed)
        counts.append({
            name: np.sort(np.bincount(ids))[::-1]
            for name, ids in rows["entities"].items()
        })
        assert rows["labels"].shape == (cfg["rehearse"]["train_rows"],)
        assert {k: v.shape[1] for k, v in rows["features"].items()} == {
            "global": cfg["fixed_dim"], "per_user": cfg["user_dim"],
            "per_song": cfg["song_dim"]}
    for other in counts[1:]:
        for name in counts[0]:
            np.testing.assert_array_equal(other[name], counts[0][name])
    # no two seeds give the same arrays
    assert not np.array_equal(_rows(1)[1]["labels"], _rows(2)[1]["labels"])
    # both tables hold an entity past the cap
    cap = config()["active_cap"]
    assert all(c[0] > cap for c in counts[0].values())


def test_work_sharded_is_work_multi_plus_the_exchange():
    counts = {
        "rows": 10,
        "coordinates_work": [
            {"name": "fixed", "kind": "fixed", "dim": 4, "active_slots": 0,
             "entities": 0},
            {"name": "per-user", "kind": "random", "dim": 2,
             "active_slots": 6, "entities": 3, "exchange_rows": 0},
            {"name": "per-song", "kind": "random", "dim": 2,
             "active_slots": 8, "entities": 5, "exchange_rows": 10},
        ],
        "solver_iterations": [("fixed", 2.0), ("per-user", 2.0),
                              ("per-song", 2.0)] * 3,
    }
    base, got = work_multi.job(counts), work_sharded.job(counts)
    # two exchanges an update of the exchanging coordinate; an exchanged
    # row: an index and an element read, an element written, on each side
    one_update = 2 * (2 * 10 * (4 + 2 * 4))
    assert got["exchange_bytes"] == 3 * one_update
    assert got["flops"] == base["flops"]
    assert got["bytes"] == base["bytes"] + 3 * one_update
    assert got["by_coordinate"]["per-song"]["bytes"] == (
        base["by_coordinate"]["per-song"]["bytes"] + 3 * one_update)
    for name in ("fixed", "per-user"):
        assert got["by_coordinate"][name] == base["by_coordinate"][name]
    assert work_sharded.job({"rows": 10}) is None
