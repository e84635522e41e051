"""The trace reduction: exact arithmetic on hand-made planes, and the shape
of the result on a small trace recorded on the chip (``small.xplane.pb``,
recorded by ``record_small_trace.py``)."""

import os
from types import SimpleNamespace as NS

import pytest

from chipbench import reduce_trace

HERE = os.path.dirname(os.path.abspath(__file__))


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def planes():
    ops = NS(name="XLA Ops", events=[
        ev("%while.9 = (f32[8]) while(...), body=%b", 100, 70),  # 100..170
        ev("%fusion.1 = f32[8]{0} fusion(f32[8] %p)", 100, 50),  # in the while
        ev("%fusion.2 = f32[8]{0} fusion(f32[8] %p)", 150, 15),  # in the while
        ev("%copy.1 = f32[8]{0} copy(f32[8] %p)", 300, 100),     # 300..400
        ev("%fusion.1 = f32[8]{0} fusion(f32[8] %p)", 600, 100),  # 600..700
    ])
    steps = NS(name="Steps", events=[ev("step", 0, 1000)])  # a summary line
    host = NS(name="python", events=[
        ev("chipbench.job", 100, 700),        # 100..800
        ev("chipbench.dispatch", 100, 250),   # 100..350: covers gap 170..300
        ev("chipbench.fetch_model", 690, 110),  # 690..800: covers gap 700..800
        ev("not_ours", 0, 5000),
    ])
    return [
        NS(name="/device:TPU:0", lines=[ops, steps]),
        NS(name="/host:CPU", lines=[host]),
    ]


def test_busy_window_ops_and_gaps_by_hand():
    r = reduce_trace.reduce_planes(
        planes(), ["job", "dispatch", "fetch_model"])
    assert r["window_s"] == pytest.approx(700e-9)      # 100..800
    assert r["busy_s"] == pytest.approx(270e-9)        # 70 + 100 + 100
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(150e-9)
    assert ops["fusion.2"] == pytest.approx(15e-9)
    assert ops["while.9"] == pytest.approx(5e-9)  # what its children leave
    assert ops["copy.1"] == pytest.approx(100e-9)
    assert sum(ops.values()) == pytest.approx(r["busy_s"])
    gaps = dict(r["idle_gaps"])
    assert gaps["dispatch"] == pytest.approx(130e-9)    # 170..300
    assert gaps["job"] == pytest.approx(200e-9)         # 400..600
    assert gaps["fetch_model"] == pytest.approx(100e-9)  # 700..800
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_no_device_plane_gives_nothing():
    assert reduce_trace.reduce_planes(planes()[1:], ["job"]) is None


def test_recorded_chip_trace():
    path = os.path.join(HERE, "small.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("no recorded trace beside the test")
    from jax.profiler import ProfileData

    r = reduce_trace.reduce_planes(
        ProfileData.from_file(path).planes, ["step"])
    assert r is not None and r["devices"] >= 1
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["device_ops"] and r["device_ops"][0][1] > 0
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
