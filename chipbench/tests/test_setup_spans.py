"""``chipbench.setup_spans`` over a hand-made ring: set-up's records are
those that end before the window's first job, their union on each thread;
no partial sum where the ring dropped records or holds no such record."""

import time
from types import SimpleNamespace

import pytest

from chipbench import setup_spans
from photon_ml_tpu.obs import flight


def _ring(t0, capacity=None):
    flight.reset_spans(capacity=capacity)
    for name, a, b in (
        ("xla.trace", 1.0, 3.0), ("xla.trace", 2.0, 4.0),  # overlapping
        ("xla.lower", 4.0, 5.0),
        ("xla.trace", 9.0, 11.0),  # ends inside the window
    ):
        flight.note_span((name, t0 + a, t0 + b, 0, 0, 1, {}))
    return SimpleNamespace(spans=[("job", t0 + 10.0, t0 + 20.0)])


@pytest.mark.parametrize(
    "case,names,want",
    [
        ("before_the_window", ("xla.trace",), 3.0),
        ("two_names", ("xla.trace", "xla.lower"), 4.0),
        ("dropped", ("xla.trace",), None),
        ("no_record", ("xla.compile",), None),
    ],
)
def test_setup_seconds_over_a_hand_made_ring(case, names, want):
    run = _ring(time.perf_counter(), capacity=2 if case == "dropped" else None)
    try:
        got = setup_spans.setup_seconds(run, names)
    finally:
        flight.reset_spans()
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-9)
