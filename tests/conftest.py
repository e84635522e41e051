"""Test harness: force an 8-device virtual CPU "pod".

The reference fakes a cluster with local-mode Spark
(``photon-test/.../SparkTestUtils.scala:31-75``, local[4]). Our analog is
jax's ``jax_num_cpu_devices``: every test sees 8 CPU "chips" so the full
mesh/sharding/collective path is exercised without TPU hardware. The
suite is a CPU suite (float64 oracles), so the platform is pinned too —
both are config updates, valid any time before first backend use.
``PHOTON_TEST_PLATFORMS=tpu,cpu`` leaves the chip to the chip-only tests
(``-k on_the_chip``), which skip on any other backend.
"""

import os

import jax

jax.config.update("jax_platforms",
                  os.environ.get("PHOTON_TEST_PLATFORMS", "cpu"))
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_x64", True)

import gc

import numpy as np
import pytest

# jax tracing allocates millions of short-lived objects: at the default
# gen-0 threshold (700) the collector runs constantly, and every full
# collection walks whatever the suite has accumulated so far.
gc.set_threshold(100_000, 50, 100)


@pytest.fixture(scope="module", autouse=True)
def _bound_process_state():
    """Keep one long pytest process from slowing itself down: jax's
    trace / executable caches grow with every test, and by the later
    modules the same test ran ~1.7x slower than in isolation (tier-1 was
    at 1026 s of its 870 s budget). After each module drop jax's caches,
    collect once, and freeze the survivors out of future collections."""
    yield
    jax.clear_caches()
    gc.collect()
    gc.freeze()


@pytest.fixture(autouse=True)
def _fresh_span_ring():
    """The flight ring of span records is process-global and always on:
    each test starts with it empty, at its default capacity."""
    from photon_ml_tpu.obs import flight

    flight.reset_spans()
    yield


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture
def rng():
    return np.random.default_rng(20260729)


@pytest.fixture
def dispatch_counter():
    """THE dispatch-count assertion helper (like the serving suite's
    zero-recompile drill, but for executions): wraps executable-call
    counting (``obs.dispatch_count``) so tests can prove one-dispatch
    guarantees::

        with dispatch_counter() as dc:
            train_glm(batch, cfg)          # N-lambda path
        dc.assert_program("solve_path", 1)

    Counting never forces a recompile — the zero-recompile invariants
    stay assertable inside a counted block."""
    from photon_ml_tpu.obs.dispatch_count import count_dispatches

    return count_dispatches
