"""XLA backend-compile counting via ``jax.monitoring``.

Promoted out of ``serving/stats.py`` (which re-exports it) so TRAINING can
assert its own steady-state zero-recompile invariants the same way serving
proved PR 2's zero-recompile guarantee: the cached-solve path in
``models/training.py`` and the per-pass jit cache in ``game/descent.py``
are only provably recompile-free because something counts actual XLA
backend compiles — wall-clock regressions alone can't distinguish "slow"
from "recompiling".

Every observed compile also increments the default metrics registry's
``xla.compiles`` counter and emits a ``xla.compile`` instant event on the
active tracer, so recompiles land in ``metrics.json`` and in the Perfetto
timeline without any caller wiring.

A compile REQUEST is counted whether XLA compiled the program or jax's
persistent compilation cache answered it from disk;
:func:`xla_cache_hits` counts the latter (``xla.cache_hits``), so the
programs XLA actually compiled are the difference of the two.
"""

from __future__ import annotations

import threading

from photon_ml_tpu.obs import metrics as _metrics
from photon_ml_tpu.obs import trace as _trace

__all__ = [
    "install_compile_listener",
    "xla_compile_events",
    "xla_cache_hits",
]

# every executable a jit builds fires this duration event exactly once,
# persistent-cache hit or not; tracing-only events are deliberately
# excluded — a jit-cache-hit retrace that does not reach the compiler
# costs microseconds, a backend compile costs seconds
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# fired when the persistent cache answers a compile request from disk
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_compile_lock = threading.Lock()
_compile_events = 0
_cache_hits = 0
_listener_installed = False


def _on_event_duration(name: str, secs: float, **_kw) -> None:
    global _compile_events
    if name == _COMPILE_EVENT:
        with _compile_lock:
            _compile_events += 1
        _metrics.registry().inc("xla.compiles")
        _trace.emit_event(
            "xla.compile", cat="xla", duration_ms=round(secs * 1e3, 3)
        )


def _on_event(name: str, **_kw) -> None:
    global _cache_hits
    if name == _CACHE_HIT_EVENT:
        with _compile_lock:
            _cache_hits += 1
        _metrics.registry().inc("xla.cache_hits")


def install_compile_listener() -> None:
    """Idempotently register the jax.monitoring listeners that feed
    :func:`xla_compile_events` and :func:`xla_cache_hits`. Listener
    registration is global and permanent in jax, so this installs
    exactly once per process."""
    global _listener_installed
    with _compile_lock:
        if _listener_installed:
            return
        _listener_installed = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_event_duration)
    jax.monitoring.register_event_listener(_on_event)


def xla_compile_events() -> int:
    """Process-wide count of compile requests (backend compiles plus
    persistent-cache loads) observed since
    :func:`install_compile_listener` — the ground truth any per-instance
    ``compile_count`` is cross-checked against in tests."""
    with _compile_lock:
        return _compile_events


def xla_cache_hits() -> int:
    """How many of :func:`xla_compile_events` jax's persistent
    compilation cache answered from disk."""
    with _compile_lock:
        return _cache_hits
