"""XLA compile counting and compile-path spans via ``jax.monitoring``.

Promoted out of ``serving/stats.py`` (which re-exports it) so TRAINING can
assert its own steady-state zero-recompile invariants the same way serving
proved PR 2's zero-recompile guarantee: the cached-solve path in
``models/training.py`` and the per-pass jit cache in ``game/descent.py``
are only provably recompile-free because something counts actual XLA
backend compiles — wall-clock regressions alone can't distinguish "slow"
from "recompiling".

Every observed compile also increments the default metrics registry's
``xla.compiles`` counter, so recompiles land in ``metrics.json`` without
any caller wiring.

A compile REQUEST is counted whether XLA compiled the program or jax's
persistent compilation cache answered it from disk;
:func:`xla_cache_hits` counts the latter (``xla.cache_hits``), so the
programs XLA actually compiled are the difference of the two.

The same listener writes the compile path as program spans
(``obs.add_span``: the flight ring always, the active tracer's export
when there is one), one record per jax step, retro-stamped from jax's
``time.time()`` window onto the ring's ``time.perf_counter()`` clock:

- ``xla.trace``: Python traced to a jaxpr. An inner ``jit`` traces inside
  the outer one's trace, so these records nest in time; they are never
  pushed on the span stack, so ``parent_id`` does not show that nesting.
- ``xla.lower``: the jaxpr lowered to a StableHLO module.
- ``xla.compile``: the backend compile, or the persistent-cache load
  where ``cache_hit`` (with ``retrieval_s``, the cache's read and load).

Each carries ``fun`` (jax's name of the function or module). jax calls
the listener on the compiling thread, so ``parent_id`` is the program
span that asked for the compile.
"""

from __future__ import annotations

import threading
import time

from photon_ml_tpu.obs import metrics as _metrics
from photon_ml_tpu.obs.trace import add_span as _add_span

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
# the cache's read, deserialise and load; fired on a hit only
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
# jax's compile-path time spans -> the program span each becomes
_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "xla.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "xla.lower",
    _COMPILE_EVENT: "xla.compile",
}

_compile_lock = threading.Lock()
_compile_events = 0
_cache_hits = 0
_listener_installed = False
# what the cache said of the compile running on this thread: the hit and
# retrieval events fire inside it, before its time span closes
_pending = threading.local()


def _on_event_duration(name: str, secs: float, **_kw) -> None:
    global _compile_events
    if name == _COMPILE_EVENT:
        with _compile_lock:
            _compile_events += 1
        _metrics.registry().inc("xla.compiles")
    elif name == _RETRIEVAL_EVENT:
        _pending.retrieval_s = secs


def _on_event(name: str, **_kw) -> None:
    global _cache_hits
    if name == _CACHE_HIT_EVENT:
        with _compile_lock:
            _cache_hits += 1
        _metrics.registry().inc("xla.cache_hits")
        _pending.cache_hit = True


def _on_time_span(name: str, start_time: float, end_time: float,
                  **kw) -> None:
    span = _SPANS.get(name)
    if span is None:
        return
    attrs = {"fun": str(kw.get("fun_name", ""))}
    if span == "xla.compile":
        attrs["cache_hit"] = getattr(_pending, "cache_hit", False)
        attrs["retrieval_s"] = getattr(_pending, "retrieval_s", 0.0)
        _pending.__dict__.clear()
    # jax stamps with time.time(); the ring keeps time.perf_counter()
    shift = time.perf_counter() - time.time()
    _add_span(span, start_time + shift, end_time + shift, cat="xla", **attrs)


def install_compile_listener() -> None:
    """Idempotently register the jax.monitoring listeners that feed
    :func:`xla_compile_events`, :func:`xla_cache_hits` and the
    compile-path spans. Listener registration is global and permanent
    in jax, so this installs exactly once per process."""
    global _listener_installed
    with _compile_lock:
        if _listener_installed:
            return
        _listener_installed = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_event_duration)
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_time_span_listener(_on_time_span)


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
