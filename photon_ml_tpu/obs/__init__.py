"""Unified observability: structured tracing, metrics registry, profiling.

ONE instrument threaded through every layer (docs/OBSERVABILITY.md):

- :mod:`.trace`   — nestable thread-safe spans. Always on: every span is
  one record in the flight ring (:mod:`.flight`; ``obs.recent_spans()``)
  on the ``time.perf_counter()`` clock and, while open, a
  ``jax.profiler.TraceAnnotation``. Opt-in: a ``Tracer`` exporting the
  same records as Chrome trace-event JSON (Perfetto-loadable) + a
  structured JSONL event log.
- :mod:`.metrics` — named counters/gauges/histograms; JSON snapshots
  (``metrics.json``) and Prometheus text exposition (``cli/serve.py``).
- :mod:`.compile_events` — ``jax.monitoring`` backend-compile counter
  (promoted from ``serving/stats.py``), feeding ``xla.compiles``, and
  the compile-path spans ``xla.trace`` / ``xla.lower`` / ``xla.compile``.

Drivers enable all of it in one place::

    with obs.observe(trace_dir=..., metrics_path=..., metrics_every=30,
                     profile_dir=...):
        ...

which installs the tracer, starts a periodic registry dumper, and opens a
``jax.profiler`` capture window; everything tears down (final metrics
dump, trace export) on exit. Hot paths call ``obs.span(...)`` /
``obs.emit_event(...)`` / ``obs.registry()`` unconditionally: a span
costs a microsecond or two and never synchronises; an event without a
tracer costs one global read.
"""

from __future__ import annotations

import contextlib
import os
import threading
from typing import Optional

from photon_ml_tpu.obs import collectives
from photon_ml_tpu.obs import convergence
from photon_ml_tpu.obs import dist
from photon_ml_tpu.obs import exemplars
from photon_ml_tpu.obs import quality
from photon_ml_tpu.obs import reqtrace
from photon_ml_tpu.obs import sketches
from photon_ml_tpu.obs import taxonomy
from photon_ml_tpu.obs.exemplars import (
    ExemplarStore,
    install_store as install_exemplar_store,
    set_store as set_exemplar_store,
    store as exemplar_store,
)
from photon_ml_tpu.obs.reqtrace import (
    ensure_trace_id,
    new_trace_id,
    reconstruct_timeline,
    valid_trace_id,
)
from photon_ml_tpu.obs.convergence import (
    ConvergenceReport,
    ConvergenceTracker,
    FleetSummary,
    convergence_tracker,
    decode_result,
    fleet_summary,
    install_convergence_tracker,
    uninstall_convergence_tracker,
)
from photon_ml_tpu.obs.collectives import (
    collective_span,
    note_traced_collective,
    record_collective,
)
from photon_ml_tpu.obs.compile_events import (
    install_compile_listener,
    xla_cache_hits,
    xla_compile_events,
)
from photon_ml_tpu.obs.dispatch_count import (
    DispatchCounts,
    count_dispatches,
)
from photon_ml_tpu.obs.device import (
    HbmSampler,
    HbmWatermark,
    hbm_supported,
    hbm_watermark,
    read_memory_stats,
    sample_hbm,
)
from photon_ml_tpu.obs.metrics import (
    Counter,
    Gauge,
    LatencyHistogram,
    MetricsRegistry,
    registry,
    set_registry,
)
from photon_ml_tpu.obs.dist import (
    emit_clock_sync,
    host_metric_prefix,
    merge_trace_shards,
    process_identity,
    set_process_identity,
)
from photon_ml_tpu.obs.flight import (
    FlightRecorder,
    flight_dump,
    flight_recorder,
    install_flight_recorder,
    recent_spans,
    spans_dropped,
    uninstall_flight_recorder,
)
from photon_ml_tpu.obs.quality import (
    BaselineFingerprint,
    DriftMonitor,
    OnlineQuality,
    fingerprint_collector,
    install_fingerprint_collector,
    try_load_fingerprint,
    uninstall_fingerprint_collector,
)
from photon_ml_tpu.obs.sketches import (
    HistogramSketch,
    MomentSketch,
    TopKSketch,
)
from photon_ml_tpu.obs.trace import (
    Span,
    Tracer,
    add_span,
    current_span_context,
    emit_event,
    get_tracer,
    set_tracer,
    span,
    span_context,
    trace,
)
from photon_ml_tpu.obs.xla_cost import (
    CostBook,
    CostRecord,
    cost_book,
    count_collectives,
    set_cost_book,
)

__all__ = [
    "Counter",
    "Gauge",
    "LatencyHistogram",
    "MetricsRegistry",
    "registry",
    "set_registry",
    "Span",
    "Tracer",
    "emit_event",
    "get_tracer",
    "set_tracer",
    "span",
    "add_span",
    "recent_spans",
    "spans_dropped",
    "trace",
    "install_compile_listener",
    "xla_compile_events",
    "xla_cache_hits",
    "CostBook",
    "CostRecord",
    "cost_book",
    "count_collectives",
    "set_cost_book",
    "HbmSampler",
    "HbmWatermark",
    "hbm_supported",
    "hbm_watermark",
    "read_memory_stats",
    "sample_hbm",
    "MetricsDumper",
    "observe",
    # name taxonomy registry (obs.taxonomy; photon-lint PL006)
    "taxonomy",
    # distributed observability (obs.dist)
    "dist",
    "emit_clock_sync",
    "host_metric_prefix",
    "merge_trace_shards",
    "process_identity",
    "set_process_identity",
    # collective profiler (obs.collectives)
    "collectives",
    "collective_span",
    "note_traced_collective",
    "record_collective",
    # flight recorder (obs.flight)
    "FlightRecorder",
    "flight_dump",
    "flight_recorder",
    "install_flight_recorder",
    "uninstall_flight_recorder",
    # ambient span context
    "span_context",
    "current_span_context",
    # convergence-health layer (obs.convergence)
    "convergence",
    "ConvergenceReport",
    "ConvergenceTracker",
    "FleetSummary",
    "convergence_tracker",
    "decode_result",
    "fleet_summary",
    "install_convergence_tracker",
    "uninstall_convergence_tracker",
    # executable-dispatch counting (obs.dispatch_count)
    "DispatchCounts",
    "count_dispatches",
    # model/data-quality layer (obs.sketches, obs.quality)
    "sketches",
    "quality",
    "MomentSketch",
    "HistogramSketch",
    "TopKSketch",
    "BaselineFingerprint",
    "DriftMonitor",
    "OnlineQuality",
    "fingerprint_collector",
    "install_fingerprint_collector",
    "uninstall_fingerprint_collector",
    "try_load_fingerprint",
    # request-trace propagation + reconstruction (obs.reqtrace)
    "reqtrace",
    "ensure_trace_id",
    "new_trace_id",
    "reconstruct_timeline",
    "valid_trace_id",
    # tail-based exemplar sampling (obs.exemplars)
    "exemplars",
    "ExemplarStore",
    "exemplar_store",
    "install_exemplar_store",
    "set_exemplar_store",
]


class MetricsDumper:
    """Background thread writing periodic registry snapshots to a JSON
    file (the ``--metrics-every`` surface). Daemonized and event-driven so
    ``stop()`` returns promptly instead of waiting out the interval; a
    final dump on stop means the file always reflects the completed run.
    """

    def __init__(
        self,
        path: str,
        every_s: float,
        reg: Optional[MetricsRegistry] = None,
    ):
        self.path = path
        self.every_s = every_s
        self._registry = reg if reg is not None else registry()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def _run(self) -> None:
        while not self._stop.wait(self.every_s):
            try:
                self._registry.dump(self.path)
            except OSError:
                pass  # a full disk must not kill the training loop

    def start(self) -> "MetricsDumper":
        if self.every_s > 0 and self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name="obs-metrics-dumper", daemon=True
            )
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        self._registry.dump(self.path)


@contextlib.contextmanager
def observe(
    trace_dir: Optional[str] = None,
    metrics_path: Optional[str] = None,
    metrics_every: float = 0.0,
    profile_dir: Optional[str] = None,
    hbm_every_s: float = 0.5,
    process_name: str = "photon_ml_tpu",
    flight_dir: Optional[str] = None,
    flight_records: int = 2048,
):
    """Driver-level enable-everything context.

    - ``trace_dir``: install the span tracer; ``trace.json`` +
      ``events.jsonl`` land there on exit. Also installs the compile
      listener so recompiles show up in the timeline and registry, and —
      on platforms whose devices report ``memory_stats()`` — a live HBM
      sampler emitting counter tracks every ``hbm_every_s`` seconds
      (0 disables; unsupported platforms cost one probe). A
      ``clock.sync`` event anchors the shard for pod-level merging
      (``photon-obs merge``; obs.dist).
    - ``metrics_path`` (+ ``metrics_every`` seconds): periodic default-
      registry snapshots; a final snapshot is always written on exit.
      With only ``trace_dir`` set, ``metrics.json`` defaults into it.
    - ``profile_dir``: a ``jax.profiler`` capture window around the block
      (TensorBoard/Perfetto-loadable device profile — the deep tool under
      the span timeline).
    - ``flight_dir``/``flight_records``: install a crash flight recorder
      (obs.flight) holding the last ``flight_records`` observations;
      ``flight-<reason>.json`` dumps land in ``flight_dir`` (default:
      ``trace_dir``). Spans are in the always-on flight ring either
      way; with ``flight_dir`` set but no ``trace_dir``, a ring-only
      tracer is installed so that instant events feed the recorder too,
      without accumulating an unbounded trace. ``flight_records=0``
      disables.

    All-None is a no-op: drivers wrap their body unconditionally and let
    flags decide.
    """
    if metrics_path is None and trace_dir is not None:
        metrics_path = os.path.join(trace_dir, "metrics.json")
    dumper = None
    hbm = None
    flight = None
    installed_tracer = False
    with contextlib.ExitStack() as stack:
        if trace_dir is not None:
            install_compile_listener()
            stack.enter_context(trace(trace_dir, process_name=process_name))
            hbm = HbmSampler(hbm_every_s).start()
            installed_tracer = True
        elif flight_dir is not None and flight_records > 0:
            # ring-only tracer: events route to the flight recorder,
            # nothing accumulates, nothing is written unless a dump
            # fires
            ring_tracer = Tracer(None, process_name=process_name,
                                 keep_events=False)
            prev = set_tracer(ring_tracer)
            stack.callback(set_tracer, prev)
            installed_tracer = True
        if (trace_dir is not None or flight_dir is not None) and (
            flight_records > 0
        ):
            flight = install_flight_recorder(
                capacity=flight_records,
                flight_dir=flight_dir if flight_dir is not None else trace_dir,
            )
            stack.callback(uninstall_flight_recorder)
        if installed_tracer:
            # anchor this shard for pod-trace merging (barrier-backed
            # sync is emitted by parallel.multihost when a pod joins)
            emit_clock_sync(sync_id="observe-start")
        if profile_dir is not None:
            import jax

            os.makedirs(profile_dir, exist_ok=True)
            stack.enter_context(jax.profiler.trace(profile_dir))
        if metrics_path is not None:
            os.makedirs(
                os.path.dirname(os.path.abspath(metrics_path)), exist_ok=True
            )
            dumper = MetricsDumper(metrics_path, metrics_every).start()
        try:
            yield
        except BaseException as e:
            # the envelope unwinds BEFORE sys.excepthook runs, so the
            # crash hook would fire with the recorder already
            # uninstalled — dump here, while the ring still holds the
            # spans leading into the crash. GeneratorExit and
            # SystemExit are deliberate exits, not crashes (signal
            # paths dump "preemption" from the GracefulShutdown
            # handler while the recorder is still installed)
            if flight is not None and not isinstance(
                e, (GeneratorExit, SystemExit)
            ):
                try:
                    flight.note(
                        {
                            "kind": "event",
                            "name": "crash",
                            "exception": f"{type(e).__name__}: {e}",
                        }
                    )
                    flight.dump("crash")
                except Exception:
                    pass
            raise
        finally:
            if flight is not None:
                flight.sample_metrics()
            if hbm is not None:
                hbm.stop()
            if dumper is not None:
                dumper.stop()
