"""Program spans: one primitive, kept in the flight ring, mirrored into the
profiler's trace, exported on request.

The reference's only timing instrument is ``Driver.scala:124-149`` — ad-hoc
elapsed-millis log lines per phase. That tells you *that* a GAME pass took
9 seconds, never *where* they went (solver iterations vs recompiles vs
host<->device transfer). :func:`span` is the process-wide replacement.

What a span does, always (no switch):

1. **One record in the flight ring** (``obs.flight``) on exit:
   ``(name, start, end, span_id, parent_id, thread, attrs)``, ``start`` and
   ``end`` from ``time.perf_counter()``, ``parent_id`` the innermost span
   open on the same thread. A reader (``obs.recent_spans()``) cuts the ring
   to any window it holds ``perf_counter`` stamps for. A retro-stamped
   form, :func:`add_span`, writes the same record for work whose stamps
   are known only afterwards (a request's life, read off its future).
2. **One ``jax.profiler.TraceAnnotation``** held while the span is open,
   when ``jax`` is already imported. Outside a profiler session that is a
   TraceMe that records nothing; inside ``jax.profiler.trace(...)`` the
   program's spans land in the ``.xplane.pb`` host plane on the clock of
   the device events.
3. **Never synchronises.** ``span(...).sync(arrays)`` is for callers that
   ask for ``jax.block_until_ready`` and want the blocked time on the span.

What is opt-in: a :class:`Tracer` (``obs.trace(dir)`` /
``obs.observe(trace_dir=...)``) exports the same records as Chrome
trace-event JSON (Perfetto / ``chrome://tracing``) and a structured JSONL
event log next to the run's ``log-message.txt``, and carries instant
events and counter tracks, which exist only under a tracer.

Constraints, in priority order:

1. **Cheap.** Training hot loops and the serving worker call :func:`span`
   unconditionally: a span costs one small object, two clock reads, one
   TraceMe and one ``deque.append`` — no lock, nothing serialized.
2. **Thread-safe.** The serving micro-batcher and stats flushers span from
   worker threads; records carry the recording thread id, exported events
   append under the tracer's lock.
3. **No jax dependency.** Importable from CPU-only subprocesses (bench
   baselines) and before backend selection; jax is looked up in
   ``sys.modules``, never imported.

Usage::

    from photon_ml_tpu import obs

    with obs.span("train", combo=0):        # nestable, thread-safe
        ...
    obs.recent_spans()                      # -> [(name, start, end, ...)]

    with obs.trace("out/trace"):            # install an exporter
        with obs.span("train", combo=0):
            ...
        obs.emit_event("retry", label="read part-0.avro", attempt=2)
    # -> out/trace/trace.json (Perfetto) + out/trace/events.jsonl
"""

from __future__ import annotations

import atexit
import contextlib
import io
import itertools
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from photon_ml_tpu.obs import flight as _flight

__all__ = [
    "Span",
    "Tracer",
    "trace",
    "span",
    "add_span",
    "span_context",
    "current_span_context",
    "emit_event",
    "get_tracer",
    "set_tracer",
]

EVENTS_FILENAME = "events.jsonl"
TRACE_FILENAME = "trace.json"


class Tracer:
    """Collects trace events and streams them to a JSONL log.

    ``_FLUSH_EVERY`` bounds the unflushed-span window (see
    :meth:`_log_jsonl`).

    Timestamps are microseconds since the tracer's epoch
    (``perf_counter_ns`` based — monotonic, immune to wall-clock steps),
    which is what the Chrome trace-event format's ``ts`` field wants.
    ``export()`` writes the accumulated events, sorted by ``ts``, as a
    ``{"traceEvents": [...]}`` document loadable in Perfetto.
    """

    _FLUSH_EVERY = 64

    def __init__(
        self,
        trace_dir: Optional[str] = None,
        process_name: str = "photon_ml_tpu",
        keep_events: bool = True,
    ):
        self._lock = threading.Lock()
        self._events: List[Dict[str, Any]] = []
        # ring-only mode (keep_events=False): route spans/events to the
        # flight recorder and JSONL without accumulating the in-memory
        # trace — the long-lived-process shape (obs.observe's
        # flight-without-trace envelope) where an unbounded event list
        # would be a leak
        self._keep_events = keep_events
        self._epoch_ns = time.perf_counter_ns()
        self._epoch_s = self._epoch_ns / 1e9  # the same instant, as a stamp
        self._epoch_unix = time.time()
        # flight-recorder hook: a FlightRecorder (obs.flight) notes every
        # instant/counter record into its bounded ring (spans are in the
        # flight ring whether or not a tracer is installed)
        self.recorder = None
        # pod identity (obs.dist): in a multi-process run the Chrome pid
        # IS the process index — per-host events land on distinct
        # Perfetto pid tracks and merge without rewriting
        from photon_ml_tpu.obs import dist as _dist

        self.process_index, self.process_count = _dist.process_identity()
        if self.process_count > 1:
            self._pid = self.process_index
            process_name = f"{process_name} host.{self.process_index}"
        else:
            self._pid = os.getpid()
        self.trace_dir = trace_dir
        self._jsonl: Optional[io.TextIOBase] = None
        self._jsonl_pending = 0
        self._atexit_registered = False
        if trace_dir is not None:
            os.makedirs(trace_dir, exist_ok=True)
            self._jsonl = open(
                os.path.join(trace_dir, EVENTS_FILENAME),
                "a",
                encoding="utf-8",
            )
            # clean-exit guard: a tracer installed WITHOUT the trace()
            # context manager (drivers that set_tracer directly, or a
            # process that exits mid-envelope) still flushes its
            # buffered span records and exports the trace — the
            # up-to-63-spans flush loss-window otherwise
            atexit.register(self._atexit_close)
            self._atexit_registered = True
        # process metadata events (name + stable ordering in Perfetto)
        self._events.append(
            {
                "ph": "M",
                "name": "process_name",
                "pid": self._pid,
                "tid": 0,
                "ts": 0,
                "args": {"name": process_name},
            }
        )
        if self.process_count > 1:
            self._events.append(
                {
                    "ph": "M",
                    "name": "process_sort_index",
                    "pid": self._pid,
                    "tid": 0,
                    "ts": 0,
                    "args": {"sort_index": self.process_index},
                }
            )

    # -- clock --------------------------------------------------------------

    def now_us(self) -> float:
        """Microseconds since this tracer's epoch (monotonic)."""
        return (time.perf_counter_ns() - self._epoch_ns) / 1e3

    def us_of(self, stamp_s: float) -> float:
        """A ``time.perf_counter()`` stamp on this tracer's clock."""
        return (stamp_s - self._epoch_s) * 1e6

    def _wall(self, ts_us: float) -> float:
        """Unix seconds for a tracer timestamp (JSONL human anchor)."""
        return self._epoch_unix + ts_us / 1e6

    # -- recording ----------------------------------------------------------

    def _log_jsonl(
        self, record: Dict[str, Any], flush: bool = False, note: bool = True
    ) -> None:
        """Append one JSONL record. Span records are flushed every
        ``_FLUSH_EVERY`` writes (a crash loses at most a handful of
        timing lines — the flight ring covers that window);
        instant events — faults, retries, preemptions — flush
        immediately, since they exist to survive the crash that
        follows them. ``note``: also into the flight recorder's ring
        (not for spans: the flight ring has them already)."""
        rec = self.recorder
        if note and rec is not None:
            rec.note(record)
        if self._jsonl is None or self._jsonl.closed:
            return
        if self.process_count > 1:
            record = {"host": self.process_index, **record}
        self._jsonl.write(json.dumps(record, sort_keys=True) + "\n")
        self._jsonl_pending += 1
        if flush or self._jsonl_pending >= self._FLUSH_EVERY:
            self._jsonl.flush()
            self._jsonl_pending = 0

    def add_span(
        self,
        name: str,
        ts_us: float,
        dur_us: float,
        cat: str = "app",
        tid: Optional[int] = None,
        args: Optional[Dict[str, Any]] = None,
    ) -> None:
        """Record a span with an explicit window on this tracer's clock
        (``now_us``) — the retro-stamped form for callers that hold a
        tracer. Writes the same flight-ring record as :func:`add_span`
        and exports it through this tracer."""
        start_s = self._epoch_s + ts_us / 1e6
        _record(
            name, start_s, start_s + max(dur_us, 0.0) / 1e6, cat, tid,
            args if args is not None else {}, self,
        )

    def _export_span(
        self,
        name: str,
        start_s: float,
        end_s: float,
        cat: str,
        tid: Optional[int],
        args: Dict[str, Any],
    ) -> None:
        """One flight-ring record as a complete ('X') Chrome event and a
        JSONL line."""
        ts_us = self.us_of(start_s)
        dur_us = (end_s - start_s) * 1e6
        ev = {
            "ph": "X",
            "name": name,
            "cat": cat,
            "pid": self._pid,
            "tid": tid if tid is not None else threading.get_ident(),
            "ts": round(ts_us, 3),
            "dur": round(max(dur_us, 0.0), 3),
            "args": args,
        }
        with self._lock:
            if self._keep_events:
                self._events.append(ev)
            self._log_jsonl(
                {
                    "kind": "span",
                    "name": name,
                    "cat": cat,
                    "time_unix": round(self._wall(ts_us), 6),
                    "duration_ms": round(max(dur_us, 0.0) / 1e3, 6),
                    **args,
                },
                note=False,
            )

    def add_instant(
        self,
        name: str,
        cat: str = "event",
        args: Optional[Dict[str, Any]] = None,
        flush: bool = True,
    ) -> None:
        """Instant events flush the JSONL immediately by default — they
        exist to survive the crash that follows them. Periodic telemetry
        instants (per-pass convergence summaries) pass ``flush=False``
        and ride the batched span flush instead."""
        ts = self.now_us()
        ev = {
            "ph": "i",
            "s": "t",  # thread-scoped instant
            "name": name,
            "cat": cat,
            "pid": self._pid,
            "tid": threading.get_ident(),
            "ts": round(ts, 3),
            "args": args or {},
        }
        with self._lock:
            if self._keep_events:
                self._events.append(ev)
            self._log_jsonl(
                {
                    "kind": "event",
                    "name": name,
                    "cat": cat,
                    "time_unix": round(self._wall(ts), 6),
                    **(args or {}),
                },
                flush=flush,
            )

    def add_counter(
        self,
        name: str,
        values: Dict[str, float],
        ts_us: Optional[float] = None,
    ) -> None:
        """Record a Chrome counter-track sample ('C' event): Perfetto
        renders successive samples of the same ``name`` as a stacked
        area graph under the timeline — the HBM telemetry surface
        (``obs.device``). ``ts_us`` retro-stamps the sample (the
        convergence layer replays a solve's tape across the solve's
        span window; the iterations happened inside one dispatch, so
        their timestamps are only known after it returns). Samples are
        periodic and bulky, so the JSONL mirror rides the batched span
        flush, not the instant-event immediate flush."""
        ev = {
            "ph": "C",
            "name": name,
            "cat": "counter",
            "pid": self._pid,
            "tid": 0,
            "ts": round(self.now_us() if ts_us is None else ts_us, 3),
            "args": dict(values),
        }
        with self._lock:
            if self._keep_events:
                self._events.append(ev)
            self._log_jsonl(
                {
                    "kind": "counter",
                    "name": name,
                    "time_unix": round(self._wall(ev["ts"]), 6),
                    **values,
                }
            )

    # -- readout ------------------------------------------------------------

    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def export(self, path: Optional[str] = None) -> Optional[str]:
        """Write the Chrome trace-event JSON (sorted by ``ts`` so readers
        that assume emission order see monotone timestamps). Returns the
        path written, or None when there is nowhere to write."""
        if path is None:
            if self.trace_dir is None:
                return None
            path = os.path.join(self.trace_dir, TRACE_FILENAME)
        with self._lock:
            events = sorted(self._events, key=lambda e: (e["ts"], -e.get("dur", 0)))
        doc = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "metadata": {
                "epoch_unix": self._epoch_unix,
                "process_index": self.process_index,
                "process_count": self.process_count,
            },
        }
        with open(path, "w", encoding="utf-8") as f:
            json.dump(doc, f)
        return path

    def flush(self) -> None:
        """Force the buffered JSONL span records to disk. Called from
        shutdown paths (``GracefulShutdown``) so a graceful exit never
        loses the up-to-``_FLUSH_EVERY - 1`` buffered records."""
        with self._lock:
            if self._jsonl is not None and not self._jsonl.closed:
                self._jsonl.flush()
                self._jsonl_pending = 0

    def close(self) -> None:
        if self._atexit_registered:
            self._atexit_registered = False
            try:
                atexit.unregister(self._atexit_close)
            except Exception:
                pass
        if self._jsonl is not None and not self._jsonl.closed:
            self._jsonl.close()  # implicit flush of any buffered records

    def _atexit_close(self) -> None:
        """Clean-exit fallback for tracers never close()d: export the
        trace document (the context manager normally does this) and
        flush/close the JSONL log."""
        try:
            self.export()
        except Exception:
            pass
        self.close()


# ---------------------------------------------------------------------------
# Active-tracer plumbing
# ---------------------------------------------------------------------------

# ONE process-global active tracer (like logging's root logger): training,
# serving, and resilience all emit into the same timeline, which is the
# point of a *unified* instrument. Deliberately not thread-local — worker
# threads must land on the main timeline.
_active: Optional[Tracer] = None
_install_lock = threading.Lock()


def get_tracer() -> Optional[Tracer]:
    return _active


def set_tracer(tracer: Optional[Tracer]) -> Optional[Tracer]:
    """Install ``tracer`` as the process-wide destination (None disables).
    Returns the previous tracer so callers can restore it."""
    global _active
    with _install_lock:
        prev = _active
        _active = tracer
    return prev


@contextlib.contextmanager
def trace(trace_dir: Optional[str], process_name: str = "photon_ml_tpu"):
    """Install a :class:`Tracer` writing under ``trace_dir`` for the
    block; export ``trace.json`` and close the JSONL log on exit. With
    ``trace_dir=None`` the block runs untraced (flag-plumbing
    convenience: ``with trace(args.trace_dir): ...``)."""
    if trace_dir is None:
        yield None
        return
    tracer = Tracer(trace_dir, process_name=process_name)
    prev = set_tracer(tracer)
    try:
        yield tracer
    finally:
        set_tracer(prev)
        tracer.export()
        tracer.close()


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------

# span ids: one process-wide counter (``next`` on it is atomic)
_ids = itertools.count(1)

# Per-thread stack of ``(span_id, ambient fields)``: the innermost open
# span (a record's ``parent_id``) and the request-scoped attributes
# (trace/request/batch ids) that cross API seams without threading kwargs
# through them — the serving micro-batcher opens a context around its
# score_fn call and the engine's `serving.score` span inherits the batch
# identity. Thread-local so concurrent micro-batchers don't cross-tag.
_span_ctx = threading.local()

_annotation_cls = None


def _annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` for ``name`` when jax is already
    imported, else None. Never imports jax."""
    global _annotation_cls
    cls = _annotation_cls
    if cls is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return None
        try:
            cls = _annotation_cls = jax.profiler.TraceAnnotation
        except AttributeError:  # jax is still being imported
            return None
    return cls(name)


def _record(name, start_s, end_s, cat, tid, attrs, tracer,
            span_id=None, parent_id=None) -> int:
    """The one place a span becomes a record: the flight ring always, the
    tracer's export when one is given."""
    if span_id is None:
        span_id = next(_ids)
    if parent_id is None:
        stack = getattr(_span_ctx, "stack", None)
        parent_id = stack[-1][0] if stack else 0
    if tid is None:
        tid = threading.get_ident()
    _flight.note_span(
        (name, start_s, end_s, span_id, parent_id, tid, attrs)
    )
    if tracer is not None:
        tracer._export_span(name, start_s, end_s, cat, tid, attrs)
    return span_id


def add_span(
    name: str,
    start_s: float,
    end_s: float,
    cat: str = "app",
    tid: Optional[int] = None,
    **attrs,
) -> int:
    """The retro-stamped form: record a span whose ``time.perf_counter()``
    stamps are known only afterwards (a request's life, a stage timed by
    other means). Same record as a live span; no profiler annotation, for
    its time has passed. Returns the span id."""
    return _record(name, start_s, end_s, cat, tid, attrs, _active)


class Span:
    """A live span: records on ``__exit__`` (see the module docstring).

    ``set(**attrs)`` attaches attributes (the record's ``attrs``; Perfetto's
    args pane and the JSONL line under a tracer). ``sync(value)`` blocks
    until the device work producing ``value`` is done and annotates the
    span with the blocked milliseconds — wall time alone cannot split an
    async dispatch from device completion. A span that exits via an
    exception is recorded with ``error=True``; where the time went is most
    valuable exactly when the phase died (same contract as ``timed()``).
    """

    __slots__ = ("name", "cat", "args", "span_id", "parent_id", "_t0",
                 "_ann")

    def __init__(self, name: str, cat: str, args: dict):
        self.name = name
        self.cat = cat
        self.args = args
        self.span_id = next(_ids)
        self.parent_id = 0
        self._t0 = 0.0
        self._ann = None

    def __enter__(self) -> "Span":
        stack = getattr(_span_ctx, "stack", None)
        if stack is None:
            stack = _span_ctx.stack = []
        ctx = None
        if stack:
            self.parent_id, ctx = stack[-1]
            if ctx:
                self.args = {**ctx, **self.args}
        stack.append((self.span_id, ctx))
        ann = self._ann = _annotation(self.name)
        if ann is not None:
            ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        _span_ctx.stack.pop()
        if exc_type is not None:
            self.args["error"] = True
        _record(
            self.name, self._t0, t1, self.cat, None, self.args, _active,
            self.span_id, self.parent_id,
        )
        return False

    def set(self, **attrs) -> None:
        self.args.update(attrs)

    def sync(self, value):
        """``jax.block_until_ready(value)``, annotating the span with the
        blocked time (``device_wait_ms``) — the device-time attribution
        seam. Imports jax lazily so the module stays stdlib-only."""
        import jax

        t0 = time.perf_counter()
        out = jax.block_until_ready(value)
        self.args["device_wait_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 4
        )
        return out


def current_span_context() -> Optional[Dict[str, Any]]:
    """The innermost ambient span-context dict, or None."""
    stack = getattr(_span_ctx, "stack", None)
    return (stack[-1][1] or None) if stack else None


@contextlib.contextmanager
def span_context(**fields):
    """Attach ``fields`` to every span opened in this thread inside the
    block (explicit span attrs win on key collision). Nestable: inner
    contexts layer over outer ones."""
    stack = getattr(_span_ctx, "stack", None)
    if stack is None:
        stack = _span_ctx.stack = []
    if stack:
        parent_id, outer = stack[-1]
        merged = {**outer, **fields} if outer else dict(fields)
    else:
        parent_id, merged = 0, dict(fields)
    stack.append((parent_id, merged))
    try:
        yield
    finally:
        stack.pop()


def span(name: str, cat: str = "app", **attrs) -> Span:
    """A span (context manager): always live, always recorded — see the
    module docstring for what that costs and where the record goes."""
    return Span(name, cat, attrs)


def emit_event(name: str, cat: str = "event", **fields) -> None:
    """Record an instantaneous structured event (retry fired, fault
    injected, rollback, preemption…) on the active tracer; no-op when
    tracing is off. Fields must be JSON-serializable."""
    tracer = _active
    if tracer is not None:
        tracer.add_instant(name, cat=cat, args=fields)
