"""Flight ring and crash flight recorder: the last N observations.

**The span ring** is on from import, in every process: every
``obs.span`` and every retro-stamped ``obs.add_span`` appends one tuple
``(name, start, end, span_id, parent_id, thread, attrs)`` to a bounded
``deque`` (``SPAN_CAPACITY`` records; ``start``/``end`` are
``time.perf_counter()`` seconds, ``parent_id`` the innermost span open
on the recording thread or 0, ``attrs`` the caller's dict). Recording is
one ``deque.append``: no lock of its own, no dict built, nothing
serialized. :func:`recent_spans` copies the ring out and
:func:`spans_dropped` says how many records fell off its far end, so a
reader can tell a quiet window from a lost one.

**The flight recorder** is the opt-in part. The tracer batches its JSONL
records 64 deep and the trace document only exports on clean teardown,
so the moments that matter most — the spans and metric movements
immediately BEFORE a divergence rollback, a preemption, or an unhandled
crash — are exactly the ones most likely to be lost. A
:class:`FlightRecorder` keeps the active tracer's instant events and
HBM counter samples plus periodic metric-delta samples in a ring of its
own, and :func:`flight_dump` serializes them together with the newest
records of the span ring as ``flight-<reason>.json`` the moment
something goes wrong:

- ``resilience.shutdown.GracefulShutdown`` dumps on SIGTERM/SIGINT/
  preemption (reason ``preemption``; programmatic -> ``shutdown``),
- the GAME divergence guard dumps on a non-finite rollback
  (``divergence``),
- an installed ``sys.excepthook`` chain dumps on any unhandled crash
  (``crash``) before the previous hook runs.

The dump is self-contained: reason, pod identity (``obs.dist``), the
records (oldest first, with a dropped-record count), and a full metrics
registry snapshot — a post-mortem no longer depends on whatever happened
to be flushed, nor on a ``--trace-dir`` having been given: the spans are
in the ring either way.

This module imports nothing of the package at import time
(``obs.trace`` records into it), only inside the functions that dump.
"""

from __future__ import annotations

import collections
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

__all__ = [
    "SPAN_CAPACITY",
    "note_span",
    "recent_spans",
    "spans_dropped",
    "reset_spans",
    "FlightRecorder",
    "install_flight_recorder",
    "uninstall_flight_recorder",
    "flight_recorder",
    "flight_dump",
]

DEFAULT_CAPACITY = 2048

# ---------------------------------------------------------------------------
# The span ring (always on)
# ---------------------------------------------------------------------------

SPAN_CAPACITY = 65536
# index of each field in a span record
NAME, START, END, SPAN_ID, PARENT_ID, THREAD, ATTRS = range(7)

_spans: "collections.deque[tuple]" = collections.deque(maxlen=SPAN_CAPACITY)
_spans_dropped = 0
# one (unix, perf_counter) pair, so a dump can say when a span was
_UNIX0, _PERF0 = time.time(), time.perf_counter()


def note_span(record: tuple) -> None:
    """Append one span record. ``deque.append`` is atomic under the
    interpreter lock; the dropped count is a plain integer, so two
    threads that fill the last slot in the same instant can leave it one
    short — it is never short for longer than until the next append."""
    global _spans_dropped
    if len(_spans) >= _spans.maxlen:
        _spans_dropped += 1
    _spans.append(record)


def recent_spans(since_s: Optional[float] = None) -> List[tuple]:
    """The ring's span records, oldest first: tuples ``(name, start,
    end, span_id, parent_id, thread, attrs)`` on the
    ``time.perf_counter()`` clock. With ``since_s`` only the records
    that ended at or after it."""
    records = list(_spans)  # one C-level copy: atomic under the lock
    if since_s is not None:
        records = [r for r in records if r[END] >= since_s]
    return records


def spans_dropped() -> int:
    """How many span records the ring has pushed out since the process
    started (or since :func:`reset_spans`)."""
    return _spans_dropped


def reset_spans(capacity: Optional[int] = None) -> None:
    """Empty the ring and zero the dropped count (tests; a new
    ``capacity`` stays until the next reset names another)."""
    global _spans, _spans_dropped
    _spans = collections.deque(
        maxlen=capacity if capacity is not None else SPAN_CAPACITY
    )
    _spans_dropped = 0


def _span_as_dict(rec: tuple) -> Dict[str, Any]:
    """A span record in the shape the tracer's JSONL log gives spans."""
    out = dict(rec[ATTRS]) if rec[ATTRS] else {}
    out.update(
        kind="span",
        name=rec[NAME],
        time_unix=round(_UNIX0 + (rec[START] - _PERF0), 6),
        duration_ms=round((rec[END] - rec[START]) * 1e3, 6),
        span_id=rec[SPAN_ID],
        parent_id=rec[PARENT_ID],
        thread=rec[THREAD],
    )
    return out


# ---------------------------------------------------------------------------
# The flight recorder (opt-in): events, counters, metric deltas, dumps
# ---------------------------------------------------------------------------


def _registry():
    from photon_ml_tpu.obs.metrics import registry

    return registry()



class FlightRecorder:
    """Fixed-capacity ring of recent observation records.

    ``note(record)`` is the tracer-side hook (called for every instant /
    counter JSONL-style record; spans are in the module's span ring
    already); ``sample_metrics()`` appends a counter-delta record (what
    moved since the last sample); ``dump(reason)`` writes the newest
    ``capacity`` records of both rings + a registry snapshot to
    ``flight-<reason>.json`` and never raises — it runs on the failure
    paths it exists to document.
    """

    def __init__(
        self,
        capacity: int = DEFAULT_CAPACITY,
        flight_dir: Optional[str] = None,
        registry=None,
    ):
        if capacity <= 0:
            raise ValueError(f"capacity must be positive, got {capacity}")
        self.capacity = capacity
        self.flight_dir = flight_dir
        self._registry = registry
        self._lock = threading.Lock()
        self._ring: "collections.deque[Dict[str, Any]]" = collections.deque(
            maxlen=capacity
        )
        self._seq = 0
        self._dropped = 0
        self._last_counters: Dict[str, float] = {}

    # -- recording ----------------------------------------------------------

    def note(self, record: Dict[str, Any]) -> None:
        """Append one observation record (already JSON-safe)."""
        with self._lock:
            self._seq += 1
            if len(self._ring) == self.capacity:
                self._dropped += 1
            self._ring.append(
                {"seq": self._seq, "time_unix": round(time.time(), 6),
                 **record}
            )

    def sample_metrics(self) -> None:
        """Append a ``metrics_delta`` record: every counter that moved
        since the previous sample. Gauge/histogram state rides the full
        snapshot in :meth:`dump`; counters are the ones whose *movement*
        tells the crash story (retries fired, rollbacks, rejected
        requests)."""
        reg = self._registry if self._registry is not None else _registry()
        counters = reg.snapshot()["counters"]
        with self._lock:
            changed = {
                name: round(value - self._last_counters.get(name, 0.0), 6)
                for name, value in counters.items()
                if value != self._last_counters.get(name, 0.0)
            }
            self._last_counters = dict(counters)
        if changed:
            self.note(
                {
                    "kind": "metrics_delta",
                    "time_unix": round(time.time(), 6),
                    "changed": changed,
                }
            )

    # -- readout ------------------------------------------------------------

    def records(self) -> list:
        with self._lock:
            return list(self._ring)

    def dump(
        self, reason: str, flight_dir: Optional[str] = None
    ) -> Optional[str]:
        """Write ``flight-<reason>.json`` (suffixing ``-2``, ``-3``… when
        the name exists: repeated rollbacks in one run must not clobber
        the first post-mortem). Returns the path, or None when there is
        nowhere to write or the write failed — the failure path being
        documented must not gain a second failure."""
        directory = flight_dir or self.flight_dir or "."
        reason = "".join(
            c if (c.isalnum() or c in "-_") else "-" for c in str(reason)
        ) or "unknown"
        try:
            self.sample_metrics()
        except Exception:
            pass
        with self._lock:
            records = list(self._ring)
            dropped = self._dropped
        # the newest spans of the always-on ring, in the order they ended
        spans = [_span_as_dict(r) for r in recent_spans()[-self.capacity:]]
        records = sorted(
            records + spans,
            key=lambda r: r["time_unix"] + r.get("duration_ms", 0.0) / 1e3,
        )[-self.capacity:]
        dropped += spans_dropped()
        reg = self._registry if self._registry is not None else _registry()
        try:
            metrics = reg.snapshot()
        except Exception:
            metrics = {}
        from photon_ml_tpu.obs import dist as _dist

        idx, count = _dist.process_identity()
        payload = {
            "reason": reason,
            "time_unix": round(time.time(), 6),
            "process_index": idx,
            "process_count": count,
            "pid": os.getpid(),
            "capacity": self.capacity,
            "records_dropped": dropped,
            "records": records,
            "metrics": metrics,
        }
        try:
            os.makedirs(directory, exist_ok=True)
            path = os.path.join(directory, f"flight-{reason}.json")
            n = 2
            while os.path.exists(path):
                path = os.path.join(directory, f"flight-{reason}-{n}.json")
                n += 1
            with open(path, "w", encoding="utf-8") as f:
                json.dump(payload, f)
            return path
        except Exception:
            return None


# ---------------------------------------------------------------------------
# Process-global recorder + crash hook
# ---------------------------------------------------------------------------

_recorder: Optional[FlightRecorder] = None
_prev_excepthook = None


def _crash_excepthook(exc_type, exc, tb) -> None:
    rec = _recorder
    if rec is not None:
        try:
            rec.note(
                {
                    "kind": "event",
                    "name": "crash",
                    "time_unix": round(time.time(), 6),
                    "exception": f"{exc_type.__name__}: {exc}",
                }
            )
            rec.dump("crash")
        except Exception:
            pass
    hook = _prev_excepthook or sys.__excepthook__
    hook(exc_type, exc, tb)


def install_flight_recorder(
    capacity: int = DEFAULT_CAPACITY,
    flight_dir: Optional[str] = None,
    registry=None,
    crash_hook: bool = True,
) -> FlightRecorder:
    """Install a process-global flight recorder: attach it to the active
    tracer (events/counters start landing in its ring), and chain
    a crash ``sys.excepthook`` that dumps ``flight-crash.json`` before
    the previous hook runs. Returns the recorder. Re-installing replaces
    the previous recorder (its ring is abandoned)."""
    global _recorder, _prev_excepthook
    rec = FlightRecorder(
        capacity=capacity, flight_dir=flight_dir, registry=registry
    )
    _recorder = rec
    from photon_ml_tpu.obs.trace import get_tracer

    tracer = get_tracer()
    if tracer is not None:
        tracer.recorder = rec
    if crash_hook and sys.excepthook is not _crash_excepthook:
        _prev_excepthook = sys.excepthook
        sys.excepthook = _crash_excepthook
    return rec


def uninstall_flight_recorder() -> None:
    """Detach the global recorder and restore the previous excepthook."""
    global _recorder, _prev_excepthook
    from photon_ml_tpu.obs.trace import get_tracer

    tracer = get_tracer()
    if tracer is not None and tracer.recorder is _recorder:
        tracer.recorder = None
    _recorder = None
    if sys.excepthook is _crash_excepthook:
        sys.excepthook = _prev_excepthook or sys.__excepthook__
        _prev_excepthook = None


def flight_recorder() -> Optional[FlightRecorder]:
    """The installed process-global recorder, or None."""
    return _recorder


def flight_dump(
    reason: str, flight_dir: Optional[str] = None
) -> Optional[str]:
    """Dump the global recorder's ring as ``flight-<reason>.json``.
    No-op (returns None) when no recorder is installed — failure paths
    call this unconditionally."""
    rec = _recorder
    if rec is None:
        return None
    return rec.dump(reason, flight_dir=flight_dir)
