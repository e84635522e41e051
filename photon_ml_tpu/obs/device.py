"""Live device (HBM) memory telemetry.

The only HBM numbers the repo had were static: compiled-footprint
``memory_analysis()`` sizes recorded by the bench and the ingest path's
*designed* peak ("dataset + one chunk"). Nothing ever observed the live
allocator — a 2x assembly peak or a leaked device buffer was invisible
until an OOM. This module samples ``device.memory_stats()`` (PJRT's
allocator counters: ``bytes_in_use``, ``peak_bytes_in_use``, ...) into
the obs layer:

- :func:`sample_hbm` — one sample per local device: ``hbm.d<i>.*``
  registry gauges plus Chrome **counter-track** events on the active
  tracer (Perfetto renders them as a memory graph under the timeline).
- :class:`HbmSampler` — background thread sampling on an interval for
  the life of an ``obs.observe`` envelope.
- :func:`hbm_watermark` — context manager bracketing a phase (ingest
  assembly, a descent pass, serving warmup): records before/after/peak
  bytes, exposes ``delta_bytes``/``peak_bytes`` to the caller, and emits
  a ``hbm.watermark`` event + ``hbm.<label>.*`` gauges.

Support is platform-dependent: CPU (and some backends) return ``None``
from ``memory_stats()``. Everything here degrades to a graceful no-op —
zero threads, zero events, zero cost — so CPU test/bench runs and the
<5% overhead gate are untouched. Tests monkeypatch :func:`read_memory_stats`
to drive the machinery without real HBM.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, List, Optional

# symbol imports: the package rebinds its `trace` attribute to the
# context-manager function once __init__ runs (see xla_cost.py)
from photon_ml_tpu.obs.metrics import registry as _registry
from photon_ml_tpu.obs.trace import emit_event as _emit_event
from photon_ml_tpu.obs.trace import get_tracer as _get_tracer

__all__ = [
    "read_memory_stats",
    "hbm_supported",
    "sample_hbm",
    "HbmSampler",
    "HbmWatermark",
    "hbm_watermark",
]

# The allocator counters worth exporting (when present); memory_stats()
# key names follow PJRT's TF-derived allocator stats.
_STAT_KEYS = ("bytes_in_use", "peak_bytes_in_use", "largest_alloc_size")


def read_memory_stats(device=None) -> Optional[Dict[str, int]]:
    """``device.memory_stats()`` with every failure mode collapsed to
    ``None`` (unsupported platform, uninitialized backend, transient
    runtime error). The ONE seam the rest of the module reads through — tests
    monkeypatch this to simulate an HBM-bearing device."""
    try:
        if device is None:
            import jax

            device = jax.local_devices()[0]
        stats = device.memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    return {k: int(v) for k, v in stats.items()}


def _local_devices() -> List:
    try:
        import jax

        return list(jax.local_devices())
    except Exception:
        return []


def hbm_supported() -> bool:
    """True when at least the first local device reports memory stats."""
    return read_memory_stats() is not None


def sample_hbm(registry=None, tracer=None) -> Dict[str, Dict[str, int]]:
    """Sample every local device once. Returns ``{device_label: stats}``
    (empty when unsupported); side effects: ``hbm.d<i>.*`` gauges and a
    counter-track event per device on the active tracer."""
    reg = registry if registry is not None else _registry()
    tr = tracer if tracer is not None else _get_tracer()
    out: Dict[str, Dict[str, int]] = {}
    for i, dev in enumerate(_local_devices()):
        stats = read_memory_stats(dev)
        if stats is None:
            # device 0 unsupported => the platform is; don't probe 8x
            if i == 0:
                break
            continue
        label = f"d{i}"
        out[label] = stats
        track = {}
        for k in _STAT_KEYS:
            if k in stats:
                reg.set_gauge(f"hbm.{label}.{k}", stats[k])
                track[k] = stats[k]
        if tr is not None and track:
            tr.add_counter(f"hbm.{label}", track)
    return out


class HbmSampler:
    """Background HBM sampler for the life of an observe() envelope.

    ``start()`` is a no-op when the platform reports no memory stats, so
    installing it unconditionally costs one probe. Event-driven stop
    (like MetricsDumper): teardown returns promptly, and a final sample
    on stop means the trace's counter track covers the full window.
    """

    def __init__(self, every_s: float = 0.5, registry=None):
        self.every_s = every_s
        self._registry = registry
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def _run(self) -> None:
        while not self._stop.wait(self.every_s):
            sample_hbm(registry=self._registry)

    def start(self) -> "HbmSampler":
        if (
            self.every_s > 0
            and self._thread is None
            and read_memory_stats() is not None
        ):
            self._thread = threading.Thread(
                target=self._run, name="obs-hbm-sampler", daemon=True
            )
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
            sample_hbm(registry=self._registry)


class HbmWatermark:
    """Result object of :func:`hbm_watermark`. ``supported`` is False on
    platforms without memory stats; every byte field is then None."""

    __slots__ = (
        "label", "supported", "before_bytes", "after_bytes",
        "peak_bytes", "delta_bytes",
    )

    def __init__(self, label: str):
        self.label = label
        self.supported = False
        self.before_bytes: Optional[int] = None
        self.after_bytes: Optional[int] = None
        self.peak_bytes: Optional[int] = None
        self.delta_bytes: Optional[int] = None


@contextlib.contextmanager
def hbm_watermark(label: str, registry=None):
    """Bracket a phase with HBM readings on the first local device.

    Yields an :class:`HbmWatermark`; on exit (supported platforms) fills
    ``before/after/peak/delta`` bytes, sets ``hbm.<label>.peak_bytes`` /
    ``hbm.<label>.delta_bytes`` gauges, and emits an ``hbm.watermark``
    instant event. ``peak_bytes`` is the allocator's high-water mark *as
    of phase end* — monotone per process, so compare watermarks of the
    same phase across configurations, not across phases of one run.
    Unsupported platforms run the body with zero overhead beyond two
    ``None`` probes.
    """
    wm = HbmWatermark(label)
    before = read_memory_stats()
    try:
        yield wm
    finally:
        if before is not None:
            after = read_memory_stats()
            if after is not None:
                wm.supported = True
                wm.before_bytes = before.get("bytes_in_use")
                wm.after_bytes = after.get("bytes_in_use")
                wm.peak_bytes = after.get("peak_bytes_in_use")
                if (
                    wm.before_bytes is not None
                    and wm.after_bytes is not None
                ):
                    wm.delta_bytes = wm.after_bytes - wm.before_bytes
                reg = (
                    registry if registry is not None else _registry()
                )
                if wm.peak_bytes is not None:
                    reg.set_gauge(f"hbm.{label}.peak_bytes", wm.peak_bytes)
                if wm.delta_bytes is not None:
                    reg.set_gauge(f"hbm.{label}.delta_bytes", wm.delta_bytes)
                _emit_event(
                    "hbm.watermark",
                    cat="hbm",
                    label=label,
                    before_bytes=wm.before_bytes,
                    after_bytes=wm.after_bytes,
                    peak_bytes=wm.peak_bytes,
                    delta_bytes=wm.delta_bytes,
                )
