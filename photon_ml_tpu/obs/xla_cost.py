"""XLA cost book: device-level performance accounting as observability.

Before this module the only hardware-efficiency numbers in the repo (MFU,
HBM utilization, per-device footprints, collective counts — the BENCH
record's ``mfu``/``hbm_util``/``sparse_fs_scaling`` fields) were computed
by hand inside ``bench.py``: analytic FLOP arithmetic, a one-off regex
over HLO text, local peak constants. Training, serving, and the PR-3 obs
layer could not see them, and nothing guaranteed the bench's accounting
matched what actually compiled. The cost book promotes that accounting to
a first-class instrument:

- :func:`CostBook.record` wraps any **lowered or compiled** executable
  and extracts XLA's own numbers — ``cost_analysis()`` FLOPs and bytes
  accessed, ``memory_analysis()`` argument/temp/output sizes (compiled
  only), and collective-op counts parsed from the optimized HLO
  (:func:`count_collectives`, the generalization of the regex formerly
  inlined in ``bench.py``). Records key by executable name + shape
  bucket, land in the metrics registry as ``xla.cost.*`` gauges, and
  emit a ``xla.cost_record`` instant event on the active tracer.
- :meth:`CostRecord.achieved` turns a record + a measured window into
  hardware attribution: ``flops``, ``achieved_tflops``, ``mfu``,
  ``bytes_per_s`` (``bench.py``'s MFU).

Every analysis is best-effort: backends without a cost/memory analysis
(or exotic executables) degrade to the caller-supplied analytic
fallbacks, never to an exception — observability must not fail the work
it observes.
"""

from __future__ import annotations

import dataclasses
import re
import threading
from typing import Any, Dict, Optional, Tuple

# symbol imports (not `from obs import trace`): the package rebinds its
# `trace` attribute to the context-manager function, so module-attribute
# imports resolve to the function once __init__ has run
from photon_ml_tpu.obs.metrics import MetricsRegistry
from photon_ml_tpu.obs.metrics import registry as _registry
from photon_ml_tpu.obs.trace import emit_event as _emit_event

__all__ = [
    "DevicePeaks",
    "DEVICE_PEAKS",
    "device_peaks",
    "require_device_peaks",
    "COLLECTIVE_RE",
    "count_collectives",
    "CostRecord",
    "CostBook",
    "cost_book",
    "set_cost_book",
]


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip roofline peaks: dense bf16 matmul FLOP/s and
    HBM bytes/s."""

    flops: float
    hbm_bps: float


# The ONE peaks table every consumer shares, keyed by jax's
# ``device_kind``. A device that is not here gets NO utilisation figure
# (mfu / hbm_util) from the program — never another chip's peaks.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 819 GB/s HBM per chip). GLM objective passes stream the design matrix
# at ~2 FLOP/byte — far below the ~240 FLOP/byte compute-bound knee — so
# the HBM line is the relevant ceiling for the solvers in this repo.
DEVICE_PEAKS: Dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(flops=197e12, hbm_bps=819e9),
}


def device_peaks(device_kind: Optional[str] = None) -> Optional[DevicePeaks]:
    """Peaks of ``device_kind`` (default: the first jax device's), or
    None when the table does not list it."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    return DEVICE_PEAKS.get(device_kind)


def require_device_peaks() -> DevicePeaks:
    """``device_peaks()`` for callers whose output is a utilisation
    figure (bench.py, chip_smoke.py): an unlisted device is an error."""
    peaks = device_peaks()
    if peaks is None:
        import jax

        raise RuntimeError(
            "no roofline peaks for device_kind "
            f"{jax.devices()[0].device_kind!r}; known: "
            f"{sorted(DEVICE_PEAKS)} (obs/xla_cost.py DEVICE_PEAKS)"
        )
    return peaks


# The collective ops that matter for the scaling story (each -start
# variant collapses onto its base op — async collectives lower as
# start/done pairs and must not double-count). Formerly inlined at the
# bench's sparse-scaling measurement; now the one shared definition.
# Counting matches INSTRUCTIONS (the opcode followed by its operand
# list), not every textual occurrence: the regex that used to be inlined
# in bench.py also matched `%all-reduce` OPERAND references in fusion
# consumers, double-or-more counting each real collective (BENCH_r05's
# "4 all-reduces" in the F>=2 objective pass were 2 instructions plus
# their uses). Collective COUNTS therefore drop across the board
# relative to the r01-r05 history — a counting fix, not a perf change
# (the sentinel's direction for `collectives.` is lower-is-better, so
# the fix cannot trip it).
COLLECTIVE_RE = re.compile(
    r"\b(all-reduce(?:-start)?|all-gather(?:-start)?|"
    r"all-to-all|reduce-scatter|collective-permute)\("
)


def count_collectives(hlo_text: str) -> Dict[str, int]:
    """Collective-INSTRUCTION counts in an (optimized) HLO dump,
    ``{op_base_name: count}`` with ``-start`` variants folded into the
    base op. Empty dict = no collectives (the single-device case)."""
    counts: Dict[str, int] = {}
    for m in COLLECTIVE_RE.findall(hlo_text):
        base = m.split("-start")[0]
        counts[base] = counts.get(base, 0) + 1
    return counts


def _sig(x: float, digits: int = 4) -> float:
    """Round to significant digits: fixed-decimal rounding flattens
    tiny-but-real utilizations (a 600-row drill's MFU) to 0.0, and a
    zero in a trace reads as 'no work', not 'small work'."""
    return float(f"{x:.{digits}g}")


@dataclasses.dataclass(frozen=True)
class CostRecord:
    """One executable's static cost profile.

    ``flops``/``bytes_accessed`` come from XLA's cost analysis of ONE
    execution (loop bodies with dynamic trip counts are counted once —
    callers scale by their own pass counts, exactly like bench.py's
    counted-work methodology). ``argument_bytes``/``output_bytes``/
    ``temp_bytes`` are the compiled per-device memory analysis (None for
    lowered-only records). ``source`` says which analyses ran:
    ``"compiled"``, ``"lowered"``, or ``"analytic"`` (every XLA analysis
    unavailable; the caller's fallbacks carried the numbers).
    """

    name: str
    bucket: str
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    argument_bytes: Optional[int] = None
    output_bytes: Optional[int] = None
    temp_bytes: Optional[int] = None
    collectives: Dict[str, int] = dataclasses.field(default_factory=dict)
    source: str = "analytic"
    # caller-pinned bytes for bandwidth-roofline arithmetic. XLA's
    # static bytes-accessed counts every materialization in the
    # unoptimized module — including dtype-convert round trips a fused
    # backend never pays (a bf16 design upcast to f32 counts ~2x its
    # true HBM traffic) — so callers measuring a bandwidth ceiling may
    # pin the minimal traffic here; ``achieved()`` prefers it.
    roofline_bytes: Optional[float] = None

    def achieved(
        self,
        seconds: float,
        passes: float = 1.0,
        peaks: Optional[DevicePeaks] = None,
    ) -> Dict[str, float]:
        """Hardware attribution for ``passes`` executions of this record
        over a measured ``seconds`` window — the span-annotation payload
        (flops / achieved_tflops / bytes_per_s, plus mfu / hbm_util
        against ``peaks``, default the running device's — omitted when
        :data:`DEVICE_PEAKS` does not list it)."""
        out: Dict[str, float] = {}
        if seconds <= 0:
            return out
        if peaks is None:
            peaks = device_peaks()
        if self.flops is not None:
            fl = self.flops * passes
            out["flops"] = fl
            out["achieved_tflops"] = _sig(fl / seconds / 1e12)
            if peaks is not None:
                out["mfu"] = _sig(fl / seconds / peaks.flops)
        hbm_bytes = (
            self.roofline_bytes
            if self.roofline_bytes is not None
            else self.bytes_accessed
        )
        if hbm_bytes is not None:
            bps = hbm_bytes * passes / seconds
            out["bytes_per_s"] = _sig(bps)
            if peaks is not None:
                out["hbm_util"] = _sig(bps / peaks.hbm_bps)
        return out


class CostBook:
    """Thread-safe (name, shape bucket) -> :class:`CostRecord` map.

    One book per process (:func:`cost_book`) is the common case — bench,
    training, and serving record into the same table, which is the point:
    the BENCH record's MFU and a traced solve's ``mfu`` span arg are then
    the same arithmetic over the same XLA numbers.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._records: Dict[Tuple[str, str], CostRecord] = {}

    def record(
        self,
        name: str,
        executable: Any = None,
        bucket: str = "",
        analytic_flops: Optional[float] = None,
        analytic_bytes: Optional[float] = None,
        roofline_bytes: Optional[float] = None,
        registry: Optional[MetricsRegistry] = None,
    ) -> CostRecord:
        """Analyze ``executable`` (a ``jax.stages.Lowered`` or
        ``.compile()``-d executable; None = analytic-only) and store the
        record under ``(name, bucket)``. Re-recording the same key
        replaces the record (shapes are in the bucket; a same-key
        re-record is a re-analysis of the same program).

        ``analytic_flops``/``analytic_bytes`` are fallbacks used when the
        backend exposes no cost analysis — the record is still usable for
        MFU attribution, marked ``source="analytic"``.
        ``roofline_bytes`` pins the bandwidth-roofline traffic when the
        caller knows XLA's static count overstates it (see
        :class:`CostRecord`).
        """
        flops = bytes_accessed = None
        arg_b = out_b = tmp_b = None
        colls: Dict[str, int] = {}
        source = "analytic"
        if executable is not None:
            try:
                ca = executable.cost_analysis()
            except Exception:
                ca = None
            if ca is not None:
                flops = float(ca.get("flops", 0.0)) or None
                bytes_accessed = (
                    float(ca.get("bytes accessed", 0.0)) or None
                )
                source = "lowered"
            try:
                ma = executable.memory_analysis()
                if ma is not None:
                    arg_b = int(ma.argument_size_in_bytes)
                    out_b = int(ma.output_size_in_bytes)
                    tmp_b = int(ma.temp_size_in_bytes)
                    source = "compiled"
            except Exception:
                pass
            try:
                # optimized HLO exists on compiled executables only; a
                # Lowered's as_text() is the pre-optimization module whose
                # collectives are not yet final — skip unless compiled
                if arg_b is not None:
                    colls = count_collectives(executable.as_text())
            except Exception:
                colls = {}
        if flops is None:
            flops = analytic_flops
        if bytes_accessed is None:
            bytes_accessed = analytic_bytes
        rec = CostRecord(
            name=name,
            bucket=str(bucket),
            flops=flops,
            bytes_accessed=bytes_accessed,
            argument_bytes=arg_b,
            output_bytes=out_b,
            temp_bytes=tmp_b,
            collectives=colls,
            source=source,
            roofline_bytes=roofline_bytes,
        )
        with self._lock:
            self._records[(name, rec.bucket)] = rec
        self._export(rec, registry)
        return rec

    def _export(self, rec: CostRecord, registry=None) -> None:
        """Registry gauges + a trace instant event for one record, so
        cost profiles land in ``metrics.json`` and in the Perfetto
        timeline without caller wiring."""
        reg = registry if registry is not None else _registry()
        key = rec.name + (f".{rec.bucket}" if rec.bucket else "")
        if rec.flops is not None:
            reg.set_gauge(f"xla.cost.{key}.flops", rec.flops)
        if rec.bytes_accessed is not None:
            reg.set_gauge(f"xla.cost.{key}.bytes_accessed", rec.bytes_accessed)
        if rec.temp_bytes is not None:
            reg.set_gauge(f"xla.cost.{key}.temp_bytes", rec.temp_bytes)
        if rec.collectives:
            reg.set_gauge(
                f"xla.cost.{key}.collectives", sum(rec.collectives.values())
            )
        _emit_event(
            "xla.cost_record",
            cat="xla",
            executable=rec.name,
            bucket=rec.bucket,
            flops=rec.flops,
            bytes_accessed=rec.bytes_accessed,
            argument_bytes=rec.argument_bytes,
            temp_bytes=rec.temp_bytes,
            collectives=dict(rec.collectives),
            source=rec.source,
        )

    def lookup(self, name: str, bucket: str = "") -> Optional[CostRecord]:
        with self._lock:
            return self._records.get((name, str(bucket)))

    def names(self) -> list:
        with self._lock:
            return sorted(self._records)

    def reset(self) -> None:
        with self._lock:
            self._records.clear()

    def snapshot(self) -> dict:
        """Plain-JSON view keyed ``name[.bucket]`` — lands in the BENCH
        record's ``extra.cost_book`` and in trace metadata."""
        with self._lock:
            items = list(self._records.items())
        out = {}
        for (name, bucket), rec in sorted(items):
            key = name + (f".{bucket}" if bucket else "")
            out[key] = {
                "flops": rec.flops,
                "bytes_accessed": rec.bytes_accessed,
                "argument_bytes": rec.argument_bytes,
                "output_bytes": rec.output_bytes,
                "temp_bytes": rec.temp_bytes,
                "collectives": dict(rec.collectives),
                "source": rec.source,
            }
            if rec.roofline_bytes is not None:
                out[key]["roofline_bytes"] = rec.roofline_bytes
        return out


# ONE process-global default book, mirroring the default metrics registry.
_default = CostBook()


def cost_book() -> CostBook:
    """The process-global default cost book."""
    return _default


def set_cost_book(book: CostBook) -> CostBook:
    """Swap the process default (tests). Returns the previous one."""
    global _default
    prev = _default
    _default = book
    return prev
