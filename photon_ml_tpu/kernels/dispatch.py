"""Sparse-kernel dispatch: PHOTON_SPARSE_KERNEL={auto,pallas,xla}.

``ops/sparse.py`` routes its ELL contractions either through XLA's
gather/scatter lowering or through the hand-written Pallas suite in this
package. This module is the ONE place that decides, by a rule — never by
trying a kernel and catching what happens (docs/KERNELS.md "Dispatch"):

- ``kernel_mode()``: the env knob. ``auto`` (default) and ``xla`` select
  the XLA lowering on EVERY platform. None of the six Pallas kernels
  lowers for TPU on the installed toolchain (jax 0.9.0 / libtpu 0.0.34):
  the in-kernel table gather ``w_ref[0, :][ix]`` stops at "Only 2D
  gather is supported" and the scatter's per-row ``ix[r, :]`` at an
  unimplemented ``dynamic_slice`` — both recorded as strict xfails in
  ``tests/test_kernels.py::TestTpuLowering``, so a repair flips those
  tests and forces this rule to be revisited. ``pallas`` forces the
  suite: interpret mode off-TPU (how tier-1 proves kernel semantics on
  CPU), the real lowering on TPU — where today it raises the compiler's
  own error at the first sparse pass.
- ``use_pallas(...)``: mode x shape eligibility. Even when forced,
  Pallas is skipped when the coefficient table or accumulator would not
  fit the VMEM budget (``PHOTON_PALLAS_VMEM_CAP``, default 4 MiB per
  buffer — row blocks stream, but w and the scatter accumulator are
  resident), when the batch is degenerate (0 rows/slots), or when a
  >1-device mesh is active outside ``shard_local()`` — GSPMD would
  replicate a Pallas custom call; that case is logged and counted.
- ``record_kernel_cost(...)``: every kernel wrapper books its analytic
  cost profile (FLOPs, bytes, ONE-design-read roofline traffic) into the
  shared :mod:`photon_ml_tpu.obs.xla_cost` cost book, once per
  (kernel, shape bucket).
"""

from __future__ import annotations

import contextlib
import os
import threading
from typing import Optional

import jax

__all__ = [
    "ENV_VAR",
    "VMEM_CAP_ENV",
    "KERNEL_MODES",
    "kernel_mode",
    "use_pallas",
    "interpret_mode",
    "accumulator_fits",
    "active_mesh_devices",
    "record_kernel_cost",
    "design_reads",
    "shard_local",
    "in_shard_local",
]

ENV_VAR = "PHOTON_SPARSE_KERNEL"
VMEM_CAP_ENV = "PHOTON_PALLAS_VMEM_CAP"
KERNEL_MODES = ("auto", "pallas", "xla")

# Per-buffer VMEM budget for the resident (non-streamed) buffers: the
# gathered coefficient table and the dense scatter accumulator. 4 MiB
# holds d = 1M f32 columns and leaves the double-buffered row blocks
# plenty of a ~16 MiB core (docs/KERNELS.md "Tiling").
_DEFAULT_VMEM_CAP = 4 << 20

# Design reads per pass, per kernel: the counted-work unit the fused
# passes exist to shrink. The XLA objective sequence reads the design
# once per contraction (matvec + rmatvec [+ colsum]); each fused pass
# reads (indices, values) exactly once.
_DESIGN_READS = {
    "ell_matvec": 1,
    "ell_rmatvec": 1,
    "ell_colsum": 1,
    "fused_vgc": 1,
    "fused_hvp": 1,
    "fused_hdiag": 1,
}

_record_lock = threading.Lock()
_recorded = set()

# one-shot multidevice-fallback signal
_fallback_lock = threading.Lock()
_fallback_logged = False

_shard_local_depth = threading.local()


@contextlib.contextmanager
def shard_local():
    """Mark the dynamic extent as SHARD-LOCAL: the caller guarantees the
    traced code runs per-shard under ``shard_map`` (explicit-collective
    paths like ``parallel.distributed.shard_map_value_and_grad`` /
    ``hierarchical_value_and_grad`` and the entity-sharded GAME update),
    so per-shard arrays are device-local and a Pallas custom call keeps
    its semantics — the >1-device-mesh eligibility exclusion below is
    LIFTED here. Under plain GSPMD jit the exclusion stands: the
    partitioner would replicate the custom call and silently compute on
    whole-array shapes."""
    depth = getattr(_shard_local_depth, "value", 0)
    _shard_local_depth.value = depth + 1
    try:
        yield
    finally:
        _shard_local_depth.value = depth


def in_shard_local() -> bool:
    return getattr(_shard_local_depth, "value", 0) > 0


def _note_multidevice_fallback(devices: int) -> None:
    """One-shot log + always-counted metric when the >1-device-mesh rule
    routes a forced-Pallas contraction to XLA."""
    global _fallback_logged
    from photon_ml_tpu import obs
    from photon_ml_tpu.utils.logging import PhotonLogger

    obs.registry().inc("kernels.dispatch.multidevice_fallback")
    with _fallback_lock:
        if _fallback_logged:
            return
        _fallback_logged = True
    obs.emit_event(
        "kernels.dispatch.multidevice_fallback",
        cat="kernels",
        devices=devices,
        hint=(
            "GSPMD meshes route ELL contractions to XLA; shard_map "
            "paths keep Pallas via kernels.dispatch.shard_local()"
        ),
    )
    PhotonLogger(None).warn(
        f"sparse ELL contractions falling back to the XLA lowering "
        f"under a {devices}-device mesh (Pallas custom calls are "
        "not GSPMD-partitionable); explicit shard_map paths can "
        "keep the Pallas suite via kernels.dispatch.shard_local()"
    )


def kernel_mode() -> str:
    """The validated ``PHOTON_SPARSE_KERNEL`` value (default ``auto``)."""
    mode = os.environ.get(ENV_VAR, "auto").strip().lower() or "auto"
    if mode not in KERNEL_MODES:
        raise ValueError(
            f"{ENV_VAR}={mode!r}: expected one of {KERNEL_MODES}"
        )
    return mode


def interpret_mode() -> bool:
    """Pallas interpret mode everywhere but real TPU hardware — the
    tier-1 CPU gate proves kernel semantics through the interpreter."""
    return jax.default_backend() != "tpu"


def _vmem_cap() -> int:
    try:
        return int(os.environ.get(VMEM_CAP_ENV, _DEFAULT_VMEM_CAP))
    except ValueError:
        return _DEFAULT_VMEM_CAP


def accumulator_fits(d: int, itemsize: int) -> bool:
    """Would a (d,)-dense resident buffer (coefficients in, accumulator
    out) fit the per-buffer VMEM budget? Lane-pads d the way the kernels
    do before checking."""
    d_pad = -(-(d + 1) // 128) * 128
    return d_pad * itemsize <= _vmem_cap()


def active_mesh_devices() -> int:
    """Device count of the mesh ``jax.set_mesh`` installed (1 when
    none). Readable from inside a jit trace."""
    return max(1, jax.sharding.get_abstract_mesh().size)


def use_pallas(
    d: Optional[int] = None,
    itemsize: int = 4,
    n: Optional[int] = None,
    nnz_per_row: Optional[int] = None,
) -> bool:
    """Should the current op take the Pallas path? Trace-time static:
    mode, mesh context, and shape eligibility. Only ``pallas`` mode ever
    answers True (module docstring); a selected kernel that then fails
    to lower raises the compiler's error — nothing here catches it."""
    if kernel_mode() != "pallas":
        return False
    if n is not None and n == 0:
        return False  # nothing to tile; XLA returns the empty/zero result
    if nnz_per_row is not None and nnz_per_row == 0:
        return False
    if d is not None and not accumulator_fits(d, itemsize):
        return False
    devices = active_mesh_devices()
    if devices > 1 and not in_shard_local():
        # GSPMD would replicate a Pallas custom call (wrong results at
        # whole-array shapes), so sharded solves stay on XLA — logged
        # once and counted; shard_map'd paths that declared themselves
        # shard-local keep the kernels.
        _note_multidevice_fallback(devices)
        return False
    return True


def design_reads(kernel: str) -> int:
    """Design reads per pass of a kernel in this suite — the counted
    unit behind the fused passes' >=2-reads-per-iteration saving."""
    return _DESIGN_READS[kernel]


def record_kernel_cost(
    kernel: str,
    n: int,
    k: int,
    d: int,
    itemsize: int,
    flops_per_slot: float = 2.0,
    extra_bytes: float = 0.0,
) -> None:
    """Book one (kernel, shape) cost record into the shared cost book,
    once per key per process. Called from the kernel wrappers at trace
    time — host-side and cheap, so it is safe inside jit tracing.

    ``roofline_bytes`` is pinned to ``design_reads(kernel)`` times the
    stored design bytes (indices + values): the minimal HBM traffic of
    the pass, which is exactly what the fused kernels reduce and what
    span-level achieved-bytes/s should be measured against.
    """
    key = (kernel, n, k, d, itemsize)
    with _record_lock:
        if key in _recorded:
            return
        _recorded.add(key)
    try:
        from photon_ml_tpu.obs.xla_cost import cost_book

        slots = float(n) * float(k)
        design_bytes = slots * (4 + itemsize)  # int32 ids + payload
        reads = design_reads(kernel)
        cost_book().record(
            f"kernels.{kernel}",
            None,
            bucket=f"{n}x{k}x{d}",
            analytic_flops=flops_per_slot * slots,
            analytic_bytes=reads * design_bytes + extra_bytes,
            roofline_bytes=reads * design_bytes,
        )
    except Exception:
        # observability must never fail the kernel it observes
        with _record_lock:
            _recorded.discard(key)
