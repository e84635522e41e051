"""Executable-dispatch counting: how many XLA programs did a block run?

The device-resident-loop work (ROADMAP item 1) is judged in DISPATCHES:
a regularization path that used to pay one host round trip per lambda
must execute as ONE program, and a K-pass GAME superpass as
ceil(passes/K). Wall clocks cannot prove that on a timeshared CPU bench
host — the dispatch count can, and it is platform-invariant.

``count_dispatches()`` counts per-executable-name executions by
wrapping ``pxla.ExecuteReplicated.__call__`` — the Python layer every
pjit execution funnels through *when the C++ jit fast path is off*. The
fast path caches (executable, fastpath-data) pairs in C++ and re-calls
them without touching Python, so inside the context the installer (a)
patches ``_get_fastpath_data`` to return None — no NEW fast-path
entries — and (b) clears the C++ pjit caches — no PRE-EXISTING entries.
Compiled executables live in the Python-level caches, which are NOT
cleared: counting never forces a recompile (the zero-recompile
invariants stay provable under a counter; asserted in the tests).

Counting therefore slows the host path a little (every call goes
through Python). It is a measurement harness for tests and bench
probes, not something to leave installed around production traffic.

Counts are keyed by the executable's name — the jitted function's name
(``solve_path``, ``superpass``, ``one_pass``, ...) — so assertions can
target the program under test and ignore incidental eager-op dispatches
(slicing a stacked result, building an input array) that are asynchronous
decode work, not host->device round trips of the training loop.
"""

from __future__ import annotations

import contextlib
import fnmatch
import threading
from typing import Dict, Iterator

__all__ = ["DispatchCounts", "count_dispatches"]

_LOCK = threading.Lock()
_DEPTH = 0
_SAVED = {}


class DispatchCounts:
    """Per-executable-name dispatch counts observed inside one
    ``count_dispatches()`` window, plus assertion helpers."""

    def __init__(self) -> None:
        self.by_name: Dict[str, int] = {}

    def note(self, name: str) -> None:
        with _LOCK:
            self.by_name[name] = self.by_name.get(name, 0) + 1

    def total(self) -> int:
        return sum(self.by_name.values())

    def for_program(self, pattern: str) -> int:
        """Total dispatches of executables whose name matches ``pattern``
        (fnmatch; a bare name matches itself and, via ``*name*``, its
        jit-mangled variants)."""
        return sum(
            c
            for n, c in self.by_name.items()
            if fnmatch.fnmatch(n, pattern) or pattern in n
        )

    def assert_program(self, pattern: str, expected: int) -> None:
        """Assert the program matching ``pattern`` dispatched exactly
        ``expected`` times — the test-suite surface for the one-dispatch
        guarantees (N-lambda path = 1, K-pass superpass = ceil(P/K))."""
        got = self.for_program(pattern)
        if got != expected:
            raise AssertionError(
                f"expected {expected} dispatch(es) of {pattern!r}, "
                f"counted {got}; all programs: {self.snapshot()}"
            )

    def snapshot(self) -> Dict[str, int]:
        with _LOCK:
            return dict(self.by_name)


@contextlib.contextmanager
def count_dispatches() -> Iterator[DispatchCounts]:
    """Count every XLA executable dispatch inside the block, per program
    name. Reentrant (nested counters each see the block they wrap);
    never forces a recompile. CPU/TPU alike — the seam is backend-
    independent."""
    global _DEPTH
    from jax._src import pjit as _pjit
    from jax._src.interpreters import pxla as _pxla
    from jax._src.lib import xla_client as _xc

    counts = DispatchCounts()
    with _LOCK:
        _DEPTH += 1
        if _DEPTH == 1:
            _SAVED["call"] = _pxla.ExecuteReplicated.__call__
            _SAVED["fastpath"] = _pjit._get_fastpath_data
            _SAVED["listeners"] = []
        _SAVED["listeners"].append(counts)
        if _DEPTH == 1:
            orig_call = _SAVED["call"]

            def counted_call(self, *args):
                name = getattr(self, "name", "") or "<unnamed>"
                for c in list(_SAVED.get("listeners", ())):
                    c.note(name)
                return orig_call(self, *args)

            _pxla.ExecuteReplicated.__call__ = counted_call
            # no NEW C++ fast-path entries while counting...
            _pjit._get_fastpath_data = lambda *a, **k: None
    # ...and no PRE-EXISTING ones: clear the C++ pjit caches only — the
    # Python-level compiled-executable caches survive, so nothing
    # recompiles (outside the lock: cache eviction may run destructors)
    _xc._xla.PjitFunctionCache.clear_all()
    try:
        yield counts
    finally:
        with _LOCK:
            _DEPTH -= 1
            try:
                _SAVED["listeners"].remove(counts)
            except ValueError:
                pass
            if _DEPTH == 0:
                _pxla.ExecuteReplicated.__call__ = _SAVED.pop("call")
                _pjit._get_fastpath_data = _SAVED.pop("fastpath")
                _SAVED.pop("listeners", None)
