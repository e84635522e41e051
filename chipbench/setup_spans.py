"""The program's own spans of set-up: what the per-layer metrics that split
``setup_s`` read.

Set-up is everything before the window: the ring's records
(``program_spans.ring()``) that END before the start of the first ``job``
harness span of the window (the warm-up job is set-up's; the task clears
its harness spans).  A quantity is the union of the wanted records'
intervals on each thread, summed over threads, never their plain sum: jax
traces an inner ``jit`` inside the outer one's trace, so ``xla.trace``
records nest, and a retro-stamped record's ``parent_id`` cannot show it.

``None``, never a partial sum, where

- the program keeps no ring (a checkout from before it),
- the ring has dropped any record (it can no longer vouch for set-up), or
- no record of one of the wanted names is there (a checkout without the
  span).
"""

from __future__ import annotations

from chipbench import program_spans
from chipbench.program_spans import END, NAME, START, THREAD
from chipbench.reduce_trace import _union


def setup_seconds(run, names):
    """Seconds of set-up covered by the records called one of ``names``:
    per thread the union of their intervals, summed over threads."""
    jobs = [start for name, start, _ in run.spans if name == "job"]
    got = program_spans.ring()
    if not jobs or got is None:
        return None
    records, dropped = got
    if dropped:
        return None
    cut = jobs[0]
    wanted = [r for r in records if r[NAME] in names and r[END] < cut]
    if {r[NAME] for r in wanted} != set(names):
        return None
    by_thread = {}
    for r in wanted:
        by_thread.setdefault(r[THREAD], []).append((r[START], r[END]))
    return sum(
        hi - lo for intervals in by_thread.values()
        for lo, hi in _union(intervals)
    )
