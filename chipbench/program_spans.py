"""The program's own spans, cut to a run's window: what the per-layer
metrics of source ``program_span`` read.

The program keeps every span it closes in a bounded ring
(``photon_ml_tpu.obs.recent_spans()``): tuples ``(name, start, end,
span_id, parent_id, thread, attrs)`` on the ``time.perf_counter()`` clock,
the clock of ``Run.spans``.  A reader here keeps the records that END
inside the window and returns ``None`` (never a partial number) where

- the program has no such ring (a checkout from before it), or
- the ring has dropped records that may have belonged to the window
  (``obs.spans_dropped()`` is above zero and the oldest record left ended
  after the window began), or
- no record of the wanted name is there.

Windows.  Training: from the start of the first ``job`` harness span to the
end of the ``run.counts["jobs"]``-th (a traced run books one more job after
the window, under the dispatch counter; it is not the window's).  Serving:
from the start of ``wait_generator`` plus the traffic's ``lead_in_s`` to its
end.
"""

from __future__ import annotations

NAME, START, END, SPAN_ID, PARENT_ID, THREAD, ATTRS = range(7)

TRAIN_ROOTS = ("glm.solve_path", "game.cd.run")


def ring():
    """(records oldest first, dropped count), or None where the program
    keeps no ring."""
    try:
        from photon_ml_tpu import obs
    except ImportError:
        return None
    recent = getattr(obs, "recent_spans", None)
    dropped = getattr(obs, "spans_dropped", None)
    if recent is None or dropped is None:
        return None
    return recent(), int(dropped())


def training_window(run):
    """(start, end, jobs) of the measured jobs, or None."""
    jobs = int(run.counts.get("jobs") or 0)
    spans = [s for s in run.spans if s[0] == "job"][:jobs]
    if not jobs or len(spans) < jobs:
        return None
    return spans[0][1], spans[-1][2], jobs


def serving_window(run):
    """(start, end) of the measured part of the generator's run, or None."""
    spans = [s for s in run.spans if s[0] == "wait_generator"]
    if not spans:
        return None
    _, start, end = spans[-1]
    return start + float(run.traffic.get("lead_in_s", 0.0)), end


def in_window(lo, hi):
    """The ring's records that end inside [lo, hi], or None where the ring
    cannot vouch for the window."""
    got = ring()
    if got is None:
        return None
    records, dropped = got
    if dropped and (not records or records[0][END] >= lo):
        return None
    return [r for r in records if lo <= r[END] <= hi]


def _train(run):
    """(records of the window, its root records, jobs) or None."""
    window = training_window(run)
    if window is None:
        return None
    lo, hi, jobs = window
    records = in_window(lo, hi)
    if records is None:
        return None
    roots = [r for r in records if r[NAME] in TRAIN_ROOTS]
    if not roots:
        return None
    return records, roots, jobs


def train_ms_per_job(run, names):
    """Summed duration of the records called one of ``names``, a job, in
    milliseconds; 0 where the window's jobs left none of them; None where
    the window's jobs left no root span at all."""
    got = _train(run)
    if got is None:
        return None
    records, _, jobs = got
    total = sum(r[END] - r[START] for r in records if r[NAME] in names)
    return 1e3 * total / jobs


def train_self_ms_per_job(run):
    """Host time of the jobs' root spans that is neither an enqueue nor a
    wait, a job: a root's duration less what the ``*.dispatch`` and
    ``*.fetch`` spans under it cover (at any depth, on its thread), in
    milliseconds.  The tape decode is host code of this kind and stays
    in, so dispatch + fetch + self is the root's whole duration."""
    from chipbench.reduce_trace import _union

    got = _train(run)
    if got is None:
        return None
    records, roots, jobs = got
    total = 0.0
    for root in roots:
        lo, hi = root[START], root[END]
        covered = [
            (max(r[START], lo), min(r[END], hi))
            for r in records
            if r[THREAD] == root[THREAD] and lo <= r[START] and r[END] <= hi
            and r[NAME].endswith((".dispatch", ".fetch"))
        ]
        total += (hi - lo) - sum(b - a for a, b in _union(covered))
    return 1e3 * total / jobs


def serve_records(run, name):
    """The window's records called ``name``, or None."""
    window = serving_window(run)
    if window is None:
        return None
    records = in_window(*window)
    if records is None:
        return None
    return [r for r in records if r[NAME] == name] or None


def serve_span_ms(run, name, q, count_as):
    """Exact ``q``-th percentile of the durations of the window's ``name``
    spans (one a batch), in milliseconds; their count goes to
    ``run.counts[count_as + ".n"]``."""
    records = serve_records(run, name)
    if records is None:
        return None
    return _percentile(
        run, [r[END] - r[START] for r in records], q, count_as
    )


def serve_request_ms(run, lo_stamp, hi_stamp, q, count_as):
    """Exact ``q``-th percentile, over the window's answered requests, of
    the time between two stamps of their ``serving.request`` records, in
    milliseconds.  A stamp is ``received`` (the record's start),
    ``replied`` (its end), or an attribute: ``enqueued``, ``flush``,
    ``scored``."""
    records = serve_records(run, "serving.request")
    if records is None:
        return None

    def stamp(r, which):
        if which == "received":
            return r[START]
        if which == "replied":
            return r[END]
        return r[ATTRS][which]

    gaps = [
        stamp(r, hi_stamp) - stamp(r, lo_stamp)
        for r in records if r[ATTRS].get("ok") and "flush" in r[ATTRS]
    ]
    if not gaps:
        return None
    return _percentile(run, gaps, q, count_as)


def _percentile(run, seconds, q, count_as):
    import numpy as np

    run.counts[count_as + ".n"] = len(seconds)
    return 1e3 * float(np.percentile(np.asarray(seconds, np.float64), q))
