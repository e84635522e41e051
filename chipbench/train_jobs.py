"""The timed window of traffic kind ``train_jobs``, shared by the training
tasks: identical jobs back to back until ``--seconds`` have passed, the job
in flight then runs to its end.  A traced window holds ``trace_jobs`` jobs."""

from __future__ import annotations

import time


def window(state, run, one_job):
    """``one_job(run, state["train"]) -> (fetched model, what the program
    says of it)``; keeps every fetched model and the last job's record."""
    limit = int(run.traffic["trace_jobs"]) if run.tracing else None
    t0 = time.perf_counter()
    while True:
        model, says = one_job(run, state["train"])
        state["jobs"].append(model)
        state["last"] = says
        if limit is None and time.perf_counter() - t0 >= run.seconds:
            break
        if limit is not None and len(state["jobs"]) >= limit:
            break
    wall = time.perf_counter() - t0
    n = len(state["jobs"])
    run.attempted = n
    # the whole window over everything done in it: never one job, never a
    # median of jobs
    run.end_to_end["train.time_to_auc_s"] = wall / n
    run.counts.update(jobs=n, window_wall_s=wall)


def count(state, run, one_job):
    """One more job, outside the profiler, under the program's dispatch
    counter (it slows the host path, so it never runs inside a window)."""
    from photon_ml_tpu.obs.dispatch_count import count_dispatches

    with count_dispatches() as counts:
        one_job(run, state["train"])
    run.counts["dispatches_per_job"] = counts.total()
