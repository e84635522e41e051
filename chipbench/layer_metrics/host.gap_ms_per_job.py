"""Device-idle time inside the traced jobs, per job (the breakdown's
``idle_gaps`` say under which harness span it fell)."""
LAYER = "host fetch and dispatch (device idle)"
UNIT = "ms"
MOVES = "train.time_to_auc_s"


def read(run):
    if run.trace is None or not run.counts.get("jobs"):
        return None
    idle = run.trace["window_s"] - run.trace["busy_s"]
    return 1e3 * idle / run.counts["jobs"]
