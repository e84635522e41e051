"""How late sends left against the schedule (99th percentile): a starved
generator must not be read as a fast server."""
LAYER = "load generator (benchmark)"
UNIT = "ms"
MOVES = "serve.p95_ms"


def read(run):
    return run.counts.get("late_ms_p99")
