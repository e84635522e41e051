"""``ServingStats.request_ms`` (enqueue -> result: queue wait, coalescing
window, engine call), mean over the window.  The histogram's own quantiles
are 32% wide buckets, so the mean (exact sum over count) is read instead."""
LAYER = "batching"
UNIT = "ms"
MOVES = "serve.p95_ms"


def read(run):
    return run.counts.get("server_request_ms_mean")
