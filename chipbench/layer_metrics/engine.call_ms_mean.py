"""``ServingStats.device_ms``: featurize + dispatch + the per-batch fetch of
the scores, mean over the window's batches."""
LAYER = "engine"
UNIT = "ms"
MOVES = "serve.p95_ms"


def read(run):
    return run.counts.get("engine_call_ms_mean")
