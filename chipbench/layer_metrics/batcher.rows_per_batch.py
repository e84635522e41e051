"""Rows per flushed micro-batch over the window (``ServingStats``)."""
LAYER = "batching"
UNIT = "rows"
MOVES = "serve.p95_ms"


def read(run):
    return run.counts.get("rows_per_batch")
