"""95th percentile, over the window's answered requests, of ``flush -
enqueued`` in their ``serving.request`` records: the queue and the
coalescing window, up to the start of the batch's score call."""
LAYER = "batching"
UNIT = "ms"
MOVES = "serve.p95_ms"


def read(run):
    from chipbench import program_spans

    return program_spans.serve_request_ms(
        run, "enqueued", "flush", 95, "batcher.wait_ms_p95")
