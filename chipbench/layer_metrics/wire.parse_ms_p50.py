"""Median, over the window's answered requests, of ``enqueued - received``
in their ``serving.request`` records: the line in hand to the request in
the batcher's queue (strip, JSON parse, request object, admission)."""
LAYER = "wire"
UNIT = "ms"
MOVES = "serve.p95_ms"


def read(run):
    from chipbench import program_spans

    return program_spans.serve_request_ms(
        run, "received", "enqueued", 50, "wire.parse_ms_p50")
