"""95th percentile, over the window's answered requests, of ``replied -
scored`` in their ``serving.request`` records: from the batch's scores in
hand to this request's reply flushed (the future, the in-order writer
thread, ``json.dumps``, the socket write)."""
LAYER = "wire"
UNIT = "ms"
MOVES = "serve.p95_ms"


def read(run):
    from chipbench import program_spans

    return program_spans.serve_request_ms(
        run, "scored", "replied", 95, "wire.reply_ms_p95")
