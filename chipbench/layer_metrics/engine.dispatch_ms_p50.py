"""Median duration of the window's ``serving.dispatch`` spans, one a batch:
the call into the bucket's compiled scorer, until it returns."""
LAYER = "engine"
UNIT = "ms"
MOVES = "serve.p95_ms"


def read(run):
    from chipbench import program_spans

    return program_spans.serve_span_ms(
        run, "serving.dispatch", 50, "engine.dispatch_ms_p50")
