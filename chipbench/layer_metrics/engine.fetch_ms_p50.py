"""Median duration of the window's ``serving.fetch`` spans, one a batch:
from the compiled call's return until the scores are on the host."""
LAYER = "engine"
UNIT = "ms"
MOVES = "serve.p95_ms"


def read(run):
    from chipbench import program_spans

    return program_spans.serve_span_ms(
        run, "serving.fetch", 50, "engine.fetch_ms_p50")
