"""Median duration of the window's ``serving.featurize`` spans, one a
batch: request objects to padded arrays, entity translation, the bucket's
executable looked up."""
LAYER = "engine"
UNIT = "ms"
MOVES = "serve.p95_ms"


def read(run):
    from chipbench import program_spans

    return program_spans.serve_span_ms(
        run, "serving.featurize", 50, "engine.featurize_ms_p50")
