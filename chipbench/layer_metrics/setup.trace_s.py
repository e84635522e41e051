"""Host wall of jaxpr tracing at set-up: the program's ``xla.trace`` spans
(jax's ``jaxpr_trace_duration``, one a traced function, nested where an
inner ``jit`` traces inside an outer one) that end before the window, their
union on each thread.  Paid on every run, cache or no cache: the persistent
cache's key is the lowered module.  Nothing on a checkout without the span."""
LAYER = "compile"
UNIT = "s"
MOVES = "setup_s"


def read(run):
    from chipbench import setup_spans

    return setup_spans.setup_seconds(run, ("xla.trace",))
