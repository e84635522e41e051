"""Host wall of the backend compiles at set-up, or of the persistent-cache
loads where the cache answered (``cache_hit``): the program's
``xla.compile`` spans (jax's ``backend_compile_duration``, which wraps
``compile_or_get_cached``) that end before the window, their union on each
thread.  Nothing on a checkout without the span."""
LAYER = "compile"
UNIT = "s"
MOVES = "setup_s"


def read(run):
    from chipbench import setup_spans

    return setup_spans.setup_seconds(run, ("xla.compile",))
