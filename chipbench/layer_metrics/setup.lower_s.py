"""Host wall of lowering to StableHLO at set-up: the program's ``xla.lower``
spans (jax's ``jaxpr_to_mlir_module_duration``) that end before the window,
their union on each thread.  Nothing on a checkout without the span."""
LAYER = "compile"
UNIT = "s"
MOVES = "setup_s"


def read(run):
    from chipbench import setup_spans

    return setup_spans.setup_seconds(run, ("xla.lower",))
