"""Compile requests (backend compiles and persistent-cache loads, the
program's ``obs.compile_events``) during the window.  Expected 0."""
LAYER = "compile"
UNIT = "compiles"
MOVES = "serve.p95_ms"


def read(run):
    return run.counts.get("compiles_in_window")
