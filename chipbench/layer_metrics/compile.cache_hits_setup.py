"""Compile requests of set-up that the persistent cache answered."""
LAYER = "compile"
UNIT = "hits"
MOVES = "setup_s"


def read(run):
    return run.counts.get("cache_hits_setup")
