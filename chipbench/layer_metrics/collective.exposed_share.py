"""Share of the device-busy time that the collective operations hold: the
self time of ``all-to-all``, ``all-reduce``, ``all-gather``,
``reduce-scatter`` and ``collective-permute`` (with their ``-start`` and
``-done`` forms) on the ``XLA Ops`` lines of the traced window, a mean over
the device planes like the busy time it is divided by.  Self time on the
operations' own line: while a collective runs there nothing else does, so
this is the exposed part.  Nothing where no such operation ran (a one-chip
cell, or a checkout whose program has none)."""
LAYER = "collectives (entity-sharded descent)"
UNIT = "%"
MOVES = "train.time_to_auc_s"

KINDS = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter",
         "collective-permute")


def is_collective(name: str) -> bool:
    return any(
        name == kind or name.startswith((kind + ".", kind + "-start",
                                         kind + "-done"))
        for kind in KINDS
    )


def collective_seconds(trace):
    """Summed self time of the collective operations, or None where there
    is none."""
    found = [s for name, s in trace["device_ops"] if is_collective(name)]
    return sum(found) if found else None


def read(run):
    if run.trace is None or not run.trace.get("busy_s"):
        return None
    held = collective_seconds(run.trace)
    if held is None:
        return None
    return 100.0 * held / run.trace["busy_s"]
