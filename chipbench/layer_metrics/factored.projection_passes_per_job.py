"""Passes over the factored coordinate's padded design that the solves of
the shared projection B made in one job: TRON's outer iterations + 1 + CG
iterations (a value/gradient or a Hessian-vector product each), summed over
every inner iteration of every update, from the program's own tracker
(``CoordinateUpdateRecord.inner_iterations``).  The work of a job stands on
it, so it has to read the same on every seed."""
LAYER = "solver loop"
UNIT = "passes"
MOVES = "train.time_to_auc_s"


def read(run):
    return run.counts.get("projection_passes_per_job")
