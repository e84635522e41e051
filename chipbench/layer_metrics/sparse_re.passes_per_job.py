"""Passes over the compact design that the per-lane TRON solves of the
INDEX_MAP random effects made in one job: a bucket's passes are the most
any of its real lanes made (outer iterations + 1 value/gradient, and one
Hessian-vector product a CG iteration: its batched solve ran that many),
summed over the buckets and the updates, from the program's own record
(``CoordinateUpdateRecord.inner_iterations``, which ``game.sparse_re.passes``
counts at ``materialize()``).  The work of a job stands on it, so it has to
read the same on every seed.  Nothing on a checkout without the record."""
LAYER = "solver loop"
UNIT = "passes"
MOVES = "train.time_to_auc_s"


def read(run):
    return run.counts.get("sparse_passes_per_job")
