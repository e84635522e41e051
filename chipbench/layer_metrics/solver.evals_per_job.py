"""Objective evaluations of one GLM solve (L-BFGS's own count), or coordinate
updates of one GAME run (CD iterations x coordinates).  Repeats exactly."""
LAYER = "solver loop"
UNIT = "evals"
MOVES = "train.time_to_auc_s"


def read(run):
    return run.counts.get("evals_per_job")
