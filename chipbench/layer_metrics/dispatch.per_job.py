"""XLA programs dispatched by one job, counted by the program's own
``obs.dispatch_count`` around one extra job after the traced window."""
LAYER = "path and CD dispatch"
UNIT = "dispatches"
MOVES = "train.time_to_auc_s"


def read(run):
    return run.counts.get("dispatches_per_job")
