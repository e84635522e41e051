"""Rows passed through the objective (rows x evaluations, or rows x Newton
iterations of each coordinate) by all jobs of the window, over its wall."""
LAYER = "objective pass"
UNIT = "rows/s"
MOVES = "train.time_to_auc_s"


def read(run):
    from chipbench import work

    per_job = work.job(run.config, run.counts)
    wall = run.counts.get("window_wall_s")
    if per_job is None or not wall:
        return None
    return per_job["rows_passed"] * run.counts["jobs"] / wall
