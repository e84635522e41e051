"""FLOPs a multi-coordinate GAME job needs (``work_multi.py``: shapes and
the solver's own iteration counts, per coordinate) over the job's wall over
the chip's bf16 peak: the share of the whole step that stays when a kernel
is replaced.  ``work.job`` knows two coordinates by name and reads nothing
for this task, so the cell brings its own count."""
LAYER = "whole job"
UNIT = "%"
MOVES = "train.time_to_auc_s"


def read(run):
    from chipbench import work, work_multi

    per_job = work_multi.job(run.counts)
    wall = run.counts.get("window_wall_s")
    if per_job is None or not wall:
        return None
    return work.mfu_pct(per_job["flops"] * run.counts["jobs"], wall,
                        run.peaks, int(run.cell["chips"]))
