"""FLOPs a GAME job with a factored random effect needs
(``work_factored.py``: shapes and the program's own counts, the shared-B
solve by its passes) over the job's wall over the chip's bf16 peak: the
share of the whole step that stays when a kernel is replaced.  Nothing on a
checkout whose factored update reports no passes."""
LAYER = "whole job"
UNIT = "%"
MOVES = "train.time_to_auc_s"


def read(run):
    from chipbench import work, work_factored

    per_job = work_factored.job(run.counts)
    wall = run.counts.get("window_wall_s")
    if per_job is None or not wall:
        return None
    return work.mfu_pct(per_job["flops"] * run.counts["jobs"], wall,
                        run.peaks, int(run.cell["chips"]))
