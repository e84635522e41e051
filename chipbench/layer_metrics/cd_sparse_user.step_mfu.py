"""FLOPs a GAME job with an INDEX_MAP random effect over a sparse bag needs
(``work_sparse_user.py``: shapes and the program's own counts, the lanes'
solves by their passes) over the job's wall over the chip's bf16 peak: the
share of the whole step that stays when a kernel is replaced.  Nothing on a
checkout whose sparse updates report no passes."""
LAYER = "whole job"
UNIT = "%"
MOVES = "train.time_to_auc_s"


def read(run):
    from chipbench import work, work_sparse_user

    per_job = work_sparse_user.job(run.counts)
    wall = run.counts.get("window_wall_s")
    if per_job is None or not wall:
        return None
    return work.mfu_pct(per_job["flops"] * run.counts["jobs"], wall,
                        run.peaks, int(run.cell["chips"]))
