"""FLOPs the algorithm needs for a job over the job's wall over the chip's
bf16 peak.  Far under 1% for these bandwidth-bound GLMs: it is the bound
that stays when a kernel is replaced, not a target."""
LAYER = "whole job"
UNIT = "%"
MOVES = "train.time_to_auc_s"


def read(run):
    from chipbench import work

    per_job = work.job(run.config, run.counts)
    wall = run.counts.get("window_wall_s")
    if per_job is None or not wall:
        return None
    return work.mfu_pct(per_job["flops"] * run.counts["jobs"], wall,
                        run.peaks, int(run.cell["chips"]))
