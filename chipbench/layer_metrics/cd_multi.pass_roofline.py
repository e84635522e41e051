"""Share of the HBM-bandwidth roofline of a multi-coordinate GAME job's
passes: the bytes the algorithm must move (``work_multi.py``) at the chip's
peak bandwidth over the traced device-busy time.  Bandwidth-bound by
construction, as ``work.py`` argues for the two-coordinate job."""
LAYER = "objective pass (kernels)"
UNIT = "%"
MOVES = "train.time_to_auc_s"


def read(run):
    from chipbench import work, work_multi

    per_job = work_multi.job(run.counts)
    if per_job is None or run.trace is None:
        return None
    return work.hbm_roofline_pct(
        per_job["bytes"] * run.counts["jobs"], run.trace["busy_s"], run.peaks
    )
