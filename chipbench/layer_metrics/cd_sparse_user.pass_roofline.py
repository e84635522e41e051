"""Share of the HBM-bandwidth roofline of a GAME job's passes when a
coordinate is a random effect over a sparse bag through INDEX_MAP: the
bytes the algorithm must move (``work_sparse_user.py``: a bucket pass books
its held rows' stored entries and its lanes' vectors at the bucket's
width) at the chip's peak bandwidth over the traced device-busy time.
There is no hand-written kernel: the share is the compiled update's."""
LAYER = "objective pass (kernels)"
UNIT = "%"
MOVES = "train.time_to_auc_s"


def read(run):
    from chipbench import work, work_sparse_user

    per_job = work_sparse_user.job(run.counts)
    if per_job is None or run.trace is None:
        return None
    return work.hbm_roofline_pct(
        per_job["bytes"] * run.counts["jobs"], run.trace["busy_s"], run.peaks
    )
