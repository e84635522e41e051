"""Share of the HBM-bandwidth roofline of a GAME job's passes when one
coordinate is a factored random effect: the bytes the algorithm must move
(``work_factored.py``) at the chip's peak bandwidth over the traced
device-busy time.  There is no hand-written kernel: the share is the
compiled update's.  Bandwidth-bound by construction (a slot of the shared-B
pass is 4 B a feature for 4 to 6 FLOPs at latent dimension 8 against a chip
that does 240 FLOPs in the time it moves a byte)."""
LAYER = "objective pass (kernels)"
UNIT = "%"
MOVES = "train.time_to_auc_s"


def read(run):
    from chipbench import work, work_factored

    per_job = work_factored.job(run.counts)
    if per_job is None or run.trace is None:
        return None
    return work.hbm_roofline_pct(
        per_job["bytes"] * run.counts["jobs"], run.trace["busy_s"], run.peaks
    )
