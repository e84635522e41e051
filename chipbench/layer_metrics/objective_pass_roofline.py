"""Share of the HBM-bandwidth roofline of the objective passes: the bytes the
algorithm must move (work.py, shapes and the solver's iteration counts only)
at the chip's peak bandwidth, over the traced device-busy time.  These passes
are bandwidth-bound by construction (see work.py), so this is their roofline."""
LAYER = "objective pass (kernels)"
UNIT = "%"
MOVES = "train.time_to_auc_s"


def read(run):
    from chipbench import work

    per_job = work.job(run.config, run.counts)
    if per_job is None or run.trace is None:
        return None
    return work.hbm_roofline_pct(
        per_job["bytes"] * run.counts["jobs"], run.trace["busy_s"], run.peaks
    )
