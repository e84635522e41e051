"""Share of the HBM-bandwidth roofline of an entity-sharded GAME job's
passes: the bytes the algorithm must move A CHIP (``work_sharded.py``: the
update bodies' and the row exchange's, over all chips, divided by the
cell's chips) at the chip's peak bandwidth over the traced device-busy time
(a mean over the device planes).  ``work.hbm_roofline_pct`` knows nothing of
chips, so the division is here.  There is no hand-written kernel: the share
is the compiled update body's and the exchange's gathers'."""
LAYER = "objective pass (kernels)"
UNIT = "%"
MOVES = "train.time_to_auc_s"


def read(run):
    from chipbench import work, work_sharded

    per_job = work_sharded.job(run.counts)
    if per_job is None or run.trace is None:
        return None
    return work.hbm_roofline_pct(
        per_job["bytes"] * run.counts["jobs"] / int(run.cell["chips"]),
        run.trace["busy_s"], run.peaks,
    )
