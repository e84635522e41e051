"""Share of the HBM-bandwidth roofline of the scorer: bytes each answered row
must read (its features and one table row; work.py) at the chip's peak
bandwidth, over the traced device-busy time."""
LAYER = "engine (kernels)"
UNIT = "%"
MOVES = "serve.p95_ms"


def read(run):
    from chipbench import work

    rows = run.counts.get("rows_answered")
    if run.trace is None or not rows:
        return None
    one = work.serve_row(run.config["fixed_dim"], run.config["user_dim"])
    return work.hbm_roofline_pct(one["bytes"] * rows, run.trace["busy_s"],
                                 run.peaks)
