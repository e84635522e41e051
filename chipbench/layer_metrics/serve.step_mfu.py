"""FLOPs per scored row x rows answered over the window over the chip's bf16
peak.  Tiny (the host sets this cell's numbers); all digits are printed."""
LAYER = "whole request path"
UNIT = "%"
MOVES = "serve.p95_ms"


def read(run):
    from chipbench import work

    rows = run.counts.get("rows_answered")
    if not rows:
        return None
    one = work.serve_row(run.config["fixed_dim"], run.config["user_dim"])
    return work.mfu_pct(one["flops"] * rows, run.counts["window_wall_s"],
                        run.peaks, int(run.cell["chips"]))
