"""Host wall of the bucketed random-effect design builds of set-up: the
summed durations of the program's ``game.design`` spans, one a random
effect (grouping, the reservoir sample under the active cap, the bucket
split, the padded tensors put on the device).  Nothing on a checkout
without the span."""
LAYER = "random-effect design"
UNIT = "s"
MOVES = "setup_s"


def read(run):
    spans = run.counts.get("design_spans") or []
    if not spans:
        return None
    return sum(seconds for seconds, _ in spans)
