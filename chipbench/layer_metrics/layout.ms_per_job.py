"""Host wall of the design's hot/cold layout step, a job: the summed
durations of the program's ``glm.layout`` spans (``train_glm``'s split of a
plain padded-ELL design on the device, its two small blocking fetches
included, ending before ``glm.solve_path`` opens) of the window's jobs.
Nothing on a checkout whose ``train_glm`` has no such span."""
LAYER = "objective pass"
UNIT = "ms"
MOVES = "train.time_to_auc_s"


def read(run):
    from chipbench import program_spans

    value = program_spans.train_ms_per_job(run, ("glm.layout",))
    # a parent without the span books no such record: nothing, not zero
    return value or None
