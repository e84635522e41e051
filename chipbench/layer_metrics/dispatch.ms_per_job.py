"""Host time inside the calls into the compiled programs, a job: the summed
durations of the program's ``glm.dispatch`` / ``game.dispatch`` spans (each
runs until the call returns: enqueue, not completion) of the window's jobs."""
LAYER = "path and CD dispatch"
UNIT = "ms"
MOVES = "train.time_to_auc_s"


def read(run):
    from chipbench import program_spans

    return program_spans.train_ms_per_job(
        run, ("glm.dispatch", "game.dispatch"))
