"""Host time inside the program's blocking device-to-host reads, a job: the
summed durations of its ``game.fetch`` spans (each runs until the value is
on the host, so it holds the device time the read waits out).  ``train_glm``
reads nothing back inside the program (0): its model fetch is the harness's
own ``fetch_model`` span."""
LAYER = "host fetch and dispatch (device idle)"
UNIT = "ms"
MOVES = "train.time_to_auc_s"


def read(run):
    from chipbench import program_spans

    return program_spans.train_ms_per_job(run, ("game.fetch", "glm.fetch"))
