"""Host time of a job's root span (``glm.solve_path`` / ``game.cd.run``)
that is neither an enqueue nor a wait, a job: its duration less what the
``*.dispatch`` and ``*.fetch`` spans under it cover.  The tape decode
(``game.decode`` / ``glm.decode``) is host code of this kind and counts."""
LAYER = "host fetch and dispatch (device idle)"
UNIT = "ms"
MOVES = "train.time_to_auc_s"


def read(run):
    from chipbench import program_spans

    return program_spans.train_self_ms_per_job(run)
