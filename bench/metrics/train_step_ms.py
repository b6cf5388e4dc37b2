"""train_step_ms: the window's wall time, ending when the last step's
state is ready, over the steps completed in it (host clock)."""


def read(rec):
    return rec.get("train_step_ms")
