"""extract_ms_per_step.train: device milliseconds a training step
spends in feature extraction: the operations under the program's
`engn.extract` scope, forward and backward (the backward's operations
keep their forward's scope), over the runs of `jit_train_step` in the
traced window."""
from bench.metrics._stages import ms_per_step


def read(rec):
    return ms_per_step(rec, "engn.extract")
