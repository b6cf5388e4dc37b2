"""aggregate_ms_per_step.train: device milliseconds a training step
spends in aggregation: the operations under the program's
`engn.aggregate` scope, forward and backward (the RER kernels and the
backward's scatter-adds), over the runs of `jit_train_step` in the
traced window."""
from bench.metrics._stages import ms_per_step


def read(rec):
    return ms_per_step(rec, "engn.aggregate")
