"""agg_kernel_roofline.train: the RER aggregation kernels' share of
their roofline in a training step.

The kernels' time is the device time of their operations in the trace
(the Pallas calls of kernels/rer_gather and kernels/rer_spmm).  Their
required time is, for each forward aggregate of each step the device
ran in the traced window (runs of the `jit_train_step` module), the
larger of its operations over the bf16 peak and its bytes over the HBM
bandwidth, counted from the graph's real entries (bench/cost.py), not
from padded slots.  Nothing is read where no such kernel ran.
"""
from bench import cost, trace_reduce

# the labels the RER kernels show in a TPU trace (bench/trace_reduce.py)
KERNELS = r"^kernel .*(packed_spmm|blocked_spmm)"
STEP_MODULE = "jit_train_step"


def read(rec):
    tr, peak = rec.get("trace"), rec.get("peak")
    if rec.get("job") != "train" or not tr or not peak:
        return None
    kernel_s = trace_reduce.op_seconds(tr, KERNELS)
    steps = tr["modules"].get(STEP_MODULE, 0)
    if kernel_s <= 0 or steps == 0:
        return None
    c = rec["cost"]
    need = 0.0
    for width in c["aggregate_widths"]:
        agg = cost.aggregate_cost(c["entries"], c["n"], width)
        need += cost.min_time_s(agg["flops"], agg["bytes"], peak)
    return 100.0 * steps * need / kernel_s
