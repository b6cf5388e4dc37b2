"""train_mfu: the operations one training step requires (bench/cost.py),
times the steps of the window, over the window's seconds, the chips and
their bf16 peak, in percent."""


def read(rec):
    if rec.get("job") != "train" or not rec.get("peak"):
        return None
    flops = rec["cost"]["flops_per_step"] * rec["steps"]
    peak = rec["peak"]["bf16_flops_per_s"] * rec["chips"]
    return 100.0 * flops / (rec["window_s"] * peak)
