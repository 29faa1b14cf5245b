"""Model FLOP/s utilization of the training step: forward plus backward
FLOPs per token (``bench/costs.py`` ``train_flops_per_token``, no
recomputation) times the tokens of the steps traced, over the device time
of the step executable (``jit_step_fn``) times the chips times the chip's
bf16 peak, in %."""


def read(rec):
    m = rec["trace"]["modules"].get("jit_step_fn")
    if not m or m["device_s"] <= 0:
        return None
    w = rec["work"]
    steps = m["calls"] / rec["trace"]["chips"]
    per_chip_s = m["device_s"] / rec["trace"]["chips"]
    return 100.0 * w["flops_per_step"] * steps / (
        per_chip_s * w["chips"] * rec["peak"]["flops_bf16"])
