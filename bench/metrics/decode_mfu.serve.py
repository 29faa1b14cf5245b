"""Model FLOPs of the decode steps served (``bench/costs.py``: the blocks
and the unembedding for every decoded token, attention over the positions
each can see) over the device time of the decode-burst executable
(``jit_burst``) times the chip's bf16 peak, in %."""


def read(rec):
    m = rec["trace"]["modules"].get("jit_burst")
    flops = rec["work"]["decode_model_flops"]
    if not m or m["device_s"] <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (m["device_s"] * rec["peak"]["flops_bf16"])
