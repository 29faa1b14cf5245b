"""Model FLOPs of the real prompt tokens served (``bench/costs.py``
``prefill_flops``: the blocks for every prompt token, causal attention over
each prompt, the unembedding of one position per prompt) over the device
time of the prefill-chunk executable times the chip's bf16 peak, in %."""


def read(rec):
    m = rec["trace"]["modules"].get("jit_chunk")
    flops = rec["work"]["prefill_model_flops"]
    if not m or m["device_s"] <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (m["device_s"] * rec["peak"]["flops_bf16"])
