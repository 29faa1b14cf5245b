"""Roofline share of the Pallas kernels inside the prefill-chunk
executable: the attention FLOPs and bytes of the real prompt tokens, chunk
by chunk (``bench/costs.py``), against the kernels' device time, in %."""
from bench import costs


def read(rec):
    k = rec["trace"]["kernels"].get("jit_chunk")
    if not k or k["device_s"] <= 0:
        return None
    w = rec["work"]
    return costs.roofline_share(w["attn_prefill_flops"],
                                w["attn_prefill_bytes"], k["device_s"],
                                rec["peak"])
