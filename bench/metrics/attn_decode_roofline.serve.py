"""Roofline share of the Pallas kernels inside the decode-burst
executable: the attention FLOPs and bytes of every served decode step
(``bench/costs.py``: each step reads the K and V of the positions it can
see) against the kernels' device time, in %."""
from bench import costs


def read(rec):
    k = rec["trace"]["kernels"].get("jit_burst")
    if not k or k["device_s"] <= 0:
        return None
    w = rec["work"]
    return costs.roofline_share(w["attn_decode_flops"],
                                w["attn_decode_bytes"], k["device_s"],
                                rec["peak"])
