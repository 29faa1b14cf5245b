"""Roofline share of the fused Hyft attention kernels of the training step
(the Pallas kernels inside ``jit_step_fn``): causal attention's forward
(Q.K, P.V) and backward (dP, dV, dQ, dK; no recomputation) FLOPs, and
bytes of Q, K, V, O read or written once forward and Q, K, V, O, dO read
and dQ, dK, dV written once backward, in bf16 (``bench/costs.py``), over
the kernels' device time, in %."""
from bench import costs


def read(rec):
    k = rec["trace"]["kernels"].get("jit_step_fn")
    if not k or k["device_s"] <= 0:
        return None
    m, w = rec["model"], rec["work"]
    steps = rec["trace"]["modules"]["jit_step_fn"]["calls"] \
        / rec["trace"]["chips"]
    pairs = costs.causal_pairs(w["seq_len"])
    flops = (costs.attention_flops(m["n_heads"], m["d_head"], pairs)
             + costs.attention_bwd_flops(m["n_heads"], m["d_head"], pairs))
    tensor = w["seq_len"] * m["n_heads"] * m["d_head"] * 2
    nbytes = 12 * tensor
    per_step = w["global_batch"] * m["n_layers"]
    return costs.roofline_share(flops * per_step * steps,
                                nbytes * per_step * steps,
                                k["device_s"] / rec["trace"]["chips"],
                                rec["peak"])
