"""Mixture-of-Experts block: top-k router + capacity-bounded einsum dispatch.

Expert weights are stacked on a leading ``experts`` axis and sharded over the
``model`` mesh axis (expert parallelism); the dispatch/combine einsums
contract over (tokens x experts x capacity), so GSPMD inserts the
all-to-all.  The router softmax goes through the registry — i.e. **the Hyft
accelerator also serves the router**, the paper's technique applied at a
second site (DESIGN.md §5).

The router uses top-k *after* the full softmax (Mixtral/Grok convention:
softmax over all experts, renormalize over the chosen k).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.registry import get_softmax
from repro.models.layers import ACTIVATIONS, param

F32 = jnp.float32


def moe_init(key, cfg, dtype):
    ks = jax.random.split(key, 4)
    dm, dff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    p = {
        "router": param(ks[0], (dm, E), ("embed", "experts_dim"), F32),
        "w_up": param(ks[1], (E, dm, dff), ("experts", "embed", "mlp"), dtype),
        "w_down": param(ks[2], (E, dff, dm), ("experts", "mlp", "embed"),
                        dtype, scale=dff ** -0.5),
    }
    if cfg.mlp_gated:
        p["w_gate"] = param(ks[3], (E, dm, dff), ("experts", "embed", "mlp"), dtype)
    return p


def moe_apply(p, x, cfg):
    """x: (B, S, dm) -> (out, aux) with load-balancing aux loss.

    Tokens are regrouped into fixed-size dispatch groups (Switch/MaxText
    style) so the one-hot dispatch tensor is O(tokens * E * cap_per_group)
    instead of O(tokens * E * cap_per_sequence).
    """
    B0, S0, dm = x.shape
    G = min(getattr(cfg, "moe_group", 512), B0 * S0)
    x = x.reshape(-1, G, dm)
    B, S, _ = x.shape
    E, k = cfg.n_experts, cfg.moe_top_k
    cap = max(1, int(cfg.capacity_factor * S * k / E))
    act = ACTIVATIONS[cfg.act]

    logits = jnp.einsum("bsd,de->bse", x.astype(F32), p["router"])
    probs = get_softmax(cfg.softmax_impl)(logits).astype(F32)  # Hyft router
    gate_vals, gate_idx = jax.lax.top_k(probs, k)              # (B,S,k)
    gate_vals = gate_vals / (jnp.sum(gate_vals, -1, keepdims=True) + 1e-9)

    # capacity-bounded one-hot dispatch (Switch-style, deterministic)
    onehot = jax.nn.one_hot(gate_idx, E, dtype=F32)            # (B,S,k,E)
    pos = jnp.cumsum(onehot.reshape(B, S * k, E), axis=1).reshape(B, S, k, E)
    pos = (pos * onehot - 1.0).astype(jnp.int32)               # slot per (token,choice)
    keep = (pos >= 0) & (pos < cap)
    slot = jax.nn.one_hot(jnp.where(keep, pos, -1), cap, dtype=F32)  # (B,S,k,E,cap)

    disp = jnp.einsum("bske,bskec->bsec", onehot * keep, slot)  # (B,S,E,cap)
    comb = jnp.einsum("bsk,bske,bskec->bsec", gate_vals, onehot * keep, slot)

    xe = jnp.einsum("bsec,bsd->becd", disp.astype(x.dtype), x)  # (B,E,cap,dm)
    up = jnp.einsum("becd,edf->becf", xe, p["w_up"].astype(x.dtype))
    if "w_gate" in p:
        gate = jnp.einsum("becd,edf->becf", xe, p["w_gate"].astype(x.dtype))
        h = act(gate) * up
    else:
        h = act(up)
    ye = jnp.einsum("becf,efd->becd", h, p["w_down"].astype(x.dtype))
    y = jnp.einsum("bsec,becd->bsd", comb.astype(x.dtype), ye)

    # Switch-style load-balancing loss
    density = jnp.mean(onehot[..., 0, :], axis=(0, 1)) if k == 1 else \
        jnp.mean(jnp.sum(onehot, axis=2), axis=(0, 1)) / k
    router_mean = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(density * router_mean)
    return y.reshape(B0, S0, dm), aux
