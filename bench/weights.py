"""Weights made by the benchmark from ``--seed``, on the device, in one
jitted call, in the layout the program reads.

Both the program and the plain reference are given these arrays; neither
makes its own.  The distribution is the benchmark's, chosen so that a
random model behaves like a trained one where the check needs it: logits of
order one (an embedding of standard deviation d_model**-0.5, tied to the
unembedding), so that greedy decoding wanders over the vocabulary instead
of repeating its input, and every projection at 1/sqrt(fan-in).  Norm
scales and biases are drawn around 1 and 0, so a fault in either shows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _norm(key, m: dict, layers: int | None, dtype):
    """The program's parameters of one norm ({} for OLMo's non-parametric
    LayerNorm), stacked over ``layers`` when given."""
    if m["norm"] == "np_ln":
        return {}
    lead = () if layers is None else (layers,)
    k1, k2 = jax.random.split(key)
    p = {"scale": (1.0 + _normal(k1, lead + (m["d_model"],), 0.1,
                                 jnp.float32)).astype(dtype)}
    if m["norm"] == "ln":
        p["bias"] = _normal(k2, lead + (m["d_model"],), 0.1, dtype)
    return p


def make(m: dict, dtype: str, seed: int):
    """The parameter tree of the dense decoder ``m`` (a configuration's
    sizes) in ``dtype``, drawn from ``seed`` in one jitted call."""
    dt = jnp.dtype(dtype)
    L, d, h, kv, dh, ff, v = (m["n_layers"], m["d_model"], m["n_heads"],
                              m["n_kv_heads"], m["d_head"], m["d_ff"],
                              m["vocab"])

    def build(key):
        ks = iter(jax.random.split(key, 16))
        attn = {"wq": _normal(next(ks), (L, d, h, dh), d ** -0.5, dt),
                "wk": _normal(next(ks), (L, d, kv, dh), d ** -0.5, dt),
                "wv": _normal(next(ks), (L, d, kv, dh), d ** -0.5, dt),
                "wo": _normal(next(ks), (L, h, dh, d), (h * dh) ** -0.5, dt)}
        mlp = {"w_up": _normal(next(ks), (L, d, ff), d ** -0.5, dt),
               "w_down": _normal(next(ks), (L, ff, d), ff ** -0.5, dt)}
        if m["mlp_gated"]:
            mlp["w_gate"] = _normal(next(ks), (L, d, ff), d ** -0.5, dt)
        p = {"embed": {"table": _normal(next(ks), (v, d), d ** -0.5, dt)},
             "final_norm": _norm(next(ks), m, None, dt),
             "blocks": {"norms": {"pre_attn": _norm(next(ks), m, L, dt),
                                  "pre_mlp": _norm(next(ks), m, L, dt)},
                        "attn": attn, "mlp": mlp}}
        if not m["tie_embeddings"]:
            p["unembed"] = {"table": _normal(next(ks), (v, d), d ** -0.5, dt)}
        return p

    return jax.jit(build)(key_of(seed))


def key_of(seed: int, *salt: int):
    """A JAX key for any whole ``seed``, however large."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    for v in (seed >> 32, *salt):
        key = jax.random.fold_in(key, v & 0xFFFFFFFF)
    return key


def check_layout(params, program_shapes) -> None:
    """Raise unless ``params`` has exactly the program's tree, shapes and
    dtypes (``program_shapes`` from ``jax.eval_shape`` of its init)."""
    mine = jax.tree.map(lambda x: (x.shape, str(x.dtype)), params)
    theirs = jax.tree.map(lambda x: (x.shape, str(x.dtype)), program_shapes)
    if mine != theirs:
        raise ValueError(f"benchmark weights do not match the program's "
                         f"layout:\n{mine}\n!=\n{theirs}")
