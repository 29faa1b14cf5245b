"""A toy architecture for the plug-in test (``test_bench_arch.py``): the
dense decoder of ``bench/arch/dense.py`` with one window of keys per layer
(``windows`` in the configuration; ``None`` attends to every earlier
position).  It is registered as ``bench.arch.toy`` for the test only.

The program has no windowed attention, so the toy's weights mute every
windowed layer's attention (its output projection is zero): the program,
attending to every position there, then serves what the toy's reference,
attending to the window, computes.  ``make_weights_unmuted`` leaves them
as the dense decoder draws them, and the two then part.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import reference as R
from bench.arch import Layer, dense

SIZE_KEYS = dense.SIZE_KEYS
active_matmul_params = dense.active_matmul_params
make_weights_unmuted = dense.make_weights


def layers(m: dict) -> list:
    return [Layer(m["n_heads"], m["n_kv_heads"], m["d_head"], w)
            for w in m["windows"]]


def make_weights(m: dict, dtype: str, seed: int):
    p = dense.make_weights(m, dtype, seed)
    muted = jnp.asarray([w is not None for w in m["windows"]])
    wo = p["blocks"]["attn"]["wo"]
    p["blocks"]["attn"]["wo"] = jnp.where(muted[:, None, None, None],
                                          jnp.zeros_like(wo), wo)
    return p


@functools.partial(jax.jit, static_argnames=("mk", "quant", "kv", "split"))
def served_gaps(mk, params, tokens, valid, quant=None, kv=None, split=None):
    """``dense.served_gaps`` with each layer's attention held to its
    window, layer by layer."""
    m = dict(mk)
    hy = dict(R.HYFT[m["softmax_impl"]])

    def hidden(quant):
        x = R.act_round(params["embed"]["table"][tokens].astype(jnp.float32),
                        quant)
        for i, w in enumerate(m["windows"]):
            lp = jax.tree.map(lambda a: a[i], params["blocks"])
            attn = functools.partial(R.hyft_splits, hy=hy, split=split,
                                     window=w)
            x = dense.block(m, lp, x, quant, kv, attn)
        return R.norm(m["norm"], params["final_norm"], x)

    ref = dense.logits(m, params, hidden(None))
    nxt = jnp.roll(tokens, -1)
    if quant is not None:
        nxt = jnp.argmax(dense.logits(m, params, hidden(quant), quant), -1)
    return R.gap_below_best(ref, nxt, valid)
