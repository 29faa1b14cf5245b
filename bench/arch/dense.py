"""The dense causal decoder the configurations ``olmo-1b`` and ``bert-base``
describe: its sizes, weights, plain reference and work counts.

The reference follows the published descriptions (OLMo: non-parametric
LayerNorm, SwiGLU, RoPE, tied embeddings; the BERT-base proxy: LayerNorm
with scale and bias, GELU, tied embeddings) with the departures the
configuration files list under ``assumed``: causal next-token training with
RoPE for the BERT proxy, GELU in its tanh form, and RoPE rotating the two
halves of each head.  Attention, the cache's rounding of K and V and the
float8 control are the shared ones of ``bench/reference.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import reference as R
from bench.arch import Layer
from bench.weights import key_of, normal

F32 = jnp.float32

SIZE_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_head",
             "d_ff", "vocab", "act", "mlp_gated", "norm", "rope_theta",
             "max_seq", "tie_embeddings", "softmax_impl")


def layers(m: dict) -> list:
    """Every layer attends causally to every earlier position."""
    return [Layer(m["n_heads"], m["n_kv_heads"], m["d_head"])] * m["n_layers"]


def active_matmul_params(m: dict) -> int:
    """Weights of one block that take part in a matrix product: every one,
    for every token."""
    d, h, kv, dh, ff = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["d_head"], m["d_ff"])
    attn = d * h * dh * 2 + d * kv * dh * 2
    mlp = d * ff * (3 if m["mlp_gated"] else 2)
    return attn + mlp


# --- weights -----------------------------------------------------------------

def _norm_params(key, m: dict, layers: int | None, dtype):
    """The program's parameters of one norm ({} for OLMo's non-parametric
    LayerNorm), stacked over ``layers`` when given."""
    if m["norm"] == "np_ln":
        return {}
    lead = () if layers is None else (layers,)
    k1, k2 = jax.random.split(key)
    p = {"scale": (1.0 + normal(k1, lead + (m["d_model"],), 0.1,
                                jnp.float32)).astype(dtype)}
    if m["norm"] == "ln":
        p["bias"] = normal(k2, lead + (m["d_model"],), 0.1, dtype)
    return p


def make_weights(m: dict, dtype: str, seed: int):
    """The parameter tree of the dense decoder ``m`` (a configuration's
    sizes) in ``dtype``, drawn from ``seed`` in one jitted call."""
    dt = jnp.dtype(dtype)
    L, d, h, kv, dh, ff, v = (m["n_layers"], m["d_model"], m["n_heads"],
                              m["n_kv_heads"], m["d_head"], m["d_ff"],
                              m["vocab"])

    def build(key):
        ks = iter(jax.random.split(key, 16))
        attn = {"wq": normal(next(ks), (L, d, h, dh), d ** -0.5, dt),
                "wk": normal(next(ks), (L, d, kv, dh), d ** -0.5, dt),
                "wv": normal(next(ks), (L, d, kv, dh), d ** -0.5, dt),
                "wo": normal(next(ks), (L, h, dh, d), (h * dh) ** -0.5, dt)}
        mlp = {"w_up": normal(next(ks), (L, d, ff), d ** -0.5, dt),
               "w_down": normal(next(ks), (L, ff, d), ff ** -0.5, dt)}
        if m["mlp_gated"]:
            mlp["w_gate"] = normal(next(ks), (L, d, ff), d ** -0.5, dt)
        p = {"embed": {"table": normal(next(ks), (v, d), d ** -0.5, dt)},
             "final_norm": _norm_params(next(ks), m, None, dt),
             "blocks": {"norms": {"pre_attn": _norm_params(next(ks), m, L, dt),
                                  "pre_mlp": _norm_params(next(ks), m, L, dt)},
                        "attn": attn, "mlp": mlp}}
        if not m["tie_embeddings"]:
            p["unembed"] = {"table": normal(next(ks), (v, d), d ** -0.5, dt)}
        return p

    return jax.jit(build)(key_of(seed))


# --- reference ---------------------------------------------------------------

def block(m, lp, x, quant, kv, attn):
    """One pre-norm block over one sequence x (S, d); K and V are rounded
    to the cache format ``kv`` before ``attn`` (see
    ``bench.reference.attention``) reads them."""
    S = x.shape[0]
    pos = jnp.arange(S)
    h = R.norm(m["norm"], lp["norms"]["pre_attn"], x)
    q = R.rope(R.mm("sd,dhe->she", h, lp["attn"]["wq"], quant, (0,)), pos,
               m["rope_theta"])
    k = R.rope(R.mm("sd,dhe->she", h, lp["attn"]["wk"], quant, (0,)), pos,
               m["rope_theta"])
    v = R.mm("sd,dhe->she", h, lp["attn"]["wv"], quant, (0,))
    k, v = R.kv_round(k, kv), R.kv_round(v, kv)
    g = m["n_heads"] // m["n_kv_heads"]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    o = attn(q, k, v)
    x = R.act_round(x + R.mm("she,hed->sd", o, lp["attn"]["wo"], quant,
                             (0, 1)), quant)
    h = R.norm(m["norm"], lp["norms"]["pre_mlp"], x)
    up = R.mm("sd,df->sf", h, lp["mlp"]["w_up"], quant, (0,))
    if m["mlp_gated"]:
        a = R.act(m, R.mm("sd,df->sf", h, lp["mlp"]["w_gate"], quant,
                          (0,))) * up
    else:
        a = R.act(m, up)
    return R.act_round(x + R.mm("sf,fd->sd", a, lp["mlp"]["w_down"], quant,
                                (0,)), quant)


def hidden(m, params, tokens, quant=None, kv=None, attn=R.exact):
    """Final-normed hidden states (S, d) of one sequence ``tokens`` (S,),
    one layer at a time (weights are cast to float32 inside the layer)."""
    x = R.act_round(params["embed"]["table"][tokens].astype(F32), quant)

    def body(x, lp):
        return block(m, lp, x, quant, kv, attn), None
    x, _ = jax.lax.scan(body, x, params["blocks"])
    return R.norm(m["norm"], params["final_norm"], x)


def logits(m, params, h, quant=None):
    table = params["embed" if m["tie_embeddings"] else "unembed"]["table"]
    return R.mm("sd,vd->sv", h, table, quant, (1,))


@functools.partial(jax.jit, static_argnames=("mk", "quant", "kv", "split"))
def served_gaps(mk, params, tokens, valid, quant=None, kv=None, split=None):
    """For one sequence ``tokens`` (S,) = prompt + served tokens (padded),
    the gap by which each served token's float32 logit lies below the
    float32 reference's best; ``valid`` (S,) marks the positions whose next
    token is a served one.  Attention merges Hyft over splits of ``split``
    cache positions.  With ``quant`` the control's own first choice is
    scored instead of the served token."""
    m = dict(mk)
    attn = R.attention(m, split=split)
    h = hidden(m, params, tokens, kv=kv, attn=attn)
    ref = logits(m, params, h)                       # (S, V) float32
    nxt = jnp.roll(tokens, -1)
    if quant is not None:
        hq = hidden(m, params, tokens, quant, kv, attn)
        nxt = jnp.argmax(logits(m, params, hq, quant), -1)
    return R.gap_below_best(ref, nxt, valid)


@functools.partial(jax.jit, static_argnames=("mk", "rows", "quant", "block"))
def loss_and_grad(mk, params, batch, z_loss, rows, quant=None, block=None):
    """Mean next-token NLL over the mask plus the z-loss on log Z, as the
    configuration's training objective states it, and its gradient: the
    batch is taken ``rows`` sequences at a time so that the float32 logits
    fit, and the blocks' sums are added.  Attention runs Hyft online over
    blocks of ``block`` keys."""
    m = dict(mk)
    attn = R.attention(m, block=block)

    def one(p, tokens):
        return logits(m, p, hidden(m, p, tokens, quant, attn=attn), quant)
    return R.lm_loss_and_grad(one, params, batch, z_loss, rows)
