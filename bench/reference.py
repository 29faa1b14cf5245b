"""The plain reference's shared parts, in ``jax.numpy`` at float32 and
``highest`` matmul precision: what every architecture of ``bench/arch/``
scores against, so that each is held to the same written-out Hyft.

It imports nothing of the program.  The attention softmax is the one the
configuration states (``softmax_impl``): exact, or the Hyft16 datapath of
the paper (fixed-point scores, exp by shift-add and field assembly,
fixed-point sums, division by log-subtraction), written out below from the
paper's description.  Hyft's partial results are merged where the
configuration's deployment merges them, as the paper's tree of Hyft units
does: over the KV cache's pages in serving (``hyft_splits``: each page a
split with its own max and sum), over blocks of keys in training
(``hyft_online``: a running max and sum).  Hyft16's own approximation is
far larger than bfloat16's rounding, so a reference with an exact softmax
could not tell the program from a lower-precision one.

For serving, K and V are rounded to the cache format the configuration
states (``kv_round``; fp2fx8: int8 with a scale per position and head)
before attention reads them, as a deployment of that configuration stores
them.

``quant="fp8"`` is the control: the same reference computed in float8,
the next precision below the bfloat16 the configurations compute in, with
the program's own split between compute and storage: the residual stream
(``act_round``) and every matrix product's operands (``mm``) in e4m3
(weights per output channel, activations per row), a product's incoming
gradient in e5m2, products and norms accumulated in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
I32 = jnp.int32
HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
E5M2_MAX = 57344.0
NEG = -3.0e38          # a masked score; fixed-point conversion saturates it


def sizes_key(m: dict) -> tuple:
    """The scalar and list entries of a configuration (lists as tuples),
    hashable, for the jitted references to take as a static argument."""
    def frozen(v):
        return tuple(map(frozen, v)) if isinstance(v, list) else v
    return tuple(sorted((k, frozen(v)) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool, list))))


def _round(x, axes, dtype, top):
    """Round ``x`` to the float8 ``dtype`` with one scale per slice over
    ``axes`` (the slice's largest magnitude maps to ``top``)."""
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / top
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(dtype).astype(F32) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _fp8_einsum(spec, w_axes, a, w):
    """An einsum as float8 training computes it: operands rounded to e4m3
    (activations per row, weights per output channel), the incoming
    gradient to e5m2 per row, every product accumulated in float32."""
    return _fp8_fwd(spec, w_axes, a, w)[0]


def _fp8_fwd(spec, w_axes, a, w):
    aq = _round(a, (-1,), jnp.float8_e4m3fn, E4M3_MAX)
    wq = _round(w, w_axes, jnp.float8_e4m3fn, E4M3_MAX)
    return jnp.einsum(spec, aq, wq, precision=HIGHEST), (aq, wq)


def _fp8_bwd(spec, w_axes, res, g):
    aq, wq = res
    gq = _round(g, (-1,), jnp.float8_e5m2, E5M2_MAX)
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST),
                     aq, wq)
    return vjp(gq)


_fp8_einsum.defvjp(_fp8_fwd, _fp8_bwd)


def mm(spec, a, w, quant, w_axes):
    """einsum at float32/highest, or with ``quant="fp8"`` as float8
    training computes it (``_fp8_einsum``)."""
    a, w = a.astype(F32), w.astype(F32)
    if quant == "fp8":
        return _fp8_einsum(spec, tuple(w_axes), a, w)
    return jnp.einsum(spec, a, w, precision=HIGHEST)


def act_round(x, quant):
    """The residual stream as the compute dtype stores it: with ``fp8``,
    e4m3 with one scale per row (the gradient passes unrounded here; it is
    rounded where it enters a matrix product)."""
    if quant != "fp8":
        return x
    q = _round(x, (-1,), jnp.float8_e4m3fn, E4M3_MAX)
    return x + jax.lax.stop_gradient(q - x)


def kv_round(x, kv):
    """K or V (S, H, D) as the configuration's cache stores it: for
    ``fp2fx8``, int8 with one scale per (position, head), the row's largest
    magnitude mapping to 127."""
    if kv != "fp2fx8":
        return x
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    s = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.rint(x / s), -127, 127) * s


# --- Hyft softmax ---------------------------------------------------------------
#
# Hyft16 (the paper's 16-bit configuration): a score z becomes a fixed-point
# raw, round(z * 2**frac) saturated to ``total`` bits.  exp(d) of d = z - max
# (d <= 0) is 2**t with t = d * log2(e) by shift-add (d + d/2 - d/16), and
# 2**t, for u = ceil(t) and v = t - u in (-1, 0], is 2**u * (1 + v/2): the
# exponent field u - 1 and the mantissa 1 + v assembled as a float.  The
# exponentials are summed in fixed point (truncated to ``acc`` fractional
# bits), and a / b is taken in the log domain from mantissas truncated to
# ``mant`` bits: 2**(ea - eb) * (1 + ma - mb), renormalised when ma < mb.

HYFT = {"hyft16": (("acc", 14), ("frac", 10), ("mant", 10), ("total", 16))}


def _pow2(k):
    """2.0 ** k for int32 ``k``, written as the float's exponent field;
    zero below the smallest normal float32."""
    bits = (jnp.clip(k, -126, 127) + 127) << 23
    return jnp.where(k < -126, F32(0),
                     jax.lax.bitcast_convert_type(bits.astype(I32), F32))


def _fx(z, hy):
    top = 2 ** (hy["total"] - 1)
    return jnp.clip(jnp.rint(z * F32(2.0 ** hy["frac"])), -top,
                    top - 1).astype(I32)


def _exp_fields(d, hy):
    """exp of fixed-point ``d`` <= 0 as float fields (e, m): the value is
    2**e * (1 + m / 2**mant)."""
    F = hy["frac"]
    t = jnp.minimum(d + (d >> 1) - (d >> 4), 0)
    u = -((-t) >> F)                       # ceil(t), t in fixed point
    v = t - (u << F)                       # in (-2**F, 0]
    whole = v == 0
    return jnp.where(whole, u, u - 1), jnp.where(whole, 0, (1 << F) + v)


def _value(e, m, hy):
    return ((1 << hy["mant"]) + m).astype(F32) * _pow2(e - hy["mant"])


def _fields(x, hy):
    """Exponent and mantissa of float32 ``x`` >= 0, the mantissa truncated
    to ``mant`` bits."""
    bits = jax.lax.bitcast_convert_type(x.astype(F32), I32)
    return (((bits >> 23) & 0xFF) - 127,
            (bits >> (23 - hy["mant"])) & ((1 << hy["mant"]) - 1))


def _log_div(ea, ma, eb, mb, hy):
    F = hy["mant"]
    diff = ma - mb
    neg = (diff < 0).astype(I32)
    return ((1 << F) + diff + (neg << F)).astype(F32) * _pow2(
        ea - eb - neg - F)


def _fixed(x, hy):
    """Truncation to the adder tree's ``acc`` fractional bits."""
    s = F32(2.0 ** hy["acc"])
    return jnp.floor(x * s) / s


def _finalize(acc, l, hy):
    """acc / l by log-subtraction, elementwise; ``l`` > 0 broadcasts."""
    r = _log_div(*_fields(jnp.abs(acc), hy), *_fields(l, hy), hy)
    return jnp.where(acc == 0, F32(0), jnp.where(acc < 0, -r, r))


def exact(q, k, v):
    """Causal attention with an exact softmax; q, k, v (S, H, D)."""
    S, _, D = q.shape
    pos = jnp.arange(S)
    z = jnp.einsum("qhe,khe->hqk", q, k, precision=HIGHEST) * D ** -0.5
    z = jnp.where(pos[:, None] >= pos[None, :], z, -jnp.inf)
    return jnp.einsum("hqk,khe->qhe", jax.nn.softmax(z, -1), v,
                      precision=HIGHEST)


def hyft_splits(q, k, v, hy, split, rows=128, window=None):
    """Causal Hyft attention with the keys taken in splits of ``split``
    positions: each split's max, exponentials and sum are its own, and the
    splits are merged by the Hyft exp of each split's max below the row's,
    their fixed-point sums and their products with V added, then one
    division.  With ``window`` a query sees only the last ``window``
    positions (its own included); a split wholly outside them adds nothing.
    q, k, v (S, H, D); queries ``rows`` at a time."""
    S, H, D = q.shape
    ns = S // split
    ks, vs = k.reshape(ns, split, H, D), v.reshape(ns, split, H, D)
    kpos = jnp.arange(S).reshape(ns, split)

    def block(args):
        qb, qpos = args
        z = jnp.einsum("qhd,jshd->hqjs", qb, ks,
                       precision=HIGHEST) * D ** -0.5
        kp, qp = kpos[None, None], qpos[None, :, None, None]
        seen = kp <= qp
        if window is not None:
            seen &= kp > qp - window
        z = jnp.where(seen, z, NEG)
        zr = _fx(z, hy)
        mj = jnp.max(zr, -1, keepdims=True)
        p = _value(*_exp_fields(zr - mj, hy), hy)
        lj = jnp.sum(_fixed(p, hy), -1)                     # (H, rows, ns)
        accj = jnp.einsum("hqjs,jshd->hqjd", p, vs, precision=HIGHEST)
        mj = mj[..., 0]
        alpha = _value(*_exp_fields(mj - jnp.max(mj, -1, keepdims=True),
                                    hy), hy)
        l = jnp.sum(_fixed(lj * alpha, hy), -1, keepdims=True)
        acc = jnp.sum(accj * alpha[..., None], -2)          # (H, rows, D)
        return jnp.transpose(_finalize(acc, l, hy), (1, 0, 2))
    out = jax.lax.map(block, (q.reshape(S // rows, rows, H, D),
                              jnp.arange(S).reshape(S // rows, rows)))
    return out.reshape(S, H, D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def hyft_online(q, k, v, hy, block):
    """Causal Hyft attention over blocks of ``block`` keys in order, with a
    running max and fixed-point sum rescaled by the Hyft exp of the max's
    rise; its gradient recomputes each probability from the final max and
    sum, by log-subtraction.  q, k, v (S, H, D); ``hy`` as items."""
    return _online_fwd(q, k, v, hy, block)[0]


def _online_fwd(q, k, v, hy, block):
    hy = dict(hy)
    S, H, D = q.shape
    pos = jnp.arange(S)
    z = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * D ** -0.5
    zr = _fx(jnp.where(pos[:, None] >= pos[None, :], z, NEG), hy)
    m = jnp.full((H, S, 1), -2 ** (hy["total"] - 1), I32)
    l = jnp.zeros((H, S, 1), F32)
    acc = jnp.zeros((H, S, D), F32)
    for b in range(0, S, block):
        zb = zr[..., b:b + block]
        m_new = jnp.maximum(m, jnp.max(zb, -1, keepdims=True))
        p = _value(*_exp_fields(zb - m_new, hy), hy)
        alpha = _value(*_exp_fields(m - m_new, hy), hy)
        l = _fixed(l * alpha, hy) + jnp.sum(_fixed(p, hy), -1, keepdims=True)
        acc = acc * alpha + jnp.einsum("hqk,khd->hqd", p, v[b:b + block],
                                       precision=HIGHEST)
        m = m_new
    o = _finalize(acc, l, hy)
    return jnp.transpose(o, (1, 0, 2)), (q, k, v, o, zr, m, l)


def _online_bwd(hy, block, res, do):
    hy = dict(hy)
    q, k, v, o, zr, m, l = res
    sc = q.shape[-1] ** -0.5
    p = _log_div(*_exp_fields(zr - m, hy), *_fields(l, hy), hy)
    dot = jnp.transpose(do, (1, 0, 2)).astype(F32)          # (H, S, D)
    dv = jnp.einsum("hqk,hqd->khd", p, dot, precision=HIGHEST)
    dp = jnp.einsum("hqd,khd->hqk", dot, v, precision=HIGHEST)
    ds = p * (dp - jnp.sum(dot * o, -1, keepdims=True))
    dq = jnp.einsum("hqk,khd->qhd", ds, k, precision=HIGHEST) * sc
    dk = jnp.einsum("hqk,qhd->khd", ds, q, precision=HIGHEST) * sc
    return dq, dk, dv


hyft_online.defvjp(_online_fwd, _online_bwd)


def attention(m: dict, split: int | None = None, block: int | None = None):
    """The causal attention ``m`` states, as (q, k, v) -> o over (S, H, D):
    exact, or Hyft merged over splits of ``split`` keys (serving) or
    online over blocks of ``block`` keys (training)."""
    impl = m["softmax_impl"]
    if impl == "exact":
        return exact
    if impl not in HYFT:
        raise ValueError(f"the reference has no softmax {impl!r}")
    hy = HYFT[impl]
    if split:
        return lambda q, k, v: hyft_splits(q, k, v, dict(hy), split)
    return lambda q, k, v: hyft_online(q, k, v, hy, block)


def norm(kind, p, x):
    """RMSNorm (``rms``), LayerNorm (``ln``) or non-parametric LayerNorm
    (``np_ln``) of ``x`` over its last axis, with the parameters ``p``."""
    mu = jnp.mean(x, -1, keepdims=True)
    if kind == "rms":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
        return y * p["scale"].astype(F32)
    y = (x - mu) * jax.lax.rsqrt(jnp.mean((x - mu) ** 2, -1, keepdims=True)
                                 + 1e-5)
    if kind == "np_ln":
        return y
    return y * p["scale"].astype(F32) + p["bias"].astype(F32)


def rope(x, pos, theta):
    """x (S, H, D), pos (S,): rotate the two halves of each head."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos[:, None, None].astype(F32) * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def act(m, x):
    """The activation ``m`` states: SiLU, or GELU in its tanh form."""
    if m["act"] == "silu":
        return x * jax.nn.sigmoid(x)
    if m["act"] == "gelu":
        return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654
                                          * (x + 0.044715 * x ** 3)))
    raise ValueError(m["act"])


def gap_below_best(ref, nxt, valid):
    """Per position, the gap by which the logit of ``nxt`` (S,) lies below
    the best of ``ref`` (S, V); 0 where ``valid`` (S,) is false."""
    picked = jnp.take_along_axis(ref, nxt[:, None], -1)[:, 0]
    gap = jnp.max(ref, -1) - picked
    return jnp.where(valid, gap, 0.0)


# --- training -----------------------------------------------------------------

def lm_loss_and_grad(one, params, batch, z_loss, rows):
    """Mean next-token NLL over the mask plus the z-loss on log Z, and its
    gradient, for the logits ``one(params, tokens)`` (S, V) of one
    sequence: the batch is taken ``rows`` sequences at a time so that the
    float32 logits fit, and the blocks' sums are added."""
    mask = batch["mask"].astype(F32)
    denom = jnp.maximum(jnp.sum(mask), 1.0)

    def block_sum(p, blk):
        lg = jax.vmap(lambda tokens: one(p, tokens))(blk["tokens"])
        lse = jax.nn.logsumexp(lg, -1)
        gold = jnp.take_along_axis(lg, blk["targets"][..., None], -1)[..., 0]
        msk = blk["mask"].astype(F32)
        return (jnp.sum((lse - gold) * msk)
                + z_loss * jnp.sum((lse * msk) ** 2)) / denom

    blocks = jax.tree.map(
        lambda x: x.reshape((-1, rows) + x.shape[1:]), batch)

    def body(acc, blk):
        val, g = jax.value_and_grad(block_sum)(params, blk)
        return (acc[0] + val, jax.tree.map(jnp.add, acc[1], g)), None
    zero = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
    (val, grads), _ = jax.lax.scan(body, (jnp.zeros((), F32), zero), blocks)
    return val, grads


def lr_scale(step, warmup, total, final_frac=0.1):
    """Linear warm-up from 0, then cosine down to ``final_frac``."""
    step = jnp.asarray(step, F32)
    warm = jnp.minimum(step / max(warmup, 1), 1.0)
    prog = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
    return warm * (final_frac + (1 - final_frac) * 0.5
                   * (1 + jnp.cos(jnp.pi * prog)))


def adamw_step(opt, params, grads, step, hp):
    """Clip by global norm, then one AdamW update at float32 with decoupled
    weight decay on every leaf; ``step`` counts from 0."""
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, hp["grad_clip"] / jnp.maximum(gn, 1e-12))
    grads = jax.tree.map(lambda g: g * clip, grads)
    t = step + 1
    b1, b2 = hp["b1"], hp["b2"]
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, opt["m"], grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, opt["v"], grads)
    lr = hp["lr"] * lr_scale(step, hp["warmup_steps"], hp["total_steps"])

    def upd(p, mm, vv):
        mh, vh = mm / (1 - b1 ** t), vv / (1 - b2 ** t)
        return p - lr * (mh / (jnp.sqrt(vh) + hp["eps"])
                         + hp["weight_decay"] * p)
    return jax.tree.map(upd, params, m, v), {"m": m, "v": v}, grads
