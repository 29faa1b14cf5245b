"""Attention: GQA/MHA/cross, pluggable softmax, KV cache, three fwd modes.

Modes (``AttnMode``):
  unfused  — QK^T -> registry softmax (hyft/exact/...) -> PV.  The
             paper-faithful training path: the softmax VJP is the
             accelerator's reused DIV/MUL datapath (custom_vjp in core),
             while the surrounding matmuls stay on the MXU.
  chunked  — lax.scan over KV chunks with online Hyft (max,sum,acc) carry;
             the pure-JAX twin of the fused Pallas kernel.  Lowerable in the
             multi-pod dry-run (Pallas can't lower to the CPU backend) and
             differentiable via a recompute-based custom VJP (flash-style
             backward using the saved row stats).  This is the beyond-paper
             memory-roofline lever for long sequences.
  kernel   — the Pallas flash kernels: compiled by Mosaic on a TPU backend,
             run by the Pallas interpreter on CPU (the tests).

Sequence-parallel decode (``sp_decode_attention``) implements the paper's
L1/L2 Hyft tree *across devices*: each model-axis shard computes local
(max, fixed-sum, acc) Hyft stats over its KV-cache slice; a pmax/psum pair
merges them — 2 scalars + one (D,)-vector per row over ICI instead of
all-gathering the scores.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import numerics as nm
from repro.core.hyft import HyftConfig
from repro.core.registry import get_softmax, hyft_config_for
from repro.kernels.flash_attention import hyft_alpha, hyft_finalize
from repro.models.layers import Param, param

F32 = jnp.float32
I32 = jnp.int32
NEG_BIG = -3.0e38


def attn_init(key, cfg, dtype):
    ks = jax.random.split(key, 4)
    dm, hq, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    p = {
        "wq": param(ks[0], (dm, hq, dh), ("embed", "heads", "head_dim"), dtype),
        "wk": param(ks[1], (dm, hkv, dh), ("embed", "kv_heads", "head_dim"), dtype),
        "wv": param(ks[2], (dm, hkv, dh), ("embed", "kv_heads", "head_dim"), dtype),
        "wo": param(ks[3], (hq, dh, dm), ("heads", "head_dim", "embed"), dtype,
                    scale=(hq * dh) ** -0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = Param(jnp.zeros((hq, dh), dtype), ("heads", "head_dim"))
        p["bk"] = Param(jnp.zeros((hkv, dh), dtype), ("kv_heads", "head_dim"))
        p["bv"] = Param(jnp.zeros((hkv, dh), dtype), ("kv_heads", "head_dim"))
    return p


def qkv_proj(p, x, kv_x, cfg, positions, kv_positions):
    """x: (B,S,dm) -> q (B,Hq,S,D); kv_x -> k,v (B,Hkv,Sk,D), rope'd."""
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhe->bshe", kv_x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhe->bshe", kv_x, p["wv"].astype(x.dtype))
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.rope_theta:
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, kv_positions, cfg.rope_theta)
    # -> (B, H, S, D)
    return (q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3))


def _rope(x, positions, theta):
    from repro.models.layers import apply_rope
    return apply_rope(x, positions, theta)


def out_proj(p, o):
    """o: (B,H,S,D) -> (B,S,dm)."""
    return jnp.einsum("bhsd,hde->bse", o, p["wo"].astype(o.dtype))


# --------------------------------------------------------------------------
# mode 1: unfused (paper-faithful)
# --------------------------------------------------------------------------


def unfused_attention(q, k, v, softmax_impl: str, *, causal: bool,
                      q_offset=0, kv_len_mask=None):
    """q (B,Hq,Sq,D), k/v (B,Hkv,Sk,D); softmax over full score rows."""
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    g = Hq // Hkv
    qg = q.reshape(B, Hkv, g, Sq, D)
    z = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(F32), k.astype(F32)) * (D ** -0.5)
    if causal:
        qi = q_offset + jax.lax.broadcasted_iota(I32, (Sq, Sk), 0)
        ki = jax.lax.broadcasted_iota(I32, (Sq, Sk), 1)
        z = jnp.where(qi >= ki, z, NEG_BIG)
    if kv_len_mask is not None:  # (B, Sk) bool — decode cache validity
        z = jnp.where(kv_len_mask[:, None, None, None, :], z, NEG_BIG)
    p = get_softmax(softmax_impl)(z).astype(F32)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(F32))
    return o.reshape(B, Hq, Sq, D).astype(q.dtype)


# --------------------------------------------------------------------------
# mode 2: chunked online-Hyft (pure JAX; scan over KV chunks) + custom VJP
# --------------------------------------------------------------------------


def _hyft_chunk_stats(z, cfg: HyftConfig, m_run):
    """One KV chunk: Hyft stages 1-2 against running max. Returns
    (m_new raw, alpha fp32, addend-sum fp32@acc-grid, p fp32)."""
    z_raw = nm.fp2fx(z, cfg.frac_bits, cfg.total_bits)
    zsub = z_raw[..., :: cfg.step] if cfg.step > 1 else z_raw
    blk_max = jnp.max(zsub, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_run, blk_max)
    e, m = nm.exp_unit(z_raw - m_new, cfg.frac_bits, cfg.mant_bits)
    addend = nm.expfloat_to_fx(e, m, cfg.mant_bits, cfg.acc_bits)
    l_blk = jnp.sum(addend, axis=-1, keepdims=True)
    alpha = hyft_alpha(m_run - m_new, cfg)
    p = ((1 << cfg.mant_bits) + m).astype(F32) * nm.pow2_float(e - cfg.mant_bits)
    return m_new, alpha, l_blk, p


# stage-3 finalize is shared with the fused kernels (one arithmetic for every
# online mode: chunked, fused, split-K decode, sequence-parallel)
_hyft_finalize = hyft_finalize


def _mask_chunks(kv_len_mask, B, nk, chunk):
    """(B, Sk) float mask -> (nk, B, chunk) scan slices; a 3D (B, Sq, Sk)
    per-query-row mask (the verify path) -> (nk, B, Sq, chunk).  None passes
    through."""
    if kv_len_mask is None:
        return None
    if kv_len_mask.ndim == 3:
        Sq = kv_len_mask.shape[1]
        return kv_len_mask.reshape(B, Sq, nk, chunk).transpose(2, 0, 1, 3)
    return kv_len_mask.reshape(B, nk, chunk).transpose(1, 0, 2)


def _mask_bcast(mt):
    """One scan slice of ``_mask_chunks`` broadcast against z
    (B, Hkv, g, Sq, chunk): (B, chunk) masks every query row, (B, Sq, chunk)
    masks per query row."""
    if mt.ndim == 3:
        return mt[:, None, None, :, :]
    return mt[:, None, None, None, :]


def _chunked_fwd(q, k, v, cfg: HyftConfig, causal: bool, chunk: int, q_offset,
                 kv_len_mask=None):
    """Returns (o, m_final raw, l_final). Shapes: q (B,Hq,Sq,D), k/v GQA."""
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    g = Hq // Hkv
    nk = Sk // chunk
    qg = q.reshape(B, Hkv, g, Sq, D).astype(F32) * (D ** -0.5)
    kc = k.reshape(B, Hkv, nk, chunk, D).transpose(2, 0, 1, 3, 4).astype(F32)
    vc = v.reshape(B, Hkv, nk, chunk, D).transpose(2, 0, 1, 3, 4).astype(F32)
    mc = _mask_chunks(kv_len_mask, B, nk, chunk)

    def body(carry, xs):
        m_run, l_run, acc = carry
        j, kt, vt, mt = xs
        z = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kt)
        if causal:
            qi = q_offset + jax.lax.broadcasted_iota(I32, (Sq, chunk), 0)
            ki = jax.lax.broadcasted_iota(I32, (Sq, chunk), 1) + j * chunk
            z = jnp.where((qi >= ki)[None, None, None], z, NEG_BIG)
        if mt is not None:  # pre-FP2FX, same as the unfused path
            z = jnp.where(_mask_bcast(mt) > 0, z, NEG_BIG)
        m_new, alpha, l_blk, p = _hyft_chunk_stats(z, cfg, m_run)
        l_run = nm.fx_quantize(l_run * alpha, cfg.acc_bits) + l_blk
        acc = acc * alpha + jnp.einsum("bhgqk,bhkd->bhgqd", p, vt)
        return (m_new, l_run, acc), None

    m0 = jnp.full((B, Hkv, g, Sq, 1), -(2 ** (cfg.total_bits - 1)), I32)
    l0 = jnp.zeros((B, Hkv, g, Sq, 1), F32)
    a0 = jnp.zeros((B, Hkv, g, Sq, D), F32)
    (m_f, l_f, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (jnp.arange(nk), kc, vc, mc))
    o = _hyft_finalize(acc, l_f, cfg).reshape(B, Hq, Sq, D)
    return o, m_f, l_f


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def chunked_hyft_attention(q, k, v, cfg: HyftConfig, causal: bool = True,
                           chunk: int = 512, q_offset: int = 0,
                           kv_len_mask=None):
    """Online-Hyft attention, O(chunk) memory in the KV dimension.

    ``kv_len_mask``: optional (B, Sk) float validity mask (nonzero = valid),
    per the shared mask contract in ``repro.kernels.ops``.
    """
    o, _, _ = _chunked_fwd(q, k, v, cfg, causal, chunk, q_offset, kv_len_mask)
    return o.astype(q.dtype)


def _cha_fwd(q, k, v, cfg, causal, chunk, q_offset, kv_len_mask=None):
    o, m_f, l_f = _chunked_fwd(q, k, v, cfg, causal, chunk, q_offset,
                               kv_len_mask)
    return o.astype(q.dtype), (q, k, v, kv_len_mask, o, m_f, l_f)


def _cha_bwd(cfg, causal, chunk, q_offset, res, do):
    """Flash-style backward: recompute Hyft probs per chunk from the saved
    row stats (single-pass, no online rescale), then the standard softmax
    attention gradients.  The softmax-VJP identity is applied to the *Hyft*
    probabilities — the paper's training mode, matrix-free."""
    q, k, v, kv_len_mask, o, m_f, l_f = res
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    g = Hq // Hkv
    nk = Sk // chunk
    scale = D ** -0.5
    qg = q.reshape(B, Hkv, g, Sq, D).astype(F32)
    dog = do.reshape(B, Hkv, g, Sq, D).astype(F32)
    og = o.reshape(B, Hkv, g, Sq, D).astype(F32)
    delta = jnp.sum(dog * og, axis=-1, keepdims=True)  # (B,Hkv,g,Sq,1)
    e_b, m_b = nm.lod_refloat(l_f, cfg.mant_bits)

    kc = k.reshape(B, Hkv, nk, chunk, D).transpose(2, 0, 1, 3, 4).astype(F32)
    vc = v.reshape(B, Hkv, nk, chunk, D).transpose(2, 0, 1, 3, 4).astype(F32)
    mc = _mask_chunks(kv_len_mask, B, nk, chunk)

    def probs(j, kt, mt):
        z = jnp.einsum("bhgqd,bhkd->bhgqk", qg * scale, kt)
        if causal:
            qi = q_offset + jax.lax.broadcasted_iota(I32, (Sq, chunk), 0)
            ki = jax.lax.broadcasted_iota(I32, (Sq, chunk), 1) + j * chunk
            z = jnp.where((qi >= ki)[None, None, None], z, NEG_BIG)
        if mt is not None:
            z = jnp.where(_mask_bcast(mt) > 0, z, NEG_BIG)
        z_raw = nm.fp2fx(z, cfg.frac_bits, cfg.total_bits)
        e, m = nm.exp_unit(z_raw - m_f, cfg.frac_bits, cfg.mant_bits)
        return nm.log_div(e, m, e_b, m_b, cfg.mant_bits)  # broadcast over chunk

    def body(dq, xs):
        j, kt, vt, mt = xs
        p = probs(j, kt, mt)  # (B,Hkv,g,Sq,chunk)
        dv = jnp.einsum("bhgqk,bhgqd->bhkd", p, dog)
        dp = jnp.einsum("bhgqd,bhkd->bhgqk", dog, vt)
        ds = p * (dp - delta)
        dq = dq + jnp.einsum("bhgqk,bhkd->bhgqd", ds, kt) * scale
        dk = jnp.einsum("bhgqk,bhgqd->bhkd", ds, qg) * scale
        return dq, (dk, dv)

    dq0 = jnp.zeros((B, Hkv, g, Sq, D), F32)
    dq, (dks, dvs) = jax.lax.scan(body, dq0, (jnp.arange(nk), kc, vc, mc))
    dk = dks.transpose(1, 2, 0, 3, 4).reshape(B, Hkv, Sk, D)
    dv = dvs.transpose(1, 2, 0, 3, 4).reshape(B, Hkv, Sk, D)
    dmask = None if kv_len_mask is None else jnp.zeros_like(kv_len_mask)
    return (dq.reshape(B, Hq, Sq, D).astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype), dmask)


chunked_hyft_attention.defvjp(_cha_fwd, _cha_bwd)


# --------------------------------------------------------------------------
# mode selection + decode
# --------------------------------------------------------------------------


def attention_fwd(q, k, v, cfg, *, causal=True, q_offset=0, kv_len_mask=None):
    """Dispatch on cfg.attn_mode; falls back to unfused for non-Hyft impls.

    All three modes honor the shared mask contract (``repro.kernels.ops``):
    ``kv_len_mask`` (B, Sk) marks valid KV positions, so decode and serving
    stay on the fused/online paths instead of dropping to unfused.  The only
    remaining fallbacks are non-Hyft softmax impls and a KV length the chunk
    size doesn't divide (chunked mode only).  The fused paths need a static
    ``q_offset`` for the causal mask: kernel mode raises on a traced one
    rather than quietly running the unfused path in its place.
    """
    hcfg = hyft_config_for(cfg.softmax_impl)
    mode = getattr(cfg, "attn_mode", "unfused")
    if hcfg is not None and mode == "kernel" and not isinstance(q_offset, int):
        raise ValueError(
            "attn_mode='kernel' needs a static int q_offset; a traced offset "
            "would silently drop to the unfused path")
    if hcfg is not None and isinstance(q_offset, int):
        from repro.kernels import ops
        maskf = ops.as_mask_f(kv_len_mask)
        if mode == "chunked":
            chunk = min(getattr(cfg, "attn_chunk", 512), k.shape[2])
            if k.shape[2] % chunk == 0:
                return chunked_hyft_attention(q, k, v, hcfg, causal, chunk,
                                              q_offset, maskf)
        if mode == "kernel":
            return ops.hyft_attention(
                q, k, v, hcfg, causal=causal, q_offset=q_offset,
                kv_len_mask=maskf).astype(q.dtype)
    return unfused_attention(q, k, v, cfg.softmax_impl, causal=causal,
                             q_offset=q_offset, kv_len_mask=kv_len_mask)


# --------------------------------------------------------------------------
# sequence-parallel decode: the Hyft L1/L2 tree across devices
# --------------------------------------------------------------------------


def sp_decode_attention(q, k_shard, v_shard, valid_mask, cfg: HyftConfig,
                        axis_name: str):
    """Per-shard body (call inside shard_map; KV cache sharded on seq axis).

    q: (B,Hq,1,D) replicated over ``axis_name``; k/v_shard: (B,Hkv,Ss,D)
    local slice; valid_mask: (B,Ss) bool local.  L1 = local Hyft stages 1-2;
    L2 = pmax of the fixed-point max + psum of rescaled fixed sums / accs —
    the paper's two-layer Hyft tree with ICI as the second layer.
    """
    B, Hq, _, D = q.shape
    Hkv = k_shard.shape[1]
    g = Hq // Hkv
    qg = q.reshape(B, Hkv, g, 1, D).astype(F32) * (D ** -0.5)
    z = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k_shard.astype(F32))
    z = jnp.where(valid_mask[:, None, None, None, :], z, NEG_BIG)
    # L1: local fixed-point max + exp/sum
    z_raw = nm.fp2fx(z, cfg.frac_bits, cfg.total_bits)
    m_loc = jnp.max(z_raw, axis=-1, keepdims=True)
    # L2a: global max (integer pmax over ICI)
    m_glob = jax.lax.pmax(m_loc, axis_name)
    e, m = nm.exp_unit(z_raw - m_glob, cfg.frac_bits, cfg.mant_bits)
    addend = nm.expfloat_to_fx(e, m, cfg.mant_bits, cfg.acc_bits)
    l_loc = jnp.sum(addend, axis=-1, keepdims=True)
    p = ((1 << cfg.mant_bits) + m).astype(F32) * nm.pow2_float(e - cfg.mant_bits)
    acc_loc = jnp.einsum("bhgqk,bhkd->bhgqd", p, v_shard.astype(F32))
    # L2b: global fixed-point sum + acc reduce
    l_glob = jax.lax.psum(l_loc, axis_name)
    acc_glob = jax.lax.psum(acc_loc, axis_name)
    out = _hyft_finalize(acc_glob, l_glob, cfg)
    return out.reshape(B, Hq, 1, D)


# --------------------------------------------------------------------------
# KV cache (dense or FP2FX-quantized int8)
# --------------------------------------------------------------------------
#
# ``cache_dtype="fp2fx8"`` stores K/V as int8 FP2FX raws with an fp32
# per-(head, position) scale — the paper's format-conversion idea applied to
# the KV stream decode actually spends its bandwidth on.  Writes run the
# FP2FX converter (``nm.fp2fx`` at total_bits=8); the split-K decode kernel
# fuses dequantization into its K/V loads, so HBM traffic stays int8.

FP2FX8 = "fp2fx8"
_FP2FX8_FRAC = 7  # int8 raw at 7 fractional bits; the scale folds in 2**-7


def is_fp2fx8(dtype) -> bool:
    return str(dtype) == FP2FX8


def cache_storage_dtype(dtype):
    """jnp dtype for non-attention cache buffers (SSM state, encoder memory)
    when the attention cache may be the symbolic "fp2fx8" format."""
    return jnp.dtype(jnp.float32 if is_fp2fx8(dtype) else dtype)


def fp2fx8_quantize(x):
    """(..., D) float -> (int8 raw, fp32 scale over the last axis).

    Per-(head, position) amax scale maps the row into [-127/128, 127/128];
    the FP2FX converter (round-to-nearest, saturating) then emits the int8
    raw.  Dequantization is ``raw * scale`` with the 2**-frac folded in.
    """
    amax = jnp.max(jnp.abs(x.astype(F32)), axis=-1)
    s = jnp.maximum(amax, 1e-30) * F32(128.0 / 127.0)
    raw = nm.fp2fx(x.astype(F32) / s[..., None], _FP2FX8_FRAC, 8)
    return raw.astype(jnp.int8), s * F32(2.0 ** -_FP2FX8_FRAC)


def fp2fx8_dequantize(raw, scale):
    return raw.astype(F32) * scale[..., None]


def cache_is_quantized(cache) -> bool:
    return "k_scale" in cache


def cache_kv(cache):
    """(k, v) as float arrays — dequantizes the fp2fx8 layout on demand (the
    unfused/chunked fallbacks; the split-K kernel reads the raws directly)."""
    if cache_is_quantized(cache):
        return (fp2fx8_dequantize(cache["k"], cache["k_scale"]),
                fp2fx8_dequantize(cache["v"], cache["v_scale"]))
    return cache["k"], cache["v"]


def cache_init(cfg, batch, max_len, dtype) -> dict[str, Any]:
    shape = (batch, cfg.n_kv_heads, max_len, cfg.d_head)
    if is_fp2fx8(dtype):
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:3], F32),
                "v_scale": jnp.zeros(shape[:3], F32)}
    dtype = jnp.dtype(dtype)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_update(cache, k_new, v_new, pos):
    """k_new/v_new: (B,Hkv,S_new,D); pos: scalar write offset."""
    if cache_is_quantized(cache):
        kr, ks = fp2fx8_quantize(k_new)
        vr, vs = fp2fx8_quantize(v_new)
        return {
            "k": jax.lax.dynamic_update_slice(cache["k"], kr, (0, 0, pos, 0)),
            "v": jax.lax.dynamic_update_slice(cache["v"], vr, (0, 0, pos, 0)),
            "k_scale": jax.lax.dynamic_update_slice(
                cache["k_scale"], ks, (0, 0, pos)),
            "v_scale": jax.lax.dynamic_update_slice(
                cache["v_scale"], vs, (0, 0, pos)),
        }
    k = jax.lax.dynamic_update_slice(cache["k"], k_new.astype(cache["k"].dtype),
                                     (0, 0, pos, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new.astype(cache["v"].dtype),
                                     (0, 0, pos, 0))
    return {"k": k, "v": v}


def cache_update_ragged(cache, k_new, v_new, pos_b, write_mask=None):
    """Per-row cache scatter: row ``b``'s (Hkv, 1, D) K/V lands at its own
    position ``pos_b[b]`` — the slot-pool decode step, where every slot sits
    at a different sequence length.

    ``write_mask`` (B,) bool gates the write per row: a False row re-writes
    its *old* cache content at ``pos_b[b]`` (an exact no-op), so finished
    (EOS'd / drained) slots in the continuous-batching pool stop mutating
    their cache while the rest of the pool keeps decoding.
    """
    B = k_new.shape[0]
    gate = jnp.ones((B,), bool) if write_mask is None else write_mask

    def upd(buf, new, pos, g):
        # buf (Hkv, L[, D]); position axis is axis 1 for values and scales
        start = (0, pos) + (0,) * (buf.ndim - 2)
        old = jax.lax.dynamic_slice(buf, start, new.shape)
        new = jnp.where(g, new.astype(buf.dtype), old)
        return jax.lax.dynamic_update_slice(buf, new, start)

    up = jax.vmap(upd, in_axes=(0, 0, 0, 0))
    if cache_is_quantized(cache):
        kr, ks = fp2fx8_quantize(k_new)
        vr, vs = fp2fx8_quantize(v_new)
        return {"k": up(cache["k"], kr, pos_b, gate),
                "v": up(cache["v"], vr, pos_b, gate),
                "k_scale": up(cache["k_scale"], ks, pos_b, gate),
                "v_scale": up(cache["v_scale"], vs, pos_b, gate)}
    return {"k": up(cache["k"], k_new, pos_b, gate),
            "v": up(cache["v"], v_new, pos_b, gate)}


def cache_update_block_ragged(cache, k_new, v_new, pos_b, n_valid,
                              write_mask=None):
    """Multi-token ragged scatter: token ``j`` of row ``b`` lands at
    ``pos_b[b] + j`` — the speculative-decode verify write, where the
    [last_token, draft...] chunk enters the cache BEFORE attention exactly
    like the one-token decode step's write-then-attend.

    ``n_valid`` (B,) bounds each row's real tokens (draft lengths are
    ragged across the batch); lanes with ``j >= n_valid[b]`` — and whole
    rows with ``write_mask[b]`` False — rewrite their *old* content at a
    clamped position, so padded drafts neither corrupt the cache nor shift
    a ``dynamic_update_slice`` at the cache edge.  Token-by-token through
    ``cache_update_ragged`` so the fp2fx8 per-(head, position) scales are
    bitwise those of sequential decode writes.
    """
    B, _, S, _ = k_new.shape
    L = cache["k"].shape[2]
    base = jnp.ones((B,), bool) if write_mask is None else write_mask
    nv = jnp.asarray(n_valid, I32)
    for j in range(S):
        gate = base & (j < nv) & (pos_b + j < L)
        pj = jnp.clip(pos_b + j, 0, L - 1)
        cache = cache_update_ragged(cache, k_new[:, :, j:j + 1],
                                    v_new[:, :, j:j + 1], pj, gate)
    return cache


# --------------------------------------------------------------------------
# paged KV cache (block-table indirection over a global page pool)
# --------------------------------------------------------------------------
#
# ``kv_layout="paged"`` (DESIGN.md §10) replaces the per-slot dense stripe
# with one global pool of fixed-size pages — (n_pages + 1, Hkv, page_size, D)
# per layer, dense or fp2fx8 — plus a per-sequence block table mapping
# virtual KV block j to a physical page.  Page 0 is the reserved null page
# (``repro.serve.kvpool.NULL_PAGE``): masked writes are *redirected* at it
# instead of gated, so the token scatter never needs a gather-then-rewrite
# and two rows can never race on a live page (distinct slots own distinct
# unshared tail pages; shared prefix pages are read-only by construction).


def paged_cache_init(cfg, n_pages, page_size, dtype) -> dict[str, Any]:
    """One layer's page pool: ``n_pages`` usable pages + the null page 0."""
    shape = (n_pages + 1, cfg.n_kv_heads, page_size, cfg.d_head)
    if is_fp2fx8(dtype):
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:3], F32),
                "v_scale": jnp.zeros(shape[:3], F32)}
    dtype = jnp.dtype(dtype)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_update_paged(cache, k_new, v_new, pos_b, block_tables,
                       write_mask=None):
    """Per-row paged scatter: row ``b``'s (Hkv, 1, D) K/V lands in physical
    page ``block_tables[b, pos_b[b] // ps]`` at offset ``pos_b[b] % ps``.

    ``write_mask`` (B,) bool redirects masked rows to the null page — their
    write happens but lands in the sink, so finished slots stop mutating
    live pages without any gather.
    """
    ps = cache["k"].shape[2]
    blk = pos_b // ps
    off = pos_b % ps
    page = jnp.take_along_axis(block_tables, blk[:, None], axis=1)[:, 0]
    if write_mask is not None:
        page = jnp.where(write_mask, page, 0)

    def scat(pool, new):  # new (B, Hkv[, D])
        return pool.at[page, :, off].set(new.astype(pool.dtype))

    if cache_is_quantized(cache):
        kr, ks = fp2fx8_quantize(k_new)
        vr, vs = fp2fx8_quantize(v_new)
        return {"k": scat(cache["k"], kr[:, :, 0]),
                "v": scat(cache["v"], vr[:, :, 0]),
                "k_scale": scat(cache["k_scale"], ks[:, :, 0]),
                "v_scale": scat(cache["v_scale"], vs[:, :, 0])}
    return {"k": scat(cache["k"], k_new[:, :, 0]),
            "v": scat(cache["v"], v_new[:, :, 0])}


def cache_update_block_paged(cache, k_new, v_new, pos_b, block_tables,
                             n_valid, write_mask=None):
    """Paged multi-token scatter: token ``j`` of row ``b`` lands through the
    block table at virtual position ``pos_b[b] + j``, all tokens in ONE
    scatter per buffer.  Lanes past ``n_valid[b]``, rows with
    ``write_mask`` False, and lanes past the table's virtual extent are
    redirected to the null page — the usual paged "no write" that can never
    race a live page.  fp2fx8 scales are per (head, position), so
    quantizing the block at once gives the bits of token-by-token writes.
    """
    B, _, S, _ = k_new.shape
    ps = cache["k"].shape[2]
    Lv = block_tables.shape[1] * ps
    base = jnp.ones((B,), bool) if write_mask is None else write_mask
    lane = jnp.arange(S, dtype=I32)[None, :]
    pos = pos_b[:, None] + lane                                  # (B, S)
    gate = (base[:, None] & (lane < jnp.asarray(n_valid, I32)[:, None])
            & (pos < Lv))
    pos = jnp.clip(pos, 0, Lv - 1)
    page = jnp.take_along_axis(block_tables, pos // ps, axis=1)
    page = jnp.where(gate, page, 0)
    off = pos % ps

    def scat(pool, new):  # new (B, Hkv, S[, D]) -> rows (B, S, Hkv[, D])
        return pool.at[page, :, off].set(
            jnp.moveaxis(new, 2, 1).astype(pool.dtype))

    if cache_is_quantized(cache):
        kr, ks = fp2fx8_quantize(k_new)
        vr, vs = fp2fx8_quantize(v_new)
        return {"k": scat(cache["k"], kr), "v": scat(cache["v"], vr),
                "k_scale": scat(cache["k_scale"], ks),
                "v_scale": scat(cache["v_scale"], vs)}
    return {"k": scat(cache["k"], k_new), "v": scat(cache["v"], v_new)}


def paged_gather_kv(cache, block_tables):
    """Materialize the virtual dense (B, Hkv, nb * ps, D) float K/V of each
    sequence from its block table — the unfused/chunked fallback; the paged
    split-K kernel gathers via its index maps instead."""

    def flat(pool):  # (B, nb, Hkv, ps[, D]) -> (B, Hkv, nb * ps[, D])
        x = jnp.moveaxis(jnp.take(pool, block_tables, axis=0), 2, 1)
        return x.reshape(x.shape[0], x.shape[1], -1, *x.shape[4:])

    if cache_is_quantized(cache):
        return (fp2fx8_dequantize(flat(cache["k"]), flat(cache["k_scale"])),
                fp2fx8_dequantize(flat(cache["v"]), flat(cache["v_scale"])))
    return flat(cache["k"]), flat(cache["v"])


def decode_attention_paged(q, cache, block_tables, cfg, *, kv_len_mask=None):
    """Sq=1 attention over a paged KV pool — the paged serving fast path.

    With a Hyft softmax and ``attn_mode="kernel"`` this dispatches to the
    block-table split-K kernel (pages gathered by scalar-prefetched index
    maps, fp2fx8 dequant fused into the page loads); every other combination
    materializes the virtual dense K/V and falls through to the regular
    dispatch, so all three attention modes serve the paged layout.
    """
    hcfg = hyft_config_for(cfg.softmax_impl)
    mode = getattr(cfg, "attn_mode", "unfused")
    if hcfg is not None and mode == "kernel" and q.shape[2] == 1:
        from repro.kernels import ops
        return ops.hyft_paged_decode_attention(
            q, cache["k"], cache["v"], block_tables, hcfg,
            kv_len_mask=ops.as_mask_f(kv_len_mask),
            k_scale=cache.get("k_scale"),
            v_scale=cache.get("v_scale")).astype(q.dtype)
    k, v = paged_gather_kv(cache, block_tables)
    return attention_fwd(q, k, v, cfg, causal=False, kv_len_mask=kv_len_mask)


def decode_attention(q, cache, cfg, *, kv_len_mask=None):
    """Sq=1 attention over the KV cache — the serving fast path.

    With a Hyft softmax and ``attn_mode="kernel"`` this dispatches to the
    split-K fused decode kernel (``repro.kernels.ops.hyft_decode_attention``),
    reading the fp2fx8 cache raws directly (dequant fused into the K/V
    loads).  Every other combination dequantizes once and falls through to
    the regular mode dispatch.
    """
    hcfg = hyft_config_for(cfg.softmax_impl)
    mode = getattr(cfg, "attn_mode", "unfused")
    if hcfg is not None and mode == "kernel" and q.shape[2] == 1:
        from repro.kernels import ops
        return ops.hyft_decode_attention(
            q, cache["k"], cache["v"], hcfg,
            kv_len_mask=ops.as_mask_f(kv_len_mask),
            k_scale=cache.get("k_scale"),
            v_scale=cache.get("v_scale")).astype(q.dtype)
    k, v = cache_kv(cache)
    return attention_fwd(q, k, v, cfg, causal=False, kv_len_mask=kv_len_mask)


# --------------------------------------------------------------------------
# chunked attend-at-offset (Sq = chunk, per-token causal frontier) — the
# attention entry behind model.prefill_chunk: prefill chunks, prefix-hit
# suffixes, and speculative-decode verify all land here (DESIGN.md §12)
# --------------------------------------------------------------------------


def _verify_unfused(q, k, v, softmax_impl: str, kv_pos_mask):
    """Unfused reference with a per-query-token (B, Sq, Sk) mask — the same
    arithmetic as ``unfused_attention``'s masked decode, one row per draft
    token, so greedy verify matches greedy sequential decode per row."""
    B, Hq, Sq, D = q.shape
    Hkv = k.shape[1]
    g = Hq // Hkv
    qg = q.reshape(B, Hkv, g, Sq, D)
    z = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(F32),
                   k.astype(F32)) * (D ** -0.5)
    z = jnp.where(kv_pos_mask[:, None, None, :, :] > 0, z, NEG_BIG)
    p = get_softmax(softmax_impl)(z).astype(F32)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(F32))
    return o.reshape(B, Hq, Sq, D).astype(q.dtype)


def verify_attention(q, cache, cfg, *, kv_pos_mask, block_tables=None):
    """Attend a token chunk at per-row offsets against the serving cache:
    ``q`` carries a chunk of Sq already-written tokens per row and
    ``kv_pos_mask`` (B, Sq, Lk) each token's causal frontier (``kv_index
    <= pos + t``), so every chunk token sees exactly the KV a sequential
    decode step would have.  This is ``model.prefill_chunk``'s attention
    (DESIGN.md §12): prompt-chunk prefill, prefix-hit suffixes, and
    speculative-decode verify (Sq = draft_k + 1) are all this one call.

    With a Hyft softmax and ``attn_mode="kernel"`` this is the split-K
    verify kernel (dense stripes or — with ``block_tables`` — the paged
    pool, fp2fx8 dequant fused into the loads); chunked mode runs the
    online-Hyft scan under the same per-row mask; everything else falls to
    the unfused reference.  Each mode mirrors its decode counterpart's
    arithmetic, which is what makes greedy speculative decode
    token-for-token identical to vanilla greedy decode.
    """
    hcfg = hyft_config_for(cfg.softmax_impl)
    mode = getattr(cfg, "attn_mode", "unfused")
    if hcfg is not None and mode == "kernel":
        from repro.kernels import ops
        return ops.hyft_verify_attention(
            q, cache["k"], cache["v"], kv_pos_mask, hcfg,
            block_tables=block_tables,
            k_scale=cache.get("k_scale"),
            v_scale=cache.get("v_scale")).astype(q.dtype)
    if block_tables is not None:
        k, v = paged_gather_kv(cache, block_tables)
    else:
        k, v = cache_kv(cache)
    if hcfg is not None and mode == "chunked":
        chunk = min(getattr(cfg, "attn_chunk", 512), k.shape[2])
        if k.shape[2] % chunk == 0:
            from repro.kernels import ops
            return chunked_hyft_attention(
                q, k, v, hcfg, False, chunk, 0,
                ops.as_mask_f(kv_pos_mask)).astype(q.dtype)
    return _verify_unfused(q, k, v, cfg.softmax_impl, kv_pos_mask)
