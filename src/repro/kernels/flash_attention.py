"""Fused flash attention with Hyft softmax — the TPU-native form of §3.6.

The paper pipelines softmax's three stages (max | exp+sum | div) *across
vectors* because one vector's stages are sequential.  On TPU the same row
independence is exploited the opposite way: we stream KV blocks through VMEM
and maintain *online* (max, sum, acc) state per query row, so stage 1/2/3 of
consecutive blocks overlap inside one kernel — one HBM pass over K/V instead
of the three passes an unfused QK^T -> softmax -> PV takes.  The paper's
L1/L2 tree of Hyft units (Fig. 6) is exactly the associative (max,sum) merge
used here blockwise (and cross-device in ``repro.models.attention``'s
sequence-parallel decode).

All softmax arithmetic inside is Hyft's: FP2FX, Booth shift-add, field
assembly, fixed-point accumulation, and the final log-subtract division.
The online rescale multiplies by the *Hyft-approximated* exp of the max
delta (the DIV/MUL unit in rescale duty).

Forward accumulator pattern: (bh, q, kv) grid with kv innermost; output
blocks and the (m, l) stat blocks map to the same index for every kv step,
so they stay resident in VMEM and serve as carry; finalization happens at
the last step.

Mask contract (DESIGN.md §3): ``kv_len_mask`` is an optional float32
``(B, Sk)`` array, 1.0 = valid KV position, 0.0 = padded/invalid.  It rides
into the kernels as ``(B, 1, Sk)`` so each block is a ``(1, bk)`` lane row:
Mosaic tiles the last two block dims, which must be multiples of (8, 128)
or span the array, so a ``(1, bk)`` block of a 2-D ``(B, Sk)`` array is
refused while the 3-D form compiles.  Per-row stats (m, l, delta) travel
as ``(BH, Sq, 1)`` columns for the same reason.  Masking
happens on the *float scores before FP2FX* (identical to the unfused path):
invalid scores become ``NEG_BIG``, the converter saturates them to the
fixed-point minimum and the exponent unit flushes their probability to zero.
Sequences that are not block multiples are padded automatically and the
padding is folded into the same mask.

Backward (paper §3.5, training mode): a ``jax.custom_vjp`` whose bwd is two
Pallas kernels that *recompute* the Hyft probabilities per (q, kv) block
from the saved final row stats ``(m, l)`` — flash-style, single pass, no
online rescale — mirroring the arithmetic of ``_cha_bwd`` in
``repro.models.attention``:

  p  = log_div(exp_unit(fp2fx(z) - m), lod_refloat(l))   # DIV unit reused
  dv = p^T do;  dp = do v^T;  ds = p (dp - delta);  delta = <do, o>
  dq = ds k * scale;  dk = ds^T q * scale

The dq kernel runs on a (bh, q, kv) grid with the dq block as carry over kv
steps; the dk/dv kernel runs on a (bh_kv, kv, group*q) grid with the dk/dv
blocks as carry over the fused (GQA group x q block) inner dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import numerics as nm
from repro.core.hyft import HyftConfig

F32 = jnp.float32
I32 = jnp.int32
NEG_BIG = -3.0e38  # pre-quantization mask value; FP2FX saturates it to fx lo


def _pad0(x, widths):
    """``jnp.pad`` with a dtype-matched zero fill: the default Python-int
    fill is a weak scalar that inserts a convert_element_type per pad (int8
    KV raws included), which the format-flow auditor counts as churn."""
    return jnp.pad(x, widths, constant_values=x.dtype.type(0))


def hyft_finalize(acc, l, cfg: HyftConfig):
    """Hyft stage 3: log-subtract division ``acc / l`` through the DIV unit.

    acc: (..., D) fp32 PV accumulator; l: (..., 1) fp32 fixed-point sum.
    Shared by the fused kernels' last step, the chunked path, the
    sequence-parallel combine, and the split-K decode combine — one
    arithmetic, so every online mode finalizes identically.
    """
    e_b, m_b = nm.lod_refloat(l, cfg.mant_bits)
    sg, e_n, m_n = nm.float_fields(acc, cfg.mant_bits)
    res = nm.log_div(e_n, m_n, e_b, m_b, cfg.mant_bits)
    res = jnp.where(sg == 1, -res, res)
    return jnp.where(acc == F32(0), F32(0), res)


def hyft_alpha(d_raw, cfg: HyftConfig):
    """Hyft-approximated ``exp(d)`` of a fixed-point max delta (d <= 0),
    assembled to fp32 — the DIV/MUL unit in rescale duty (online merges)."""
    e_a, m_a = nm.exp_unit(d_raw, cfg.frac_bits, cfg.mant_bits)
    return ((1 << cfg.mant_bits) + m_a).astype(F32) * nm.pow2_float(
        e_a - cfg.mant_bits)


# --------------------------------------------------------------------------
# forward kernel
# --------------------------------------------------------------------------


def _flash_fwd_kernel(*refs, cfg: HyftConfig, sm_scale: float, causal: bool,
                      block_q: int, block_k: int, nk: int, q_offset: int,
                      has_mask: bool):
    if has_mask:
        q_ref, k_ref, v_ref, mask_ref, o_ref, m_ref, l_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref = refs
        mask_ref = None
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, -(2 ** (cfg.total_bits - 1)))
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(F32)              # (bq, dh)
    k = k_ref[0].astype(F32)              # (bk, dh)
    v = v_ref[0].astype(F32)              # (bk, dh)
    z = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32) * sm_scale
    if causal:
        qi = q_offset + iq * block_q + jax.lax.broadcasted_iota(I32, z.shape, 0)
        ki = ik * block_k + jax.lax.broadcasted_iota(I32, z.shape, 1)
        z = jnp.where(qi >= ki, z, NEG_BIG)
    if has_mask:  # pre-FP2FX, same as the unfused path; (1, bk) row
        z = jnp.where(mask_ref[0] > F32(0), z, NEG_BIG)

    # ---- Hyft stage 1: FP2FX + (strided) block max, merged with running max
    z_raw = nm.fp2fx(z, cfg.frac_bits, cfg.total_bits)
    zsub = z_raw[:, :: cfg.step] if cfg.step > 1 else z_raw
    blk_max = jnp.max(zsub, axis=-1, keepdims=True)
    m_old = m_ref[:, :1]
    m_new = jnp.maximum(m_old, blk_max)

    # ---- Hyft stage 2: exponent unit + fixed-point accumulation
    e, m = nm.exp_unit(z_raw - m_new, cfg.frac_bits, cfg.mant_bits)
    addend = nm.expfloat_to_fx(e, m, cfg.mant_bits, cfg.acc_bits)
    l_blk = jnp.sum(addend, axis=-1, keepdims=True)

    # online rescale of the carried sum/acc by the *Hyft* exp of the max delta
    alpha = hyft_alpha(m_old - m_new, cfg)
    l_new = nm.fx_quantize(l_ref[:, :1] * alpha, cfg.acc_bits) + l_blk

    # ---- probabilities as assembled floats -> MXU matmul with V
    p = ((1 << cfg.mant_bits) + m).astype(F32) * nm.pow2_float(e - cfg.mant_bits)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=F32)
    acc = o_ref[0].astype(F32) * alpha + pv

    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
    o_ref[...] = acc[None].astype(o_ref.dtype)

    # ---- Hyft stage 3: log-subtract division at the last kv step
    @pl.when(ik == nk - 1)
    def _finalize():
        res = hyft_finalize(o_ref[0].astype(F32), l_ref[:, :1], cfg)
        o_ref[...] = res[None].astype(o_ref.dtype)


def _flash_fwd_impl(q3, k3, v3, maskf, *, cfg: HyftConfig, sm_scale: float,
                    causal: bool, bq: int, bk: int, group: int,
                    q_offset: int, interpret: bool):
    """Blocked forward on pre-padded 3D operands.

    q3: (BH, Sq, D); k3/v3: (BHkv, Sk, D); maskf: (B, Sk) float or None.
    Returns (o (BH,Sq,D) f32, m (BH,Sq,1) i32 raw, l (BH,Sq,1) f32).
    """
    BH, Sq, D = q3.shape
    Sk = k3.shape[1]
    Hq_per_b = BH // max(maskf.shape[0], 1) if maskf is not None else 0
    nq, nk = Sq // bq, Sk // bk
    grid = (BH, nq, nk)
    has_mask = maskf is not None

    kern = functools.partial(_flash_fwd_kernel, cfg=cfg, sm_scale=sm_scale,
                             causal=causal, block_q=bq, block_k=bk, nk=nk,
                             q_offset=q_offset, has_mask=has_mask)
    in_specs = [
        pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bk, D), lambda b, i, j, g=group: (b // g, j, 0)),
        pl.BlockSpec((1, bk, D), lambda b, i, j, g=group: (b // g, j, 0)),
    ]
    operands = [q3, k3, v3]
    if has_mask:
        in_specs.append(
            pl.BlockSpec((1, 1, bk), lambda b, i, j, h=Hq_per_b: (b // h, 0, j)))
        operands.append(maskf[:, None, :])
    o, m_st, l_st = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((bq, 128), lambda b, i, j, n=nq: (b * n + i, 0)),
            pl.BlockSpec((bq, 128), lambda b, i, j, n=nq: (b * n + i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, D), F32),
            jax.ShapeDtypeStruct((BH * Sq, 128), I32),
            jax.ShapeDtypeStruct((BH * Sq, 128), F32),
        ],
        interpret=interpret,
    )(*operands)
    return (o, m_st[:, :1].reshape(BH, Sq, 1),
            l_st[:, :1].reshape(BH, Sq, 1))


# --------------------------------------------------------------------------
# backward kernels (recompute-from-stats, flash-style)
# --------------------------------------------------------------------------


def _recompute_probs(q, k, mask_row, m_row, l_row, *, cfg, sm_scale, causal,
                     qi0, ki0):
    """Hyft probabilities of one (bq, bk) tile from the saved final row stats
    (``mask_row`` (1, bk) or None; ``m_row``/``l_row`` (bq, 1) columns).

    Identical arithmetic to the chunked path's ``probs``: elementwise, so the
    result is independent of how the forward blocked the KV axis."""
    z = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32) * sm_scale
    if causal:
        qi = qi0 + jax.lax.broadcasted_iota(I32, z.shape, 0)
        ki = ki0 + jax.lax.broadcasted_iota(I32, z.shape, 1)
        z = jnp.where(qi >= ki, z, NEG_BIG)
    if mask_row is not None:
        z = jnp.where(mask_row > F32(0), z, NEG_BIG)
    z_raw = nm.fp2fx(z, cfg.frac_bits, cfg.total_bits)
    e, m = nm.exp_unit(z_raw - m_row, cfg.frac_bits, cfg.mant_bits)
    e_b, m_b = nm.lod_refloat(l_row, cfg.mant_bits)
    return nm.log_div(e, m, e_b, m_b, cfg.mant_bits)


def _flash_bwd_dq_kernel(*refs, cfg: HyftConfig, sm_scale: float,
                         causal: bool, block_q: int, block_k: int,
                         q_offset: int, has_mask: bool):
    if has_mask:
        (q_ref, k_ref, v_ref, do_ref, delta_ref, m_ref, l_ref, mask_ref,
         dq_ref) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, delta_ref, m_ref, l_ref, dq_ref = refs
        mask_ref = None
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    q = q_ref[0].astype(F32)
    k = k_ref[0].astype(F32)
    v = v_ref[0].astype(F32)
    do = do_ref[0].astype(F32)
    p = _recompute_probs(
        q, k, mask_ref[0] if has_mask else None,
        m_ref[0], l_ref[0], cfg=cfg, sm_scale=sm_scale,
        causal=causal, qi0=q_offset + iq * block_q, ki0=ik * block_k)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=F32)
    ds = p * (dp - delta_ref[0])
    dq = jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                             preferred_element_type=F32) * sm_scale
    dq_ref[...] = dq_ref[...] + dq[None]


def _flash_bwd_dkv_kernel(*refs, cfg: HyftConfig, sm_scale: float,
                          causal: bool, block_q: int, block_k: int,
                          nq: int, q_offset: int, has_mask: bool):
    if has_mask:
        (q_ref, do_ref, delta_ref, m_ref, l_ref, k_ref, v_ref, mask_ref,
         dk_ref, dv_ref) = refs
    else:
        (q_ref, do_ref, delta_ref, m_ref, l_ref, k_ref, v_ref,
         dk_ref, dv_ref) = refs
        mask_ref = None
    ik, it = pl.program_id(1), pl.program_id(2)
    iq = it % nq  # q-block index inside the fused (group x q-block) axis

    @pl.when(it == 0)
    def _init():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    q = q_ref[0].astype(F32)
    k = k_ref[0].astype(F32)
    v = v_ref[0].astype(F32)
    do = do_ref[0].astype(F32)
    p = _recompute_probs(
        q, k, mask_ref[0] if has_mask else None,
        m_ref[0], l_ref[0], cfg=cfg, sm_scale=sm_scale,
        causal=causal, qi0=q_offset + iq * block_q, ki0=ik * block_k)
    dv = jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                             preferred_element_type=F32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=F32)
    ds = p * (dp - delta_ref[0])
    dk = jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                             preferred_element_type=F32) * sm_scale
    dk_ref[...] = dk_ref[...] + dk[None]
    dv_ref[...] = dv_ref[...] + dv[None]


def _flash_bwd_impl(q3, k3, v3, maskf, do3, o3, m2, l2, *, cfg, sm_scale,
                    causal, bq, bk, group, q_offset, interpret, batch):
    """Backward on pre-padded 3D operands; returns (dq3, dk3, dv3)."""
    BH, Sq, D = q3.shape
    BHkv, Sk = k3.shape[0], k3.shape[1]
    nq, nk = Sq // bq, Sk // bk
    has_mask = maskf is not None
    hq_per_b = BH // batch
    delta = jnp.sum(do3.astype(F32) * o3.astype(F32), axis=-1,
                    keepdims=True)  # (BH, Sq, 1), like m2/l2
    mask3 = maskf[:, None, :] if has_mask else None

    # ---- dq: (bh, q, kv) grid, kv innermost, dq block as carry ------------
    row_spec = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0))
    in_specs = [
        pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, bk, D), lambda b, i, j, g=group: (b // g, j, 0)),
        pl.BlockSpec((1, bk, D), lambda b, i, j, g=group: (b // g, j, 0)),
        pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        row_spec, row_spec, row_spec,
    ]
    operands = [q3, k3, v3, do3, delta, m2, l2]
    if has_mask:
        in_specs.append(pl.BlockSpec(
            (1, 1, bk), lambda b, i, j, h=hq_per_b: (b // h, 0, j)))
        operands.append(mask3)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, cfg=cfg, sm_scale=sm_scale,
                          causal=causal, block_q=bq, block_k=bk,
                          q_offset=q_offset, has_mask=has_mask),
        grid=(BH, nq, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), F32),
        interpret=interpret,
    )(*operands)

    # ---- dk/dv: (bh_kv, kv, group*q) grid, dk/dv blocks as carry ----------
    # the fused inner axis t enumerates (GQA group member, q block); index
    # maps decode it as head = b*group + t // nq, q block = t % nq.
    qrow3 = pl.BlockSpec(
        (1, bq, D), lambda b, j, t, g=group, n=nq: (b * g + t // n, t % n, 0))
    qrow2 = pl.BlockSpec(
        (1, bq, 1), lambda b, j, t, g=group, n=nq: (b * g + t // n, t % n, 0))
    in_specs = [
        qrow3, qrow3, qrow2, qrow2, qrow2,
        pl.BlockSpec((1, bk, D), lambda b, j, t: (b, j, 0)),
        pl.BlockSpec((1, bk, D), lambda b, j, t: (b, j, 0)),
    ]
    operands = [q3, do3, delta, m2, l2, k3, v3]
    hkv_per_b = BHkv // batch
    if has_mask:
        in_specs.append(pl.BlockSpec(
            (1, 1, bk), lambda b, j, t, h=hkv_per_b: (b // h, 0, j)))
        operands.append(mask3)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, cfg=cfg, sm_scale=sm_scale,
                          causal=causal, block_q=bq, block_k=bk, nq=nq,
                          q_offset=q_offset, has_mask=has_mask),
        grid=(BHkv, nk, group * nq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, t: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BHkv, Sk, D), F32),
            jax.ShapeDtypeStruct((BHkv, Sk, D), F32),
        ],
        interpret=interpret,
    )(*operands)
    return dq, dk, dv


# --------------------------------------------------------------------------
# custom VJP plumbing (operates on pre-padded 4D arrays)
# --------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash_attn(q, k, v, maskf, cfg, sm_scale, causal, bq, bk, interpret,
                q_offset):
    o, _, _ = _flash_fwd_impl(
        _h3(q), _h3(k), _h3(v), maskf, cfg=cfg, sm_scale=sm_scale,
        causal=causal, bq=bq, bk=bk, group=q.shape[1] // k.shape[1],
        q_offset=q_offset, interpret=interpret)
    return o.reshape(q.shape)


def _h3(x):
    B, H, S, D = x.shape
    return x.reshape(B * H, S, D)


def _flash_attn_fwd(q, k, v, maskf, cfg, sm_scale, causal, bq, bk, interpret,
                    q_offset):
    o, m2, l2 = _flash_fwd_impl(
        _h3(q), _h3(k), _h3(v), maskf, cfg=cfg, sm_scale=sm_scale,
        causal=causal, bq=bq, bk=bk, group=q.shape[1] // k.shape[1],
        q_offset=q_offset, interpret=interpret)
    return o.reshape(q.shape), (q, k, v, maskf, o, m2, l2)


def _flash_attn_bwd(cfg, sm_scale, causal, bq, bk, interpret, q_offset,
                    res, do):
    q, k, v, maskf, o3, m2, l2 = res
    dq, dk, dv = _flash_bwd_impl(
        _h3(q), _h3(k), _h3(v), maskf, _h3(do.astype(F32)), o3, m2, l2,
        cfg=cfg, sm_scale=sm_scale, causal=causal, bq=bq, bk=bk,
        group=q.shape[1] // k.shape[1], q_offset=q_offset,
        interpret=interpret, batch=q.shape[0])
    dmask = None if maskf is None else jnp.zeros_like(maskf)
    return (dq.reshape(q.shape).astype(q.dtype),
            dk.reshape(k.shape).astype(k.dtype),
            dv.reshape(v.shape).astype(v.dtype), dmask)


_flash_attn.defvjp(_flash_attn_fwd, _flash_attn_bwd)


# --------------------------------------------------------------------------
# public entry point
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=(
    "cfg", "sm_scale", "causal", "block_q", "block_k", "interpret",
    "return_stats", "q_offset"))
def flash_hyft_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         cfg: HyftConfig, sm_scale: float | None = None,
                         causal: bool = True, block_q: int = 128,
                         block_k: int = 128, *, interpret: bool,
                         return_stats: bool = False,
                         kv_len_mask: jax.Array | None = None,
                         q_offset: int = 0):
    """Fused attention with Hyft softmax — trainable and mask-aware.

    Args:
      q: (B, Hq, Sq, D);  k, v: (B, Hkv, Sk, D) with Hq % Hkv == 0 (GQA).
      kv_len_mask: optional (B, Sk) validity mask (bool or float, nonzero =
        valid) — the decode/serving cache mask.  Applied pre-FP2FX exactly
        like the unfused path.
      q_offset: static int added to query positions for the causal mask
        (partial-prefill continuation).
    Returns (B, Hq, Sq, D) in fp32 (callers cast).  Differentiable: the VJP
    runs the fused Pallas backward kernels (recompute from the saved (m, l)
    row stats through the reused DIV/MUL datapath).  With ``return_stats``
    also returns the (m, l) row stats (forward-only; used by the
    cross-device sequence-parallel combine).
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    assert Hq % Hkv == 0
    scale = sm_scale if sm_scale is not None else D ** -0.5
    # sublane-aligned query rows: a 1-row decode query pads to 8 rows
    bq, bk = min(block_q, -(-Sq // 8) * 8), min(block_k, Sk)
    pad_q, pad_k = (-Sq) % bq, (-Sk) % bk
    maskf = None
    if kv_len_mask is not None:
        maskf = kv_len_mask.astype(F32)
    elif pad_k:
        maskf = jnp.ones((B, Sk), F32)
    if pad_q:
        q = _pad0(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = _pad0(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = _pad0(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        maskf = _pad0(maskf, ((0, 0), (0, pad_k)))

    if return_stats:  # forward-only path (sequence-parallel combine)
        o, m2, l2 = _flash_fwd_impl(
            _h3(q), _h3(k), _h3(v), maskf, cfg=cfg, sm_scale=scale,
            causal=causal, bq=bq, bk=bk, group=Hq // Hkv,
            q_offset=q_offset, interpret=interpret)
        o = o.reshape(q.shape)[:, :, :Sq]
        m2 = m2.reshape(B, Hq, -1)[:, :, :Sq]
        l2 = l2.reshape(B, Hq, -1)[:, :, :Sq]
        return o, m2, l2

    out = _flash_attn(q, k, v, maskf, cfg, scale, causal, bq, bk, interpret,
                      q_offset)
    return out[:, :, :Sq]


# --------------------------------------------------------------------------
# split-K decode kernel (Sq = 1)
# --------------------------------------------------------------------------
#
# Decode streams the whole KV cache past a single query row, so the monolithic
# kernel's (bh, q, kv) grid degenerates to one q block of one row.  The decode
# kernel instead (a) folds the GQA group into the tile's row dimension — the
# group's queries share each K/V block load — and (b) splits the KV axis
# across the grid, each split emitting *local* Hyft (max, fixed-sum, acc)
# stats.  The cross-split combine is the paper's L1/L2 tree exactly as
# ``sp_decode_attention`` applies it across devices: integer max over split
# maxima, per-split rescale by the Hyft-approximated exp of the max delta,
# fixed-point sum merge, one ``lod_refloat`` + ``log_div`` finalize.
#
# K/V may arrive FP2FX-quantized (int8 raw + per-(head, position) scale, the
# fp2fx8 KV-cache layout in ``repro.models.attention``); dequantization is
# fused into the kernel's K/V loads so the HBM traffic stays int8.


def _decode_tile(q, k, v, maskrow, cfg: HyftConfig, sm_scale: float):
    """L1 of the decode tree: local Hyft stages 1-2 for one KV split.

    q (gp, dh) — GQA group folded into rows; k/v (bk, dh) fp32 (already
    dequantized); maskrow (1, bk) shared across rows, or (gp, bk) per-row
    (the verify kernel's causal-within-draft mask).  Returns (acc (gp, dh),
    m_loc (gp, 1) raw, l_loc (gp, 1)) — the split-local (max, fixed-sum,
    acc) stats.  Shared verbatim by the contiguous split-K kernel, the
    paged kernel, and the verify kernels, so a page IS a split and the
    bitwise story reduces to the combine order.
    """
    z = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32) * sm_scale
    z = jnp.where(maskrow > F32(0), z, NEG_BIG)
    z_raw = nm.fp2fx(z, cfg.frac_bits, cfg.total_bits)
    zsub = z_raw[:, :: cfg.step] if cfg.step > 1 else z_raw
    m_loc = jnp.max(zsub, axis=-1, keepdims=True)
    e, m = nm.exp_unit(z_raw - m_loc, cfg.frac_bits, cfg.mant_bits)
    addend = nm.expfloat_to_fx(e, m, cfg.mant_bits, cfg.acc_bits)
    l_loc = jnp.sum(addend, axis=-1, keepdims=True)
    p = ((1 << cfg.mant_bits) + m).astype(F32) * nm.pow2_float(e - cfg.mant_bits)
    acc = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                              preferred_element_type=F32)
    return acc, m_loc, l_loc


def _splitk_combine(acc, m_st, l_st, cfg: HyftConfig):
    """L2 of the decode tree: merge per-split Hyft stats across the split
    axis (axis 1) — integer max over split maxima, per-split rescale by the
    Hyft-approximated exp of the max delta, fixed-point sum merge, one
    finalize.  acc (BH, ns, gp, D); m_st (BH, ns, gp, 128) i32; l_st f32.
    Shared by the contiguous and paged decode kernels: identical inputs in
    identical split order give bitwise-identical outputs.
    """
    m_loc = m_st[..., 0]                        # (BH, ns, gp) i32
    l_loc = l_st[..., 0]                        # (BH, ns, gp) f32
    m_glob = jnp.max(m_loc, axis=1, keepdims=True)
    alpha = hyft_alpha(m_loc - m_glob, cfg)     # per-split rescale
    l_glob = jnp.sum(nm.fx_quantize(l_loc * alpha, cfg.acc_bits), axis=1)
    acc_glob = jnp.sum(acc * alpha[..., None], axis=1)   # (BH, gp, D)
    return hyft_finalize(acc_glob, l_glob[..., None], cfg)


def _dequant(x, s_ref, h):
    """Fused fp2fx8 dequant of one (bk, D) K/V tile.  ``s_ref`` holds the
    split's (Hkv, bk) per-(head, position) scales as lane rows (a block that
    spans the head axis, so Mosaic accepts any split width); head ``h``'s row
    is broadcast over D and transposed into the (bk, D) multiplier — the
    very products of ``x * scale[:, None]``."""
    row = s_ref[0, pl.ds(h, 1), :]                      # (1, bk)
    return x * jnp.broadcast_to(row, (x.shape[1], row.shape[1])).T


def _splitk_kernel(*refs, cfg: HyftConfig, sm_scale: float, quantized: bool,
                   paged: bool, group: int):
    """One (batch, KV split, kv head) step of the split-K machine: the L1
    tile stats through ``_decode_tile``.  Shared by the contiguous and paged
    decode and verify kernels, so a page IS a split and the bitwise story
    reduces to the combine order.  ``group`` > 0 marks the verify layout:
    the (sp, bk) per-draft-lane mask expands over the GQA group rows."""
    if paged:
        refs = refs[1:]  # the block table is consumed by the index maps
    if quantized:
        q_ref, k_ref, v_ref, ks_ref, vs_ref, mask_ref, acc_ref, m_ref, l_ref = refs
    else:
        q_ref, k_ref, v_ref, mask_ref, acc_ref, m_ref, l_ref = refs
    q = q_ref[0].astype(F32)              # (rows, dh) — GQA group (x draft)
    if paged:                             # (ps, dh) — one physical page
        k, v, mask = k_ref[0, 0], v_ref[0, 0], mask_ref[0, 0]
    else:                                 # (bk, dh)
        k, v, mask = k_ref[0], v_ref[0], mask_ref[0]
    k, v = k.astype(F32), v.astype(F32)
    if quantized:                         # dequant fused into the load
        h = pl.program_id(2)
        k, v = _dequant(k, ks_ref, h), _dequant(v, vs_ref, h)
    if group:
        mask = _verify_mask_rows(mask, group)
    acc, m_loc, l_loc = _decode_tile(q, k, v, mask, cfg, sm_scale)
    acc_ref[...] = acc[None, None]
    m_ref[...] = jnp.broadcast_to(m_loc[None, None], m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_loc[None, None], l_ref.shape)


def _splitk_stats(q3, k, v, mask, *, k_scale, v_scale, block_tables, Hkv: int,
                  bk: int, cfg: HyftConfig, sm_scale: float, group: int,
                  interpret: bool):
    """Run ``_splitk_kernel`` over a (batch, KV split, kv head) grid and
    return the per-split local stats (acc, m, l) for ``_splitk_combine``.

    q3: (B * Hkv, rows, D).  Contiguous layout: k/v (B * Hkv, Sk, D),
    scales (B, Hkv, Sk), mask (B, mr, Sk), splits of ``bk``.  Paged layout
    (``block_tables`` (B, nb)): k/v (n_pages, Hkv, ps, D), scales
    (n_pages, Hkv, ps), mask (B, nb, mr, ps); pages are the splits and
    ``bk`` is the page size.  The kv head is the innermost grid axis, so a
    scale block (all heads of one split) stays resident across it.
    """
    BHkv, rows, D = q3.shape
    B = BHkv // Hkv
    paged = block_tables is not None
    quantized = k_scale is not None
    mr = mask.shape[-2]
    if paged:
        ns = block_tables.shape[1]
        kv_spec = pl.BlockSpec((1, 1, bk, D),
                               lambda b, j, h, bt: (bt[b, j], h, 0, 0))
        s_spec = pl.BlockSpec((1, Hkv, bk), lambda b, j, h, bt: (bt[b, j], 0, 0))
        m_spec = pl.BlockSpec((1, 1, mr, bk), lambda b, j, h, bt: (b, j, 0, 0))
    else:
        ns = k.shape[1] // bk
        kv_spec = pl.BlockSpec((1, bk, D), lambda b, j, h: (b * Hkv + h, j, 0))
        s_spec = pl.BlockSpec((1, Hkv, bk), lambda b, j, h: (b, 0, j))
        m_spec = pl.BlockSpec((1, mr, bk), lambda b, j, h: (b, 0, j))
    in_specs = [pl.BlockSpec((1, rows, D),
                             lambda b, j, h, *_: (b * Hkv + h, 0, 0)),
                kv_spec, kv_spec]
    operands = [q3, k, v]
    if quantized:
        in_specs += [s_spec, s_spec]
        operands += [k_scale, v_scale]
    in_specs.append(m_spec)
    operands.append(mask)
    out_specs = [
        pl.BlockSpec((1, 1, rows, w), lambda b, j, h, *_: (b * Hkv + h, j, 0, 0))
        for w in (D, 128, 128)]
    out_shape = [jax.ShapeDtypeStruct((BHkv, ns, rows, w), dt)
                 for w, dt in ((D, F32), (128, I32), (128, F32))]
    kern = functools.partial(_splitk_kernel, cfg=cfg, sm_scale=sm_scale,
                             quantized=quantized, paged=paged, group=group)
    if paged:
        from jax.experimental.pallas import tpu as pltpu
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, ns, Hkv), in_specs=in_specs,
            out_specs=out_specs)
        return pl.pallas_call(kern, grid_spec=grid_spec, out_shape=out_shape,
                              interpret=interpret)(
            block_tables.astype(I32), *operands)
    return pl.pallas_call(kern, grid=(B, ns, Hkv), in_specs=in_specs,
                          out_specs=out_specs, out_shape=out_shape,
                          interpret=interpret)(*operands)


def _pad_kv(k, v, k_scale, v_scale, pad_k):
    """Zero-pad contiguous K/V (and their scales) along the KV axis."""
    k = _pad0(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    v = _pad0(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    if k_scale is not None:
        k_scale = _pad0(k_scale, ((0, 0), (0, 0), (0, pad_k)))
        v_scale = _pad0(v_scale, ((0, 0), (0, 0), (0, pad_k)))
    return k, v, k_scale, v_scale


def _group_rows(q, Hkv: int, rows: int):
    """(B, Hq, 1, D) decode queries -> (B * Hkv, rows, D), the GQA group
    folded into sublane-aligned tile rows."""
    B, Hq, _, D = q.shape
    q3 = q[:, :, 0, :].reshape(B, Hkv, Hq // Hkv, D)
    q3 = _pad0(q3, ((0, 0), (0, 0), (0, rows - Hq // Hkv), (0, 0)))
    return q3.reshape(B * Hkv, rows, D)


@functools.partial(jax.jit, static_argnames=(
    "cfg", "sm_scale", "block_k", "interpret"))
def flash_hyft_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                      cfg: HyftConfig, sm_scale: float | None = None,
                      block_k: int = 256, *, interpret: bool,
                      kv_len_mask: jax.Array | None = None,
                      k_scale: jax.Array | None = None,
                      v_scale: jax.Array | None = None):
    """Split-K fused decode attention with Hyft softmax (Sq = 1).

    Args:
      q: (B, Hq, 1, D);  k, v: (B, Hkv, Sk, D) float — or int8 FP2FX raws
        with ``k_scale``/``v_scale`` (B, Hkv, Sk) fp32 per-(head, position)
        scales, in which case dequantization fuses into the K/V loads.
      kv_len_mask: optional (B, Sk) validity mask (nonzero = valid); decode
        always masks (cache padding), so a missing mask means all-valid.
    Returns (B, Hq, 1, D) fp32.  Forward-only (decode is not trained
    through); for a single KV split the result is bitwise identical to the
    monolithic fused kernel on the same block.
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    assert Sq == 1 and Hq % Hkv == 0
    g = Hq // Hkv
    scale = sm_scale if sm_scale is not None else D ** -0.5
    bk = min(block_k, -(-Sk // 128) * 128)  # lane-aligned KV blocks
    pad_k = (-Sk) % bk
    maskf = (kv_len_mask.astype(F32) if kv_len_mask is not None
             else jnp.ones((B, Sk), F32))
    if pad_k:
        k, v, k_scale, v_scale = _pad_kv(k, v, k_scale, v_scale, pad_k)
        maskf = _pad0(maskf, ((0, 0), (0, pad_k)))
    Skp = Sk + pad_k
    gp = -(-g // 8) * 8  # sublane-aligned group rows

    acc, m_st, l_st = _splitk_stats(
        _group_rows(q, Hkv, gp), k.reshape(B * Hkv, Skp, D),
        v.reshape(B * Hkv, Skp, D), maskf[:, None, :], k_scale=k_scale,
        v_scale=v_scale, block_tables=None, Hkv=Hkv, bk=bk, cfg=cfg,
        sm_scale=scale, group=0, interpret=interpret)

    # ---- L2: integer-max / fixed-sum tree combine across KV splits
    out = _splitk_combine(acc, m_st, l_st, cfg)
    return out[:, :g].reshape(B, Hkv, g, D).reshape(B, Hq, 1, D)


# --------------------------------------------------------------------------
# paged decode kernel (Sq = 1, block-table K/V gather)
# --------------------------------------------------------------------------
#
# The split-K decode kernel assumes a contiguous (B, Hkv, Sk, D) KV stripe
# per sequence.  The paged serving layout instead keeps one global pool of
# fixed-size pages — (n_pages, Hkv, page_size, D), dense or int8 fp2fx8 —
# and a per-sequence block table mapping virtual KV block j to a physical
# page.  The kernel below is the same split-K machine with pages as splits:
# the block table rides in as a scalar-prefetch operand so the BlockSpec
# index maps can route grid step (b, j, h) to physical page bt[b, j] (the
# DMA for the next page issues while this one computes — on TPU the gather
# is free).  Each page emits the same local (max, fixed-sum, acc) stats via
# ``_decode_tile`` and the combine is ``_splitk_combine`` — so with pages
# laid out sequentially (bt[b, j] == j over a contiguous pool) the result
# is bitwise identical to ``flash_hyft_decode`` at block_k == page_size.
# The validity mask rides in as (B, nb, 1, ps), one full-width row block
# per page, so any page size compiles (int8 pages down to 16 tokens).


@functools.partial(jax.jit, static_argnames=("cfg", "sm_scale", "interpret"))
def flash_hyft_decode_paged(q: jax.Array, k_pages: jax.Array,
                            v_pages: jax.Array, block_tables: jax.Array,
                            cfg: HyftConfig, sm_scale: float | None = None,
                            *, interpret: bool,
                            kv_len_mask: jax.Array | None = None,
                            k_scale: jax.Array | None = None,
                            v_scale: jax.Array | None = None):
    """Split-K fused decode attention over a paged KV pool (Sq = 1).

    Args:
      q: (B, Hq, 1, D);  k_pages, v_pages: (n_pages, Hkv, page_size, D)
        float — or int8 FP2FX raws with ``k_scale``/``v_scale``
        (n_pages, Hkv, page_size) fp32 scales (the fp2fx8 page layout),
        in which case dequantization fuses into the page loads.
      block_tables: (B, nb) int32 — virtual KV block j of sequence b lives
        in physical page ``block_tables[b, j]`` (scalar-prefetched so the
        grid's BlockSpec index maps do the gather).
      kv_len_mask: optional (B, nb * page_size) validity mask over the
        *virtual* KV axis (nonzero = valid); missing means all-valid.
    Returns (B, Hq, 1, D) fp32.  With ``block_tables[b, j] == j`` over a
    contiguous pool this is bitwise identical to ``flash_hyft_decode`` at
    ``block_k == page_size`` (same tile arithmetic, same combine order).
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, ps, _ = k_pages.shape
    nb = block_tables.shape[1]
    assert Sq == 1 and Hq % Hkv == 0
    g = Hq // Hkv
    scale = sm_scale if sm_scale is not None else D ** -0.5
    gp = -(-g // 8) * 8  # sublane-aligned group rows
    maskf = (kv_len_mask.astype(F32) if kv_len_mask is not None
             else jnp.ones((B, nb * ps), F32))

    acc, m_st, l_st = _splitk_stats(
        _group_rows(q, Hkv, gp), k_pages, v_pages,
        maskf.reshape(B, nb, 1, ps), k_scale=k_scale, v_scale=v_scale,
        block_tables=block_tables, Hkv=Hkv, bk=ps, cfg=cfg, sm_scale=scale,
        group=0, interpret=interpret)

    out = _splitk_combine(acc, m_st, l_st, cfg)
    return out[:, :g].reshape(B, Hkv, g, D).reshape(B, Hq, 1, D)


# --------------------------------------------------------------------------
# speculative-decode verify kernel (Sq = K + 1 draft tokens per slot)
# --------------------------------------------------------------------------
#
# Speculative decoding turns K one-token decode steps into ONE prefill-shaped
# verification: the model scores [last_token, draft_1..draft_K] in a single
# pass and keeps the longest accepted prefix.  That is exactly the regime the
# Hyft pipeline amortizes best — the softmax work is batched along the
# sequence axis, so the per-token share of stage-1/2/3 overhead drops by the
# draft length (the same observation Vasyltsov & Chang make for batched
# softmax approximation).
#
# The kernel is the split-K decode machine with the draft axis folded into
# the tile rows alongside the GQA group: q rows enumerate (group member,
# draft position), every row shares each K/V block load, and each split
# emits the same local (max, fixed-sum, acc) stats through ``_decode_tile``
# merged by ``_splitk_combine``.  The ONLY new ingredient is the mask: draft
# token t sits at cache position pos+t and must see exactly [0, pos+t] —
# a per-ROW validity mask (causal within the draft, ragged lengths across
# the batch) instead of the decode kernel's per-slot row.  The caller
# supplies it as (B, Sq, Lk); it rides in un-duplicated (the mask depends
# only on the draft lane) and expands over the GQA group inside the tile,
# so at Sq == 1 the kernel is bitwise identical to ``flash_hyft_decode`` /
# ``flash_hyft_decode_paged`` on the same splits.
#
# Both KV layouts are served by one entry point: contiguous (B, Hkv, Sk, D)
# stripes split by ``block_k``, or a paged pool + scalar-prefetched block
# tables with pages as splits.  fp2fx8 dequantization fuses into the K/V
# loads exactly as in the decode kernels.


# Most chunk lanes per verify kernel call.  The per-split stats are
# (B * Hkv, splits, Hq // Hkv * lanes, D) fp32, so a 512-token chunk over
# 16-token pages would hold ~3 GB of them per layer at olmo-1b widths; a
# longer chunk runs as a ``lax.map`` over lane blocks, each lane's
# arithmetic unchanged.
VERIFY_LANE_BLOCK = 128


def _verify_mask_rows(mask, group: int):
    """(sp, bk) per-draft-lane mask -> (group * sp, bk) tile rows.  The
    mask depends only on the draft lane, so it rides in UN-duplicated and
    expands over the GQA group inside the tile (a VMEM broadcast) instead
    of streaming a group-fold redundant HBM buffer."""
    sp, bk = mask.shape
    return jnp.broadcast_to(mask[None], (group, sp, bk)).reshape(
        group * sp, bk)


@functools.partial(jax.jit, static_argnames=(
    "cfg", "sm_scale", "block_k", "interpret"))
def flash_hyft_verify(q: jax.Array, k: jax.Array, v: jax.Array,
                      kv_pos_mask: jax.Array, cfg: HyftConfig,
                      sm_scale: float | None = None, block_k: int = 256,
                      *, interpret: bool,
                      block_tables: jax.Array | None = None,
                      k_scale: jax.Array | None = None,
                      v_scale: jax.Array | None = None):
    """Split-K fused chunk attention with Hyft softmax (Sq = token chunk).

    The kernel behind ``verify_attention``'s kernel mode, and through it
    ``model.prefill_chunk`` (DESIGN.md §12): prompt-chunk prefill,
    prefix-hit suffixes, and speculative-decode verify (Sq = draft_k + 1)
    all lower to this one entry.

    Args:
      q: (B, Hq, Sq, D) — the chunk's queries (for verify, the
        [last_token, draft_1..draft_K] lanes).
      k, v: contiguous (B, Hkv, Sk, D) stripes, or — with ``block_tables``
        (B, nb) — a paged pool (n_pages, Hkv, page_size, D).  Either layout
        may be int8 FP2FX raws with ``k_scale``/``v_scale`` fp32 scales
        (dequantization fuses into the loads).
      kv_pos_mask: (B, Sq, Lk) per-draft-token validity over the (virtual)
        KV axis, nonzero = visible — the causal-within-draft mask
        ``kv_index <= pos + t`` plus any cache-length masking.  Ragged
        draft lengths across the batch ride in here (a padded draft row's
        outputs are discarded by the caller).
    A chunk longer than ``VERIFY_LANE_BLOCK`` runs in lane blocks.
    Returns (B, Hq, Sq, D) fp32.  Forward-only.  At Sq == 1 this is bitwise
    identical to ``flash_hyft_decode`` (same splits) / ``_decode_paged``
    (pages as splits): the tile arithmetic is the shared ``_decode_tile``
    and the combine the shared ``_splitk_combine``; only the mask gained a
    row axis.
    """
    maskf = kv_pos_mask.astype(F32)       # (B, Sq, Lk)
    kw = dict(cfg=cfg, sm_scale=sm_scale, block_k=block_k,
              interpret=interpret, block_tables=block_tables,
              k_scale=k_scale, v_scale=v_scale)
    B, Hq, Sq, D = q.shape
    block_q = VERIFY_LANE_BLOCK
    if Sq <= block_q:
        return _verify_lanes(q, k, v, maskf, **kw)
    nq = -(-Sq // block_q)
    pad = nq * block_q - Sq               # padded lanes: fully masked
    qs = _pad0(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    qs = qs.reshape(B, Hq, nq, block_q, D).transpose(2, 0, 1, 3, 4)
    ms = _pad0(maskf, ((0, 0), (0, pad), (0, 0)))
    ms = ms.reshape(B, nq, block_q, -1).transpose(1, 0, 2, 3)
    out = jax.lax.map(lambda a: _verify_lanes(a[0], k, v, a[1], **kw),
                      (qs, ms))
    out = out.transpose(1, 2, 0, 3, 4).reshape(B, Hq, nq * block_q, D)
    return out[:, :, :Sq]


def _verify_lanes(q, k, v, maskf, *, cfg, sm_scale, block_k, interpret,
                  block_tables, k_scale, v_scale):
    """``flash_hyft_verify`` on one block of chunk lanes (one kernel call)."""
    B, Hq, Sq, D = q.shape
    Hkv = k.shape[1]
    assert Hq % Hkv == 0
    g = Hq // Hkv
    scale = sm_scale if sm_scale is not None else D ** -0.5
    sp = -(-Sq // 8) * 8                  # sublane-aligned draft rows
    rows = g * sp                         # tile rows: (group, draft) folded

    q3 = q.reshape(B, Hkv, g, Sq, D)
    q3 = _pad0(q3, ((0, 0), (0, 0), (0, 0), (0, sp - Sq), (0, 0)))
    q3 = q3.reshape(B * Hkv, rows, D)

    if block_tables is not None:  # ---- paged layout: pages as splits ----
        ps = k.shape[2]
        nb = block_tables.shape[1]
        maskE = _pad0(maskf, ((0, 0), (0, sp - Sq), (0, 0)))  # (B, sp, Lv)
        maskE = maskE.reshape(B, sp, nb, ps).transpose(0, 2, 1, 3)
        acc, m_st, l_st = _splitk_stats(
            q3, k, v, maskE, k_scale=k_scale, v_scale=v_scale,
            block_tables=block_tables, Hkv=Hkv, bk=ps, cfg=cfg,
            sm_scale=scale, group=g, interpret=interpret)
    else:  # ---- contiguous layout: block_k splits, as flash_hyft_decode ----
        Sk = k.shape[2]
        bk = min(block_k, -(-Sk // 128) * 128)  # lane-aligned KV blocks
        pad_k = (-Sk) % bk
        if pad_k:
            k, v, k_scale, v_scale = _pad_kv(k, v, k_scale, v_scale, pad_k)
            maskf = _pad0(maskf, ((0, 0), (0, 0), (0, pad_k)))
        Skp = Sk + pad_k
        maskE = _pad0(maskf, ((0, 0), (0, sp - Sq), (0, 0)))  # (B, sp, Skp)
        acc, m_st, l_st = _splitk_stats(
            q3, k.reshape(B * Hkv, Skp, D), v.reshape(B * Hkv, Skp, D), maskE,
            k_scale=k_scale, v_scale=v_scale, block_tables=None, Hkv=Hkv,
            bk=bk, cfg=cfg, sm_scale=scale, group=g, interpret=interpret)

    out = _splitk_combine(acc, m_st, l_st, cfg)        # (BH, rows, D)
    out = out.reshape(B, Hkv, g, sp, D)[:, :, :, :Sq]
    return out.reshape(B, Hq, Sq, D)
