"""Public jit'd wrappers around the Pallas kernels + the attention contract.

``interpret`` is decided here and nowhere else (``_auto_interpret``): the
kernels compile through Mosaic on a TPU backend and run in the Pallas
interpreter on any other.  The kernel entry points take it as a required
argument, so no caller can fall into the interpreter on a chip by leaving
it out.  ``hyft_softmax`` is differentiable — its VJP is the backward
*kernel* (the accelerator's reused DIV/MUL datapath), mirroring
``repro.core.hyft.hyft_softmax``.

Mask/stats contract (DESIGN.md §3) — shared by all three attention modes
(``unfused`` / ``chunked`` / ``kernel``):

  * ``kv_len_mask``: optional per-batch KV validity mask of shape (B, Sk);
    bool or float, nonzero = valid.  Masking is applied to the *float scores
    before FP2FX* so invalid positions saturate to the fixed-point minimum
    and their Hyft probability flushes to zero.  ``as_mask_f`` normalizes it
    to float32 once, at the dispatch boundary, so the differentiable paths
    (custom_vjp) see a float-typed side input with a well-defined zero
    cotangent.
  * ``q_offset``: static int added to query positions for the causal mask.
  * row stats: every online mode carries per-row ``(m, l)`` — the int32
    fixed-point running max and the fp32 fixed-point probability sum — and
    the fused kernel saves exactly these as its backward residuals
    (``return_stats`` exposes them for the cross-device combine).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.hyft import HyftConfig
from repro.kernels import hyft_softmax as _hk
from repro.kernels.flash_attention import (  # noqa: F401
    flash_hyft_attention, flash_hyft_decode, flash_hyft_decode_paged,
    flash_hyft_verify)

F32 = jnp.float32


def _auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


def as_mask_f(kv_len_mask) -> jax.Array | None:
    """Normalize a KV validity mask (bool/int/float or None) to float32."""
    if kv_len_mask is None:
        return None
    return kv_len_mask.astype(F32)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def hyft_softmax(z: jax.Array, cfg: HyftConfig) -> jax.Array:
    return _hk.hyft_softmax_fwd_kernel(z, cfg, interpret=_auto_interpret())


def _fwd(z, cfg):
    s = _hk.hyft_softmax_fwd_kernel(z, cfg, interpret=_auto_interpret())
    return s, (s, jnp.zeros((0,), z.dtype))


def _bwd(cfg, res, dy):
    s, dt_marker = res
    dz = _hk.hyft_softmax_bwd_kernel(s, dy, cfg, interpret=_auto_interpret())
    return (dz.astype(dt_marker.dtype),)


hyft_softmax.defvjp(_fwd, _bwd)


def hyft_attention(q, k, v, cfg: HyftConfig, sm_scale=None, causal=True,
                   block_q=128, block_k=128, kv_len_mask=None, q_offset=0,
                   return_stats=False):
    """Fused flash attention with Hyft softmax — trainable and mask-aware.

    The production ``attn_mode="kernel"`` path for prefill, decode (pass the
    cache validity mask as ``kv_len_mask``) and training (differentiable via
    the fused Pallas backward kernels).
    """
    return flash_hyft_attention(q, k, v, cfg, sm_scale=sm_scale, causal=causal,
                                block_q=block_q, block_k=block_k,
                                interpret=_auto_interpret(),
                                return_stats=return_stats,
                                kv_len_mask=as_mask_f(kv_len_mask),
                                q_offset=q_offset)


def hyft_decode_attention(q, k, v, cfg: HyftConfig, sm_scale=None,
                          block_k=256, kv_len_mask=None, k_scale=None,
                          v_scale=None):
    """Split-K fused decode attention (Sq = 1) with Hyft softmax.

    The serving fast path: the KV axis is split across the kernel grid, each
    split emits local Hyft (max, fixed-sum, acc) stats, and the cross-split
    combine is the paper's L1/L2 tree (integer max + rescaled fixed sums).
    Pass int8 ``k``/``v`` with ``k_scale``/``v_scale`` (the fp2fx8 KV-cache
    layout) to fuse dequantization into the K/V loads.
    """
    return flash_hyft_decode(q, k, v, cfg, sm_scale=sm_scale, block_k=block_k,
                             interpret=_auto_interpret(),
                             kv_len_mask=as_mask_f(kv_len_mask),
                             k_scale=k_scale, v_scale=v_scale)


def hyft_paged_decode_attention(q, k_pages, v_pages, block_tables,
                                cfg: HyftConfig, sm_scale=None,
                                kv_len_mask=None, k_scale=None, v_scale=None):
    """Split-K fused decode attention over a paged KV pool (Sq = 1).

    The block table is scalar-prefetched so the kernel's index maps gather
    physical pages directly; each page emits local Hyft (max, fixed-sum,
    acc) stats and the cross-page combine is the same L1/L2 tree as the
    contiguous split-K kernel — bitwise-equal to it when pages are laid out
    sequentially.  Pass int8 pages + ``k_scale``/``v_scale`` pools (the
    fp2fx8 page layout) to fuse dequantization into the page loads.
    """
    return flash_hyft_decode_paged(q, k_pages, v_pages, block_tables, cfg,
                                   sm_scale=sm_scale,
                                   interpret=_auto_interpret(),
                                   kv_len_mask=as_mask_f(kv_len_mask),
                                   k_scale=k_scale, v_scale=v_scale)


def hyft_verify_attention(q, k, v, kv_pos_mask, cfg: HyftConfig,
                          sm_scale=None, block_k=256, block_tables=None,
                          k_scale=None, v_scale=None):
    """Split-K fused verify attention (Sq = draft chunk) with Hyft softmax.

    The speculative-decoding fast path: scores the [last_token, drafts]
    chunk of every slot in one kernel call, with a per-draft-token
    ``kv_pos_mask`` (B, Sq, Lk) carrying the causal-within-draft frontier
    and ragged draft lengths.  ``block_tables`` switches K/V to the paged
    pool layout (pages as splits); int8 K/V with ``k_scale``/``v_scale``
    fuse fp2fx8 dequantization into the loads.  At Sq == 1 this is bitwise
    identical to the decode kernels on the same splits.
    """
    return flash_hyft_verify(q, k, v, as_mask_f(kv_pos_mask), cfg,
                             sm_scale=sm_scale, block_k=block_k,
                             interpret=_auto_interpret(),
                             block_tables=block_tables,
                             k_scale=k_scale, v_scale=v_scale)
