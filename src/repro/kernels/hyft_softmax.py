"""Pallas TPU kernels for Hyft softmax (forward and backward).

TPU adaptation of the accelerator datapath (DESIGN.md §2): the row tile lives
in VMEM; every hardware block (FP2FX, Booth shift-add, field assembly, fixed
adder tree, LOD, log-subtract divide) becomes int32 VPU arithmetic on the
bitcast tile — no transcendentals, no FP divides.  The arithmetic is the
*same jnp graph* as the pure-JAX oracle (``repro.core.hyft``), traced inside
the kernel, so kernel and oracle agree bit-for-bit.

Tiling: grid over row blocks, each program owns a ``(block_rows, cols)`` tile
(full row resident — the standalone kernel targets rows that fit VMEM; longer
rows use the fused flash kernel which blocks the row dimension online).

The kernels read and write fp32 only; the wrappers cast to and from the
format's ``cfg.dtype``.  Mosaic on v5e cannot pack or load 16-bit floats
(``tpu.pack_subelements`` f32->f16 is refused), and the oracle's result is
the fp32 datapath output cast to ``cfg.dtype``, so casting outside the
kernel gives the same bits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import numerics as nm
from repro.core.hyft import HyftConfig

F32 = jnp.float32


def _fwd_kernel(z_ref, o_ref, *, cfg: HyftConfig):
    z = z_ref[...]
    # --- input pre-processor: FP2FX + (strided) max search -----------------
    z_raw = nm.fp2fx(z, cfg.frac_bits, cfg.total_bits)
    zmax = jnp.max(z_raw[:, :: cfg.step] if cfg.step > 1 else z_raw,
                   axis=-1, keepdims=True)
    # --- hybrid exponent unit: fixed-in, float-fields-out -------------------
    e, m = nm.exp_unit(z_raw - zmax, cfg.frac_bits, cfg.mant_bits)
    # --- hybrid adder tree: FP2FX @ acc_bits, accumulate, LOD refloat -------
    addend = nm.expfloat_to_fx(e, m, cfg.mant_bits, cfg.acc_bits)
    denom = jnp.sum(addend, axis=-1, keepdims=True)
    e_b, m_b = nm.lod_refloat(denom, cfg.mant_bits)
    # --- hybrid DIV unit: log-subtract division ------------------------------
    o_ref[...] = nm.log_div(e, m, e_b, m_b, cfg.mant_bits)


def _bwd_kernel(s_ref, dy_ref, dz_ref, *, cfg: HyftConfig):
    s = s_ref[...]
    dy = dy_ref[...]
    # --- reuse of the DIV/MUL unit as log-domain multiplier (Eq. 10) --------
    prods = nm.log_mul(dy, s, cfg.mant_bits, half_range=True)
    # --- signed fixed-point adder tree for the dot product -------------------
    prods_q = nm.fx_quantize(prods, cfg.bwd_acc_bits)
    dot = jnp.sum(prods_q, axis=-1, keepdims=True)
    diff = nm.fx_quantize(dy, cfg.bwd_acc_bits) - dot
    dz_ref[...] = nm.log_mul(diff, s, cfg.mant_bits, half_range=True)


def _row_blocks(rows: int, cols: int, block_rows: int | None) -> int:
    """Row-tile size, clamped to the actual row count (a block can never be
    larger than the padded input it tiles)."""
    if block_rows is not None:
        return max(1, min(block_rows, rows))
    # keep in+out+int32 intermediates within ~6 MB of VMEM, MXU-aligned rows
    budget = 6 * 1024 * 1024
    per_row = cols * 4 * 6  # tile + out + ~4 int32 temps
    br = max(8, min(512, budget // max(per_row, 1)))
    return min(max(8, (br // 8) * 8), max(rows, 1))


@functools.partial(jax.jit, static_argnames=("cfg", "block_rows", "interpret"))
def hyft_softmax_fwd_kernel(z: jax.Array, cfg: HyftConfig,
                            block_rows: int | None = None, *,
                            interpret: bool) -> jax.Array:
    """Row-tiled forward kernel. ``z``: (..., cols); softmax over last axis."""
    shape = z.shape
    cols = shape[-1]
    z2 = z.reshape(-1, cols).astype(F32)
    rows = z2.shape[0]
    br = _row_blocks(rows, cols, block_rows)
    pad = (-rows) % br
    if pad:
        z2 = jnp.pad(z2, ((0, pad), (0, 0)))
    grid = (z2.shape[0] // br,)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, cfg=cfg),
        grid=grid,
        in_specs=[pl.BlockSpec((br, cols), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(z2.shape, F32),
        interpret=interpret,
    )(z2)
    if pad:
        out = out[:rows]
    return out.reshape(shape).astype(cfg.dtype)


@functools.partial(jax.jit, static_argnames=("cfg", "block_rows", "interpret"))
def hyft_softmax_bwd_kernel(s: jax.Array, dy: jax.Array, cfg: HyftConfig,
                            block_rows: int | None = None, *,
                            interpret: bool) -> jax.Array:
    """Row-tiled backward kernel: dz = s * (dy - <dy, s>) in Hyft arithmetic."""
    shape = s.shape
    cols = shape[-1]
    s2 = s.reshape(-1, cols).astype(F32)
    dy2 = dy.reshape(-1, cols).astype(F32)
    rows = s2.shape[0]
    br = _row_blocks(rows, cols, block_rows)
    pad = (-rows) % br
    if pad:
        s2 = jnp.pad(s2, ((0, pad), (0, 0)))
        dy2 = jnp.pad(dy2, ((0, pad), (0, 0)))
    grid = (s2.shape[0] // br,)
    out = pl.pallas_call(
        functools.partial(_bwd_kernel, cfg=cfg),
        grid=grid,
        in_specs=[pl.BlockSpec((br, cols), lambda i: (i, 0)),
                  pl.BlockSpec((br, cols), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(s2.shape, F32),
        interpret=interpret,
    )(s2, dy2)
    if pad:
        out = out[:rows]
    return out.reshape(shape).astype(cfg.dtype)
