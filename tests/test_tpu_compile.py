"""Compile every main-path Pallas kernel for a described TPU v5e.

The interpret-mode tests run the kernels' arithmetic on the CPU but never
ask Mosaic whether it can tile them.  Here each kernel is lowered and
compiled by the TPU compiler for one chip of a ``v5e:2x2`` topology that is
described, not attached, at olmo-1b widths (16 heads x 128, 2048-token
context), and the executable must hold the kernel (``tpu_custom_call``).
Nothing runs, so these say nothing about results or speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and under pytest-xdist
every worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core.hyft import HYFT16, HYFT32
from repro.core.registry import hyft_config_for
from repro.kernels import flash_attention as fa
from repro.kernels import hyft_softmax as hs

F32, BF16, I8, I32 = jnp.float32, jnp.bfloat16, jnp.int8, jnp.int32
H, D, CTX = 16, 128, 2048       # olmo-1b heads, head dim, context
B = 2
PS = 16                         # ServeConfig.page_size default
SLOTS = 8                       # the serving cell's slot pool
NB = CTX // PS
CFG = hyft_config_for("hyft16")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache off
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
            jax.config.update("jax_enable_compilation_cache", was)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _compiled_text(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(one_chip, fn, *shapes):
    assert "tpu_custom_call" in _compiled_text(one_chip, fn, *shapes)


@pytest.mark.parametrize("masked", [False, True])
def test_flash_forward(one_chip, masked):
    S = 512
    qkv = [((B, H, S, D), BF16)] * 3

    def fn(q, k, v, *m):
        return fa.flash_hyft_attention(q, k, v, CFG, interpret=False,
                                       kv_len_mask=m[0] if m else None)
    _assert_kernel(one_chip, fn, *qkv, *([((B, S), F32)] if masked else []))


@pytest.mark.parametrize("masked", [False, True])
def test_flash_forward_backward(one_chip, masked):
    """The training path: the fused forward plus both backward kernels."""
    S = 512
    qkv = [((B, H, S, D), BF16)] * 3

    def fn(q, k, v, *m):
        def loss(q, k, v):
            o = fa.flash_hyft_attention(q, k, v, CFG, interpret=False,
                                        kv_len_mask=m[0] if m else None)
            return jnp.sum(o)
        return jax.grad(loss, (0, 1, 2))(q, k, v)
    text = _compiled_text(one_chip, fn, *qkv,
                          *([((B, S), F32)] if masked else []))
    assert text.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("quantized", [False, True])
def test_decode_dense(one_chip, quantized):
    kv_dt = I8 if quantized else BF16
    shapes = [((8, H, 1, D), BF16), ((8, H, CTX, D), kv_dt),
              ((8, H, CTX, D), kv_dt), ((8, CTX), F32)]
    if quantized:
        shapes += [((8, H, CTX), F32)] * 2

    def fn(q, k, v, m, *s):
        return fa.flash_hyft_decode(q, k, v, CFG, interpret=False,
                                    kv_len_mask=m,
                                    k_scale=s[0] if s else None,
                                    v_scale=s[1] if s else None)
    _assert_kernel(one_chip, fn, *shapes)


@pytest.mark.parametrize("quantized", [False, True])
def test_decode_paged(one_chip, quantized):
    n_pages = B * NB + 1
    kv_dt = I8 if quantized else BF16
    shapes = [((B, H, 1, D), BF16), ((n_pages, H, PS, D), kv_dt),
              ((n_pages, H, PS, D), kv_dt), ((B, NB), I32),
              ((B, CTX), F32)]
    if quantized:
        shapes += [((n_pages, H, PS), F32)] * 2

    def fn(q, kp, vp, bt, m, *s):
        return fa.flash_hyft_decode_paged(q, kp, vp, bt, CFG, interpret=False,
                                          kv_len_mask=m,
                                          k_scale=s[0] if s else None,
                                          v_scale=s[1] if s else None)
    text = _compiled_text(one_chip, fn, *shapes)
    # one kernel, one result: the merged (B * Hkv, rows, D) = (B, Hq, D)
    # f32 output, no per-page stats (a tuple result would start with "(")
    results = re.findall(r"= (\S+) custom-call\([^\n]*tpu_custom_call", text)
    assert len(results) == 1 and results[0].startswith(f"f32[{B},{H},{D}]{{")
    # the grid at the serving shapes (8 slots, 2048-token block tables)
    P, blocks, vmem = fa.paged_decode_plan(H, H, D, PS, NB, kv_dt, quantized)
    assert P * PS % 128 == 0 and SLOTS * blocks <= 512
    assert vmem <= fa.PAGED_VMEM_CAP


@pytest.mark.parametrize("hq,hkv,dh,nb,quantized,grid", [
    (12, 2, 128, 2048, True, "page_blocks"),    # qwen2-1.5b, 32k tokens
    (14, 2, 64, 512, False, "page_blocks"),     # internvl2-1b, head_dim 64
    (32, 8, 128, 8192, True, "per_split"),      # mistral-nemo-12b, 128k
], ids=["qwen2-1.5b", "internvl2-1b", "mistral-nemo-12b"])
def test_decode_paged_long_context(one_chip, hq, hkv, dh, nb, quantized,
                                   grid):
    """Other configurations' widths at long block tables: the call keeps
    every page's stats in VMEM while they fit under ``PAGED_VMEM_CAP``
    (one kernel, the merged (B, Hq, D) result) and past it runs the
    per-split grid (the kernel returns per-page stats for the XLA
    combine); either way the kernel compiles."""
    kv_dt = I8 if quantized else BF16
    _, _, vmem = fa.paged_decode_plan(hq, hkv, dh, PS, nb, kv_dt, quantized)
    assert (vmem <= fa.PAGED_VMEM_CAP) == (grid == "page_blocks")
    n_pages = B * nb + 1
    shapes = [((B, hq, 1, dh), BF16), ((n_pages, hkv, PS, dh), kv_dt),
              ((n_pages, hkv, PS, dh), kv_dt), ((B, nb), I32),
              ((B, nb * PS), F32)]
    if quantized:
        shapes += [((n_pages, hkv, PS), F32)] * 2

    def fn(q, kp, vp, bt, m, *s):
        return fa.flash_hyft_decode_paged(q, kp, vp, bt, CFG, interpret=False,
                                          kv_len_mask=m,
                                          k_scale=s[0] if s else None,
                                          v_scale=s[1] if s else None)
    text = _compiled_text(one_chip, fn, *shapes)
    assert "tpu_custom_call" in text
    merged = re.search(rf"= f32\[{B},{hq},{dh}\]\{{\S* custom-call\("
                       r"[^\n]*tpu_custom_call", text)
    assert bool(merged) == (grid == "page_blocks")


@pytest.mark.parametrize("sq,quantized", [(5, False), (512, False),
                                          (512, True)])
def test_verify_dense(one_chip, sq, quantized):
    kv_dt = I8 if quantized else BF16
    shapes = [((B, H, sq, D), BF16), ((B, H, CTX, D), kv_dt),
              ((B, H, CTX, D), kv_dt), ((B, sq, CTX), F32)]
    if quantized:
        shapes += [((B, H, CTX), F32)] * 2

    def fn(q, k, v, m, *s):
        return fa.flash_hyft_verify(q, k, v, m, CFG, interpret=False,
                                    k_scale=s[0] if s else None,
                                    v_scale=s[1] if s else None)
    _assert_kernel(one_chip, fn, *shapes)


def _stats_buffers(text, bh, nb):
    """Per-split stats buffers (B * Hkv, splits, rows, 128) in compiled HLO
    text: what the per-split grid hands its XLA combine."""
    return re.findall(rf"[fs]32\[{bh},{nb},\d+,128\]", text)


def _verify_paged(one_chip, slots, nb, quantized):
    """The plan and compiled text of a paged 512-lane verify call."""
    sq, n_pages = 512, slots * nb + 1
    kv_dt = I8 if quantized else BF16
    shapes = [((slots, H, sq, D), BF16), ((n_pages, H, PS, D), kv_dt),
              ((n_pages, H, PS, D), kv_dt), ((slots, sq, nb * PS), F32),
              ((slots, nb), I32)]
    if quantized:
        shapes += [((n_pages, H, PS), F32)] * 2

    def fn(q, kp, vp, m, bt, *s):
        return fa.flash_hyft_verify(q, kp, vp, m, CFG, interpret=False,
                                    block_tables=bt,
                                    k_scale=s[0] if s else None,
                                    v_scale=s[1] if s else None)
    plan = fa.paged_verify_plan(H, H, D, PS, nb, sq, kv_dt, quantized)
    return plan, _compiled_text(one_chip, fn, *shapes)


@pytest.mark.parametrize("quantized", [False, True])
def test_verify_paged(one_chip, quantized):
    """The prefill-chunk path of paged serving at the serving cell's shapes
    (8 slots, 512-lane chunks, 2048-token tables of 16-token pages): the
    page-block grid, one kernel whose one result is the merged (B * Hkv,
    lane blocks, rows, D) output, and no per-split stats anywhere in the
    program."""
    plan, text = _verify_paged(one_chip, SLOTS, NB, quantized)
    assert plan.vmem <= fa.PAGED_VMEM_CAP
    assert _kernel_names(text) == {"flash_hyft_verify"}
    results = re.findall(r"= (\S+) custom-call\([^\n]*tpu_custom_call", text)
    assert (plan.lanes, plan.nq) == (128, 4) and len(results) == 1
    assert results[0].startswith(f"f32[{SLOTS * H},4,128,{D}]{{")
    assert not _stats_buffers(text, SLOTS * H, NB)


@pytest.mark.parametrize("quantized", [False, True])
def test_verify_paged_past_the_vmem_cap(one_chip, quantized):
    """16k-token tables keep more per-page stats than ``PAGED_VMEM_CAP``:
    the call runs the per-split grid, whose kernel returns them."""
    plan, text = _verify_paged(one_chip, 1, 1024, quantized)
    assert plan.vmem > fa.PAGED_VMEM_CAP
    assert _kernel_names(text) == {"flash_hyft_verify"}
    assert _stats_buffers(text, H, 1024)


@pytest.mark.parametrize("cfg", [HYFT16, HYFT32], ids=["hyft16", "hyft32"])
def test_softmax_kernels(one_chip, cfg):
    z = ((B * H * 8, 512), cfg.dtype)

    def fn(z, dy):
        s = hs.hyft_softmax_fwd_kernel(z, cfg, interpret=False)
        return s, hs.hyft_softmax_bwd_kernel(s, dy, cfg, interpret=False)
    assert _compiled_text(one_chip, fn, z, z).count("tpu_custom_call") >= 2


def _kernel_names(text):
    """Instruction names of the Pallas kernels in compiled HLO text."""
    return {m.group(1) for m in re.finditer(
        r"%([A-Za-z_][\w\-]*?)(?:\.\d+)? = [^\n]*"
        r'custom_call_target="tpu_custom_call"', text)}


def test_chunk_kernel_is_named_inside_its_lane_map(one_chip):
    """A 512-lane paged chunk runs its four 128-lane blocks as an axis of
    the verify kernel's grid (they ran under a ``lax.map`` before the
    page-block grid); the program holds that one kernel under its own
    name, no map body's ``closed_call``, and no per-split stats buffer,
    so a profile can tell it from other calls."""
    sq, n_pages = 512, B * NB + 1
    shapes = [((B, H, sq, D), BF16), ((n_pages, H, PS, D), I8),
              ((n_pages, H, PS, D), I8), ((B, sq, CTX), F32),
              ((B, NB), I32), ((n_pages, H, PS), F32),
              ((n_pages, H, PS), F32)]

    def fn(q, kp, vp, m, bt, ks, vs):
        return fa.flash_hyft_verify(q, kp, vp, m, CFG, interpret=False,
                                    block_tables=bt, k_scale=ks, v_scale=vs)
    text = _compiled_text(one_chip, fn, *shapes)
    assert _kernel_names(text) == {"flash_hyft_verify"}
    assert not re.search(r"%closed_call\b", text)
    assert not _stats_buffers(text, B * H, NB)


def test_training_kernels_are_named(one_chip):
    S = 512
    qkv = [((B, H, S, D), BF16)] * 3

    def fn(q, k, v):
        return jax.grad(lambda *a: jnp.sum(fa.flash_hyft_attention(
            *a, CFG, interpret=False)), (0, 1, 2))(q, k, v)
    assert _kernel_names(_compiled_text(one_chip, fn, *qkv)) == {
        "flash_hyft_attention", "flash_hyft_attention_bwd_dq",
        "flash_hyft_attention_bwd_dkv"}
