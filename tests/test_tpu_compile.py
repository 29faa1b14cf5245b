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
    _assert_kernel(one_chip, fn, *shapes)


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


@pytest.mark.parametrize("quantized", [False, True])
def test_verify_paged(one_chip, quantized):
    """The prefill-chunk path of paged serving, at the default page size."""
    sq, n_pages = 512, B * NB + 1
    kv_dt = I8 if quantized else BF16
    shapes = [((B, H, sq, D), BF16), ((n_pages, H, PS, D), kv_dt),
              ((n_pages, H, PS, D), kv_dt), ((B, sq, CTX), F32),
              ((B, NB), I32)]
    if quantized:
        shapes += [((n_pages, H, PS), F32)] * 2

    def fn(q, kp, vp, m, bt, *s):
        return fa.flash_hyft_verify(q, kp, vp, m, CFG, interpret=False,
                                    block_tables=bt,
                                    k_scale=s[0] if s else None,
                                    v_scale=s[1] if s else None)
    _assert_kernel(one_chip, fn, *shapes)


@pytest.mark.parametrize("cfg", [HYFT16, HYFT32], ids=["hyft16", "hyft32"])
def test_softmax_kernels(one_chip, cfg):
    z = ((B * H * 8, 512), cfg.dtype)

    def fn(z, dy):
        s = hs.hyft_softmax_fwd_kernel(z, cfg, interpret=False)
        return s, hs.hyft_softmax_bwd_kernel(s, dy, cfg, interpret=False)
    assert _compiled_text(one_chip, fn, z, z).count("tpu_custom_call") >= 2
