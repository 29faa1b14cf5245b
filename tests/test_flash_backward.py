"""Trainable, mask-aware fused flash kernel: backward parity + decode path.

The fused kernel's VJP (two Pallas kernels recomputing Hyft probabilities
from the saved (m, l) row stats) must match the chunked custom-VJP path —
same arithmetic, so near-bitwise when the KV block sizes agree — and stay
within the Hyft quantization envelope of ``jax.grad`` through the unfused
``hyft_softmax`` path.  Masked decode (the serving scenario) must run on the
fused kernel end to end, with zero gradient leaking into masked positions.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hyft import HYFT16, HYFT32
from repro.kernels import ops
from repro.kernels.flash_attention import flash_hyft_attention
from repro.models.attention import chunked_hyft_attention, unfused_attention

F32 = jnp.float32
KEY = jax.random.PRNGKey(7)


def _qkvw(B=1, Hq=4, Hkv=2, Sq=128, Sk=128, D=32):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, Hq, Sq, D), F32)
    k = jax.random.normal(ks[1], (B, Hkv, Sk, D), F32)
    v = jax.random.normal(ks[2], (B, Hkv, Sk, D), F32)
    w = jax.random.normal(ks[3], (B, Hq, Sq, D), F32)
    return q, k, v, w


@pytest.mark.parametrize("cfg", [HYFT16, HYFT32], ids=["h16", "h32"])
@pytest.mark.parametrize("causal", [True, False])
def test_kernel_grad_matches_chunked(cfg, causal):
    """Same KV blocking => same (m, l) stats => near-identical gradients
    (only fp32 matmul association differs)."""
    q, k, v, w = _qkvw()

    def f_kernel(q, k, v):
        o = flash_hyft_attention(q, k, v, cfg, causal=causal, block_q=64,
                                 block_k=64, interpret=True)
        return jnp.sum(o * w)

    def f_chunked(q, k, v):
        return jnp.sum(chunked_hyft_attention(q, k, v, cfg, causal, 64, 0) * w)

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gc = jax.grad(f_chunked, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gc):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=1e-4)


@pytest.mark.parametrize("cfg", [HYFT16, HYFT32], ids=["h16", "h32"])
def test_kernel_grad_close_to_unfused_softmax_grad(cfg):
    """jax.grad through attn_mode="kernel" vs jax.grad of the unfused
    hyft_softmax path — bounded by the Hyft quantization envelope already
    used for the chunked path."""
    q, k, v, _ = _qkvw(Hq=2, Hkv=2, Sq=64, Sk=64, D=16)

    def f_kernel(q, k, v):
        return jnp.sum(flash_hyft_attention(q, k, v, cfg, causal=True,
                                            block_q=32, block_k=32,
                                            interpret=True))

    def f_unfused(q, k, v):
        return jnp.sum(unfused_attention(q, k, v, "hyft32" if cfg is HYFT32
                                         else "hyft16", causal=True))

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gu = jax.grad(f_unfused, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gu):
        assert float(jnp.abs(a - b).max()) < 0.35


@pytest.mark.parametrize("cfg", [HYFT16, HYFT32], ids=["h16", "h32"])
def test_masked_decode_grad_matches_chunked(cfg):
    """Masked non-causal (decode/serving) gradients: fused kernel == chunked
    path under the shared mask contract; no gradient at masked positions."""
    q, k, v, w = _qkvw(B=2, Hq=4, Hkv=2, Sq=8, Sk=64, D=16)
    valid = 40
    maskf = (jnp.arange(64)[None, :] < valid).astype(F32).repeat(2, 0)

    def f_kernel(q, k, v):
        o = flash_hyft_attention(q, k, v, cfg, causal=False, block_q=8,
                                 block_k=32, interpret=True, kv_len_mask=maskf)
        return jnp.sum(o * w)

    def f_chunked(q, k, v):
        return jnp.sum(
            chunked_hyft_attention(q, k, v, cfg, False, 32, 0, maskf) * w)

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gc = jax.grad(f_chunked, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gc):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=1e-4)
    # masked KV positions receive (at most) negligible dk/dv: Hyft16's
    # narrow fixed range leaves a ~2**-105 residual probability; Hyft32
    # flushes to exactly zero
    assert float(jnp.abs(gk[1][:, :, valid:]).max()) < 1e-12
    assert float(jnp.abs(gk[2][:, :, valid:]).max()) < 1e-12


def test_masked_fwd_matches_unfused():
    """Fused forward with kv_len_mask stays within the log-div Taylor bound
    of the unfused masked path (same bound as the sp-decode test)."""
    q, k, v, _ = _qkvw(B=2, Hq=4, Hkv=2, Sq=1, Sk=64, D=16)
    valid = jnp.arange(64)[None, :].repeat(2, 0) < 40
    o = ops.hyft_attention(q, k, v, HYFT32, causal=False, kv_len_mask=valid)
    o_ref = unfused_attention(q, k, v, "hyft32", causal=False,
                              kv_len_mask=valid)
    assert float(jnp.abs(o - o_ref).max()) < 0.06


def test_nonmultiple_lengths_auto_padded():
    """Sequence lengths that don't divide the block sizes are padded inside
    the wrapper and produce the same result as smaller exact blocks."""
    q, k, v, _ = _qkvw(Sq=96, Sk=200, D=16)
    a = flash_hyft_attention(q, k, v, HYFT32, causal=False, block_q=64,
                             block_k=128, interpret=True)
    b = flash_hyft_attention(q, k, v, HYFT32, causal=False, block_q=32,
                             block_k=8, interpret=True)
    # same elementwise Hyft math; only the online merge order differs
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-2)
    assert a.shape == (1, 4, 96, 16)


def test_q_offset_matches_full_causal():
    """A partial-prefill continuation (q_offset > 0) equals the suffix rows
    of the full causal computation."""
    q, k, v, _ = _qkvw(Sq=64, Sk=64, D=16)
    full = flash_hyft_attention(q, k, v, HYFT32, causal=True, block_q=32,
                                block_k=32, interpret=True)
    tail = flash_hyft_attention(q[:, :, 32:], k, v, HYFT32, causal=True,
                                block_q=32, block_k=32, interpret=True,
                                q_offset=32)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(full[:, :, 32:]),
                               atol=1e-6)


class TestEngineOnFusedKernel:
    """serve/engine decode with attn_mode="kernel" never touches the unfused
    fallback — the acceptance criterion for the serving path."""

    def _model(self, attn_mode):
        from repro.configs.base import ModelConfig
        from repro.models import build_model
        cfg = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=32,
                          n_heads=4, n_kv_heads=2, d_head=8, d_ff=64,
                          vocab=64, softmax_impl="hyft32",
                          attn_mode=attn_mode, compute_dtype="float32")
        return build_model(cfg)

    def test_decode_no_unfused_fallback(self, monkeypatch):
        from repro.configs.base import ServeConfig
        from repro.models import attention as attn_mod
        from repro.models.layers import unbox
        from repro.serve.engine import generate

        model = self._model("kernel")
        params = unbox(model.init(jax.random.PRNGKey(0)))

        def boom(*a, **kw):
            raise AssertionError("masked decode fell back to unfused")
        monkeypatch.setattr(attn_mod, "unfused_attention", boom)

        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (2, 8), 0, 64, jnp.int32)}
        scfg = ServeConfig(batch=2, prefill_len=8, max_len=16,
                           cache_dtype="float32")
        out = generate(model, params, batch, scfg, max_new=4)
        assert out.shape == (2, 4)

    def test_serve_config_attn_mode_override(self, monkeypatch):
        """ServeConfig.attn_mode="kernel" upgrades an unfused model at the
        engine boundary (the launch/serve plumbing)."""
        from repro.configs.base import ServeConfig
        from repro.models import attention as attn_mod
        from repro.models.layers import unbox
        from repro.serve.engine import generate

        model = self._model("unfused")
        params = unbox(model.init(jax.random.PRNGKey(0)))

        def boom(*a, **kw):
            raise AssertionError("override did not reach the fused kernel")
        monkeypatch.setattr(attn_mod, "unfused_attention", boom)

        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (2, 4), 0, 64, jnp.int32)}
        scfg = ServeConfig(batch=2, prefill_len=4, max_len=10,
                           cache_dtype="float32", attn_mode="kernel")
        out = generate(model, params, batch, scfg, max_new=3)
        assert out.shape == (2, 3)


def test_train_step_attn_mode_override():
    """TrainConfig.attn_mode="kernel" trains through the fused fwd+bwd
    kernels (the train/step plumbing)."""
    import repro.optim as optim
    from repro.configs.base import ModelConfig, TrainConfig
    from repro.models import build_model
    from repro.models.layers import unbox
    from repro.train.step import make_step_fn

    cfg = ModelConfig(name="tiny", family="dense", n_layers=1, d_model=16,
                      n_heads=2, n_kv_heads=2, d_head=8, d_ff=32, vocab=32,
                      softmax_impl="hyft32", attn_mode="unfused",
                      compute_dtype="float32")
    model = build_model(cfg)
    params = unbox(model.init(jax.random.PRNGKey(0)))
    tcfg = TrainConfig(global_batch=2, seq_len=8, total_steps=2, remat="none",
                       attn_mode="kernel")
    ocfg = optim.OptConfig(name="adamw", lr=1e-3)
    step = make_step_fn(model, tcfg, ocfg)
    state = {"params": params, "opt": optim.init(ocfg, params),
             "step": jnp.zeros((), jnp.int32), "rng": jax.random.PRNGKey(0)}
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, 32, jnp.int32)
    state, metrics = step(state, {"tokens": toks, "targets": toks})
    assert jnp.isfinite(metrics["loss"])


def test_kernel_mode_refuses_traced_q_offset():
    """Kernel mode never quietly swaps in the unfused path: a traced
    ``q_offset`` (which the fused kernels cannot take) is an error."""
    from repro.configs import get_config, smoke_config
    from repro.models.attention import attention_fwd
    cfg = smoke_config(get_config("olmo-1b")).with_(softmax_impl="hyft16",
                                                    attn_mode="kernel")
    q, k, v, _ = _qkvw(Sq=8, Sk=8)
    with pytest.raises(ValueError, match="static int q_offset"):
        jax.jit(lambda off: attention_fwd(q, k, v, cfg, q_offset=off))(3)
    # a static offset takes the kernel
    assert attention_fwd(q, k, v, cfg, q_offset=3).shape == q.shape
