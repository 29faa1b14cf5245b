"""The plain reference's Hyft16 attention against the program's Pallas
kernels (in the interpreter), at small sizes: the paged verify and decode
kernels (pages as splits, fp2fx8 K/V) and the fused training kernel's
forward and backward.  The reference is written from the paper, so these
agree only if both follow the same arithmetic; an exact softmax differs
from either by the size of Hyft's own approximation."""
import jax
import jax.numpy as jnp
import pytest

from bench import reference as R

HY = R.HYFT["hyft16"]
H, D = 2, 16


def _close(a, b) -> bool:
    """Equal but for float32 sums taken in another order."""
    return float(jnp.max(jnp.abs(a - b))) <= 1e-5 * float(jnp.max(jnp.abs(b)))


def _qkv(n, S, scale=1.5):
    keys = jax.random.split(jax.random.PRNGKey(7), n)
    return [jax.random.normal(k, (S, H, D), jnp.float32) * scale
            for k in keys]


def _heads_first(x):
    return jnp.transpose(x, (1, 0, 2))


def _paged(x, ps):
    """(S, H, D) -> fp2fx8 pages (nb, H, ps, D), scales (nb, H, ps), and
    the dequantized (S, H, D) the reference reads."""
    from repro.models.attention import fp2fx8_dequantize, fp2fx8_quantize
    raw, scale = fp2fx8_quantize(_heads_first(x))           # (H, S, D)
    S = x.shape[0]
    nb = S // ps
    pages = raw.reshape(H, nb, ps, D).transpose(1, 0, 2, 3)
    scales = scale.reshape(H, nb, ps).transpose(1, 0, 2)
    return pages, scales, _heads_first(fp2fx8_dequantize(raw, scale))


def test_paged_kernels_match_the_reference_over_page_splits():
    from repro.core.hyft import HYFT16
    from repro.kernels import flash_attention as fa
    S, ps = 64, 16
    q, k, v = _qkv(3, S)
    kp, ks, kd = _paged(k, ps)
    vp, vs, vd = _paged(v, ps)
    bt = jnp.arange(S // ps, dtype=jnp.int32)[None]
    causal = (jnp.arange(S)[None, :] <= jnp.arange(S)[:, None])
    chunk = fa.flash_hyft_verify(
        _heads_first(q)[None], kp, vp, causal.astype(jnp.float32)[None],
        HYFT16, interpret=True, block_tables=bt, k_scale=ks, v_scale=vs)[0]
    last = fa.flash_hyft_decode_paged(
        _heads_first(q[-1:])[None], kp, vp, bt, HYFT16, interpret=True,
        k_scale=ks, v_scale=vs)[0]
    ref = R._hyft_splits(q, kd, vd, dict(HY), ps, rows=16)
    assert _close(_heads_first(chunk), ref)
    assert _close(last[:, 0], ref[-1])
    exact = R._exact(q, kd, vd)
    assert float(jnp.max(jnp.abs(exact - ref))) > 0.05


def test_fused_training_kernel_matches_the_online_reference():
    from repro.core.hyft import HYFT16
    from repro.kernels import flash_attention as fa
    S = 256
    q, k, v, do = _qkv(4, S, scale=1.0)

    def prog(q, k, v):
        t = lambda x: _heads_first(x)[None]  # noqa: E731
        return _heads_first(fa.flash_hyft_attention(
            t(q), t(k), t(v), HYFT16, interpret=True)[0])

    o1, vjp1 = jax.vjp(prog, q, k, v)
    o2, vjp2 = jax.vjp(lambda *a: R._hyft_online(*a, HY, 128), q, k, v)
    assert _close(o1, o2)
    assert all(_close(a, b) for a, b in zip(vjp1(do), vjp2(do)))
    o3, vjp3 = jax.vjp(R._exact, q, k, v)
    assert float(jnp.max(jnp.abs(o3 - o2))) > 0.05


def test_attention_follows_the_configuration():
    assert R.attention({"softmax_impl": "exact"}) is R._exact
    with pytest.raises(ValueError):
        R.attention({"softmax_impl": "koca"})
