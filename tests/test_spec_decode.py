"""Speculative decoding: verify kernel, drafters, greedy parity, rollback.

Contracts under test:
  * ``flash_hyft_verify`` at Sq == 1 is bitwise identical to the split-K
    decode kernels — dense AND paged, float AND fp2fx8 — and at Sq > 1
    each lane is bitwise the decode kernel's output under that lane's own
    causal frontier (the causal-within-draft mask);
  * greedy spec serving (``scheduler="spec"``) is token-for-token identical
    to vanilla greedy continuous serving across dense, fp2fx8, paged, and
    paged+prefix-cache layouts (and therefore to solo ``generate``, by the
    PR 3/4 parity suites);
  * EOS and budget act on ACCEPTED tokens only;
  * mid-spec-burst preemption under page pressure leaves PagePool
    refcounts and radix-trie-shared pages exactly consistent;
  * the n-gram drafter's proposal is always a literal continuation of its
    context (hypothesis property);
  * the top-k/top-p sampling filters (satellite) restrict draws to the
    right candidate sets.
"""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ServeConfig

F32 = jnp.float32
I32 = jnp.int32


def _setup(arch="qwen2-1.5b", vocab=64, **kw):
    from repro.configs import get_config, smoke_config
    from repro.models import build_model
    from repro.models.layers import unbox
    cfg = smoke_config(get_config(arch)).with_(
        softmax_impl="hyft16", vocab=vocab, **kw)
    model = build_model(cfg)
    params = unbox(model.init(jax.random.PRNGKey(0)))
    return cfg, model, params


def _requests(cfg, n, rng, plen=(3, 10), max_new=(4, 10), repetitive=False):
    from repro.serve.scheduler import Request
    reqs = []
    for rid in range(n):
        if repetitive:  # motif-tiled prompt: the n-gram drafter's regime
            motif = rng.integers(0, cfg.vocab, 4).astype(np.int32)
            toks = np.concatenate(
                [np.tile(motif, 3),
                 rng.integers(0, cfg.vocab, 2).astype(np.int32)])
        else:
            toks = rng.integers(0, cfg.vocab,
                                int(rng.integers(*plen))).astype(np.int32)
        reqs.append(Request(rid=rid, tokens=toks,
                            max_new=int(rng.integers(*max_new))))
    return reqs


def _run(model, params, reqs, draft=None, **kw):
    from repro.serve.scheduler import SlotPoolEngine
    scfg = ServeConfig(max_len=kw.pop("max_len", 48),
                       cache_dtype=kw.pop("cache_dtype", "float32"),
                       n_slots=kw.pop("n_slots", 2),
                       decode_burst=4, **kw)
    eng = SlotPoolEngine(model, params, scfg, draft=draft)
    done = eng.run(list(reqs))
    return {rid: c.tokens for rid, c in done.items()}, eng


# --------------------------------------------------------------------------
# the verify kernel
# --------------------------------------------------------------------------


def _kernel_operands(rng, B=3, Hq=4, Hkv=2, Sk=40, D=16):
    q1 = jnp.asarray(rng.normal(size=(B, Hq, 1, D)), F32)
    k = jnp.asarray(rng.normal(size=(B, Hkv, Sk, D)), F32)
    v = jnp.asarray(rng.normal(size=(B, Hkv, Sk, D)), F32)
    lens = jnp.asarray([10, 25, 40])
    mask = jnp.arange(Sk)[None, :] < lens[:, None]
    return q1, k, v, mask


def test_verify_kernel_bitwise_decode_dense():
    """Sq == 1: the verify kernel IS the split-K decode kernel, bitwise."""
    from repro.core.registry import hyft_config_for
    from repro.kernels.ops import hyft_decode_attention, hyft_verify_attention
    cfg = hyft_config_for("hyft16")
    q1, k, v, mask = _kernel_operands(np.random.default_rng(0))
    dec = hyft_decode_attention(q1, k, v, cfg, kv_len_mask=mask)
    ver = hyft_verify_attention(q1, k, v, mask[:, None, :], cfg)
    assert jnp.all(dec == ver)


def test_verify_kernel_bitwise_decode_fp2fx8():
    from repro.core.registry import hyft_config_for
    from repro.kernels.ops import hyft_decode_attention, hyft_verify_attention
    from repro.models.attention import fp2fx8_quantize
    cfg = hyft_config_for("hyft16")
    q1, k, v, mask = _kernel_operands(np.random.default_rng(1))
    kr, ks = fp2fx8_quantize(k)
    vr, vs = fp2fx8_quantize(v)
    dec = hyft_decode_attention(q1, kr, vr, cfg, kv_len_mask=mask,
                                k_scale=ks, v_scale=vs)
    ver = hyft_verify_attention(q1, kr, vr, mask[:, None, :], cfg,
                                k_scale=ks, v_scale=vs)
    assert jnp.all(dec == ver)


def _paged_pool(k, v, ps):
    """Scatter contiguous (B, Hkv, Sk, D) K/V into a page pool with
    sequential per-sequence block tables."""
    B, Hkv, Sk, D = k.shape
    nb = Sk // ps
    kp = jnp.zeros((B * nb + 1, Hkv, ps, D), F32)
    vp = jnp.zeros((B * nb + 1, Hkv, ps, D), F32)
    bt = np.zeros((B, nb), np.int32)
    pid = 1
    for b in range(B):
        for j in range(nb):
            kp = kp.at[pid].set(k[b, :, j * ps:(j + 1) * ps])
            vp = vp.at[pid].set(v[b, :, j * ps:(j + 1) * ps])
            bt[b, j] = pid
            pid += 1
    return kp, vp, jnp.asarray(bt)


def test_verify_kernel_bitwise_decode_paged():
    from repro.core.registry import hyft_config_for
    from repro.kernels.ops import (hyft_paged_decode_attention,
                                   hyft_verify_attention)
    cfg = hyft_config_for("hyft16")
    q1, k, v, mask = _kernel_operands(np.random.default_rng(2))
    kp, vp, bt = _paged_pool(k, v, ps=8)
    dec = hyft_paged_decode_attention(q1, kp, vp, bt, cfg, kv_len_mask=mask)
    ver = hyft_verify_attention(q1, kp, vp, mask[:, None, :], cfg,
                                block_tables=bt)
    assert jnp.all(dec == ver)


@pytest.mark.parametrize("paged", [False, True])
def test_verify_lanes_match_decode_per_frontier(paged):
    """Every verify lane t equals the decode kernel run under lane t's own
    causal frontier (kv <= pos + t) — causal-within-draft, bitwise."""
    from repro.core.registry import hyft_config_for
    from repro.kernels.ops import (hyft_decode_attention,
                                   hyft_paged_decode_attention,
                                   hyft_verify_attention)
    cfg = hyft_config_for("hyft16")
    rng = np.random.default_rng(3)
    B, Hq, Hkv, Sk, D, S = 3, 4, 2, 40, 16, 3
    qs = jnp.asarray(rng.normal(size=(B, Hq, S, D)), F32)
    k = jnp.asarray(rng.normal(size=(B, Hkv, Sk, D)), F32)
    v = jnp.asarray(rng.normal(size=(B, Hkv, Sk, D)), F32)
    base = jnp.asarray([9, 20, 30])
    pos = base[:, None] + jnp.arange(S)[None, :]
    m3 = jnp.arange(Sk)[None, None, :] <= pos[:, :, None]
    if paged:
        kp, vp, bt = _paged_pool(k, v, ps=8)
        ver = hyft_verify_attention(qs, kp, vp, m3, cfg, block_tables=bt)
    else:
        ver = hyft_verify_attention(qs, k, v, m3, cfg)
    for t in range(S):
        mt = jnp.arange(Sk)[None, :] <= pos[:, t][:, None]
        if paged:
            dt = hyft_paged_decode_attention(qs[:, :, t:t + 1], kp, vp, bt,
                                             cfg, kv_len_mask=mt)
        else:
            dt = hyft_decode_attention(qs[:, :, t:t + 1], k, v, cfg,
                                       kv_len_mask=mt)
        assert jnp.all(dt == ver[:, :, t:t + 1])


@pytest.mark.parametrize("paged", [False, True])
def test_verify_lane_blocks_match_one_call(paged, monkeypatch):
    """A chunk longer than ``VERIFY_LANE_BLOCK`` runs as lane blocks (the
    last one padded); each lane's arithmetic is unchanged, so the result is
    bitwise that of one call over all lanes."""
    from repro.core.registry import hyft_config_for
    from repro.kernels import flash_attention as fa
    from repro.kernels.flash_attention import flash_hyft_verify
    cfg = hyft_config_for("hyft16")
    rng = np.random.default_rng(4)
    B, Hq, Hkv, Sk, D, S = 2, 4, 2, 40, 16, 20
    qs = jnp.asarray(rng.normal(size=(B, Hq, S, D)), F32)
    k = jnp.asarray(rng.normal(size=(B, Hkv, Sk, D)), F32)
    v = jnp.asarray(rng.normal(size=(B, Hkv, Sk, D)), F32)
    pos = jnp.asarray([5, 12])[:, None] + jnp.arange(S)[None, :]
    m3 = (jnp.arange(Sk)[None, None, :] <= pos[:, :, None]).astype(F32)
    kw = dict(interpret=True)
    if paged:
        k, v, bt = _paged_pool(k, v, ps=8)
        kw["block_tables"] = bt
    assert S <= fa.VERIFY_LANE_BLOCK
    whole = flash_hyft_verify(qs, k, v, m3, cfg, **kw)
    monkeypatch.setattr(fa, "VERIFY_LANE_BLOCK", 8)
    jax.clear_caches()  # the jitted entry reads the block size at trace time
    blocked = flash_hyft_verify(qs, k, v, m3, cfg, **kw)
    assert jnp.all(whole == blocked)


# --------------------------------------------------------------------------
# greedy spec == vanilla greedy, across layouts
# --------------------------------------------------------------------------


def test_spec_parity_dense():
    cfg, model, params = _setup()
    reqs = _requests(cfg, 5, np.random.default_rng(0), repetitive=True)
    base, _ = _run(model, params, reqs, scheduler="continuous")
    out, eng = _run(model, params, reqs, scheduler="spec", draft_k=4)
    assert out == base
    st = eng.stats
    assert st["spec_steps"] > 0 and st["draft_tokens"] > 0
    # the repetitive prompts + a looping random model must accept SOMETHING
    assert st["accepted_tokens"] > 0


@pytest.mark.slow
@pytest.mark.parametrize("layout", ["fp2fx8", "kernel", "paged",
                                    "paged_prefix"])
def test_spec_parity_layouts(layout):
    """Token-for-token greedy parity across cache formats and layouts,
    including the fused-kernel attention path."""
    cfg, model, params = _setup()
    kw = {
        "fp2fx8": dict(cache_dtype="fp2fx8"),
        "kernel": dict(attn_mode="kernel"),
        "paged": dict(kv_layout="paged", page_size=8, attn_mode="kernel"),
        "paged_prefix": dict(kv_layout="paged", page_size=8,
                             prefix_cache=True),
    }[layout]
    reqs = _requests(cfg, 5, np.random.default_rng(1), repetitive=True)
    base, _ = _run(model, params, reqs, scheduler="continuous", **kw)
    out, _ = _run(model, params, reqs, scheduler="spec", draft_k=4, **kw)
    assert out == base


def test_spec_eos_and_budget_on_accepted_only():
    """EOS truncates emission inside the accepted prefix and frees the slot;
    budgets never overshoot — exactly the vanilla continuous behavior."""
    cfg, model, params = _setup()
    rng = np.random.default_rng(2)
    reqs = _requests(cfg, 5, rng, repetitive=True)
    base, _ = _run(model, params, reqs, scheduler="continuous")
    eos = int(collections.Counter(
        t for toks in base.values() for t in toks).most_common(1)[0][0])
    base_eos, _ = _run(model, params, reqs, scheduler="continuous",
                       eos_id=eos)
    out, _ = _run(model, params, reqs, scheduler="spec", draft_k=4,
                  eos_id=eos)
    assert out == base_eos
    for rid, toks in out.items():
        assert len(toks) <= reqs[rid].max_new
        assert eos not in toks[:-1]  # EOS only ever terminal


@pytest.mark.slow
def test_spec_model_drafter_shares_pool_full_acceptance():
    """A draft model identical to the target must have every draft accepted
    (the drafter's teacher-sync + greedy loop is bitwise the target's own
    continuation), and outputs stay parity — the strongest end-to-end check
    of the sync/draft/verify/rollback chain."""
    cfg, model, params = _setup()
    reqs = _requests(cfg, 4, np.random.default_rng(3))
    base, _ = _run(model, params, reqs, scheduler="continuous")
    out, eng = _run(model, params, reqs, scheduler="spec", draft_k=3,
                    spec_mode="model", draft=(model, params))
    assert out == base
    st = eng.stats
    assert st["draft_tokens"] > 0
    assert st["accepted_tokens"] == st["draft_tokens"]


# --------------------------------------------------------------------------
# rollback: refcounts and trie-shared pages under preemption mid-spec
# --------------------------------------------------------------------------


@pytest.mark.slow
def test_spec_preemption_rollback_refcounts_intact():
    """A page pool too small for the load forces preemption mid-spec-burst;
    afterwards every refcount must equal the trie's exact reference count
    (slots drained), outputs must equal the dense baseline, and no slot may
    retain pages — page-tail rollback never corrupts shared pages."""
    cfg, model, params = _setup()
    rng = np.random.default_rng(4)
    head = rng.integers(0, cfg.vocab, 12).astype(np.int32)
    from repro.serve.scheduler import Request
    reqs = [Request(rid=i, tokens=np.concatenate(
                [head, rng.integers(0, cfg.vocab, 3).astype(np.int32)]),
                max_new=10) for i in range(6)]
    base, _ = _run(model, params, reqs, scheduler="continuous", n_slots=3,
                   max_len=40)
    out, eng = _run(model, params, reqs, scheduler="spec", draft_k=4,
                    n_slots=3, max_len=40, kv_layout="paged", page_size=4,
                    n_pages=12, prefix_cache=True)
    assert out == base
    assert eng.stats["preemptions"] > 0, "pool was meant to be under pressure"
    assert not eng.active.any()
    assert all(not p for p in eng.slot_pages)
    # exact refcount accounting: pool refs == trie references, nothing else
    refs = eng.pool.refs
    trie_refs = collections.Counter()
    stack = [eng.trie.root]
    while stack:
        nd = stack.pop()
        stack.extend(nd.children.values())
        for p in nd.pages:
            trie_refs[p] += 1
    for p in range(1, eng.pool.n_pages + 1):
        assert refs[p] == trie_refs.get(p, 0)
    assert eng.pool.pages_in_use == eng.trie.n_pages()


def test_spec_validation():
    cfg, model, params = _setup()
    from repro.serve.scheduler import SlotPoolEngine
    with pytest.raises(ValueError, match="greedy-only"):
        SlotPoolEngine(model, params,
                       ServeConfig(scheduler="spec", temperature=0.7))
    with pytest.raises(ValueError, match="draft_k"):
        SlotPoolEngine(model, params,
                       ServeConfig(scheduler="spec", draft_k=0))
    _, ssm_model, ssm_params = _setup(arch="mamba2-370m")
    with pytest.raises(ValueError, match="attention-family"):
        SlotPoolEngine(ssm_model, ssm_params, ServeConfig(scheduler="spec"))
    with pytest.raises(ValueError, match="unknown scheduler"):
        SlotPoolEngine(model, params, ServeConfig(scheduler="warp"))


# --------------------------------------------------------------------------
# n-gram drafter
# --------------------------------------------------------------------------


def test_ngram_drafter_lookup():
    from repro.serve.spec import NgramDrafter
    d = NgramDrafter(ngram_max=3)
    # ...[5 6 7] 9 ... [5 6 7] -> continuation after the 3-gram is 9
    ctx = np.array([1, 5, 6, 7, 9, 2, 5, 6, 7], np.int32)
    assert d.draft(ctx, 2).tolist() == [9, 2]
    # recency: the MOST RECENT earlier occurrence with a full window wins
    ctx = np.array([5, 6, 1, 5, 6, 2, 5, 6], np.int32)
    assert d.draft(ctx, 1).tolist() == [2]
    # no recurrence anywhere -> empty draft
    assert d.draft(np.array([1, 2, 3, 4], np.int32), 3).size == 0
    # a tight repeat loop still yields a full draft (the occurrence whose
    # continuation is cut off by the context end is skipped for an earlier
    # full-window one) — deterministic
    ctx = np.array([3] * 8, np.int32)
    assert d.draft(ctx, 4).tolist() == d.draft(ctx, 4).tolist() == [3] * 4


def test_ngram_drafter_continuation_property():
    """Hypothesis: every draft is a literal continuation of the context —
    the drafted run appears in the context immediately after an earlier
    occurrence of the context's trailing n-gram."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st
    from repro.serve.spec import NgramDrafter

    @settings(max_examples=200, deadline=None)
    @given(ctx=st.lists(st.integers(0, 7), min_size=0, max_size=40),
           k=st.integers(0, 6), nmax=st.integers(1, 5))
    def prop(ctx, k, nmax):
        d = NgramDrafter(ngram_max=nmax)
        out = d.draft(np.array(ctx, np.int32), k)
        assert len(out) <= k
        if len(out) == 0:
            return
        ctx_a = np.array(ctx, np.int64)
        L = len(ctx_a)
        witnessed = False
        for n in range(1, min(nmax, L - 1) + 1):
            pat = ctx_a[L - n:]
            for s in range(L - n):
                if (np.array_equal(ctx_a[s:s + n], pat)
                        and np.array_equal(ctx_a[s + n:s + n + len(out)],
                                           out)):
                    witnessed = True
        assert witnessed, "draft is not a continuation of any trailing n-gram"

    prop()


# --------------------------------------------------------------------------
# sampling satellites: top-k / top-p
# --------------------------------------------------------------------------


def test_sample_top_k_restricts_support():
    from repro.serve.engine import _sample
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(4, 32)), F32)
    keys = jax.random.split(jax.random.PRNGKey(0), 64)
    draws = np.stack([np.asarray(_sample(logits, k, 1.0, 5, 1.0))
                      for k in keys])
    top5 = np.argsort(np.asarray(logits), -1)[:, -5:]
    for b in range(4):
        assert set(draws[:, b]) <= set(top5[b]), "draw outside the top-k set"
    # top_k=1 is argmax regardless of key
    g = np.asarray(jnp.argmax(logits, -1))
    for k in keys[:8]:
        assert np.array_equal(np.asarray(_sample(logits, k, 1.0, 1, 1.0)), g)


def test_sample_top_p_restricts_support():
    from repro.serve.engine import _sample
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(4, 32)) * 3, F32)
    p = 0.6
    # reference nucleus: smallest prefix of the sorted probs reaching p
    probs = np.asarray(jax.nn.softmax(logits, -1))
    nuclei = []
    for b in range(4):
        order = np.argsort(-probs[b])
        cum = np.cumsum(probs[b][order])
        keep = int(np.searchsorted(cum, p)) + 1
        nuclei.append(set(order[:keep]))
    keys = jax.random.split(jax.random.PRNGKey(1), 64)
    draws = np.stack([np.asarray(_sample(logits, k, 1.0, 0, p))
                      for k in keys])
    for b in range(4):
        assert set(draws[:, b]) <= nuclei[b], "draw outside the nucleus"
    # tiny top_p degenerates to argmax (the top token is always kept)
    g = np.asarray(jnp.argmax(logits, -1))
    for k in keys[:8]:
        assert np.array_equal(np.asarray(_sample(logits, k, 1.0, 0, 1e-6)),
                              g)
    # out-of-range filters fail loudly instead of silently emitting token 0
    with pytest.raises(ValueError, match="top_p"):
        _sample(logits, keys[0], 1.0, 0, 0.0)
    with pytest.raises(ValueError, match="top_k"):
        _sample(logits, keys[0], 1.0, -3, 1.0)


def test_generate_top_k_one_is_greedy():
    """End-to-end: temperature > 0 with top_k=1 must reproduce the greedy
    decode exactly (single-candidate sampling), through the jitted loop."""
    from repro.serve.engine import generate
    cfg, model, params = _setup()
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(3), (2, 6), 0,
                                          cfg.vocab, I32)}
    greedy = generate(model, params, batch,
                      ServeConfig(max_len=32, cache_dtype="float32"),
                      max_new=6)
    topk1 = generate(model, params, batch,
                     ServeConfig(max_len=32, cache_dtype="float32",
                                 temperature=0.8, top_k=1),
                     max_new=6, key=jax.random.PRNGKey(7))
    assert jnp.all(greedy == topk1)
