"""Paged KV subsystem: allocator, radix trie, paged kernel, paged serving.

The contract under test (DESIGN.md §10):
  * PagePool refcounts: alloc/incref/decref round-trip, zero frees, the
    null page is never handed out;
  * RadixTrie: insert/match page-granular prefixes, edge splits at page
    boundaries, LRU eviction frees trie-only pages and respects live refs,
    copy-on-write divergence never mutates a shared page;
  * fp2fx8 page quantize/dequantize round-trip error bounds;
  * ``flash_hyft_decode_paged`` is bitwise-equal to ``flash_hyft_decode``
    on sequentially laid out pages (dense and fp2fx8), and block-table
    permutations don't change it;
  * greedy paged serving matches the dense slot pool token-for-token
    (dense and fp2fx8 layouts), prefix-cache hits provably skip prefill
    (step counts) while producing identical tokens, and page exhaustion
    preempts + requeues without changing any output.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ServeConfig
from repro.serve.kvpool import NULL_PAGE, PagePool, RadixTrie

F32 = jnp.float32


# --------------------------------------------------------------------------
# PagePool
# --------------------------------------------------------------------------


def test_pool_alloc_free_refcounts():
    pool = PagePool(6)
    a = pool.alloc(4)
    assert a is not None and len(set(a)) == 4 and NULL_PAGE not in a
    assert pool.alloc(3) is None          # partial allocations never happen
    assert pool.free_pages == 2
    pool.incref(a[0])
    pool.decref(a[0])
    assert pool.pages_in_use == 4         # still held by the original ref
    for p in a:
        pool.decref(p)
    assert pool.free_pages == 6
    b = pool.alloc(6)
    assert b is not None and NULL_PAGE not in b


def test_pool_random_workload_conserves_pages():
    rng = np.random.default_rng(0)
    pool = PagePool(16)
    held = []
    for _ in range(300):
        if held and rng.random() < 0.5:
            pool.decref(held.pop(rng.integers(len(held))))
        else:
            got = pool.alloc(int(rng.integers(1, 4)))
            if got is not None:
                held.extend(got)
        assert pool.pages_in_use == len(held)
        assert pool.free_pages + pool.pages_in_use == 16
    for p in held:
        pool.decref(p)
    assert pool.free_pages == 16


# --------------------------------------------------------------------------
# RadixTrie
# --------------------------------------------------------------------------


def _trie(n_pages=32, ps=4):
    pool = PagePool(n_pages)
    return pool, RadixTrie(pool, ps)


def test_trie_insert_match_page_granular():
    pool, trie = _trie()
    toks = list(range(11))                 # 2 full pages + a partial tail
    pages = pool.alloc(3)
    assert trie.insert(toks, pages) == 2   # only full pages are adopted
    got, n = trie.match(toks)
    assert got == pages[:2] and n == 8
    # a shorter query matches only whole pages of itself
    got, n = trie.match(toks[:6])
    assert got == pages[:1] and n == 4
    got, n = trie.match([99] * 8)
    assert got == [] and n == 0


def test_trie_split_and_divergence_copy_on_write():
    """Two prompts sharing 2 pages then diverging: the edge splits at the
    page boundary, both suffixes coexist, and the shared pages keep their
    ids (nothing is copied — divergence lands in fresh pages)."""
    pool, trie = _trie()
    a = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12]       # 3 pages
    b = a[:8] + [99, 98, 97, 96]                       # shares 2 pages
    pa = pool.alloc(3)
    trie.insert(a, pa)
    got, n = trie.match(b)
    assert got == pa[:2] and n == 8                    # prefix reuse
    pb = pool.alloc(1)                                 # only the tail is new
    assert trie.insert(b, pa[:2] + pb) == 1            # adopts just the tail
    # both full prompts still resolve, through the split edge
    assert trie.match(a) == (pa, 12)
    assert trie.match(b) == (pa[:2] + pb, 12)
    assert pool.refs[pa[0]] == 2                       # alloc ref + trie ref


def test_trie_insert_keeps_existing_pages():
    """A duplicate insert with different page ids adopts nothing — the
    first writer's pages win and the duplicates stay private."""
    pool, trie = _trie()
    toks = list(range(8))
    p1, p2 = pool.alloc(2), pool.alloc(2)
    assert trie.insert(toks, p1) == 2
    assert trie.insert(toks, p2) == 0
    assert trie.match(toks) == (p1, 8)


def test_trie_evict_lru_frees_pages_and_respects_refs():
    pool, trie = _trie(n_pages=8)
    a, b = pool.alloc(2), pool.alloc(2)
    trie.insert([1, 2, 3, 4, 5, 6, 7, 8], a)
    trie.insert([9, 10, 11, 12, 13, 14, 15, 16], b)
    for p in a + b:
        pool.decref(p)                      # trie is now the only holder
    trie.match([9, 10, 11, 12])             # touch b: a becomes LRU
    pool.incref(a[0])
    pool.incref(a[1])                       # ...but a is pinned by a "slot"
    assert trie.evict(1) == 2               # so the b edge goes instead
    assert trie.match([9, 10, 11, 12]) == ([], 0)
    assert trie.match([1, 2, 3, 4]) == (a[:1], 4)
    pool.decref(a[0])
    pool.decref(a[1])
    assert trie.evict(2) == 2               # now a is evictable
    assert pool.free_pages == 8 and trie.n_pages() == 0


def test_trie_random_property_vs_reference():
    """Random inserts/matches against a brute-force reference: match must
    return the longest page-aligned prefix ever inserted, with the pages
    of the FIRST insert that covered each page."""
    rng = np.random.default_rng(3)
    ps = 2
    pool = PagePool(512)
    trie = RadixTrie(pool, ps)
    ref: dict = {}                           # page-path tuple -> page id
    for _ in range(60):
        n_tok = int(rng.integers(ps, 17))
        toks = rng.integers(0, 3, n_tok).tolist()   # small vocab: collisions
        pages = pool.alloc(-(-n_tok // ps))
        trie.insert(toks, pages)
        for j in range(n_tok // ps):
            ref.setdefault(tuple(toks[:(j + 1) * ps]), pages[j])
        q_len = int(rng.integers(0, 17))
        q = rng.integers(0, 3, q_len).tolist()
        got, n = trie.match(q)
        want = []
        for j in range(q_len // ps):
            key = tuple(q[:(j + 1) * ps])
            if key not in ref:
                break
            want.append(ref[key])
        # the trie may stop earlier at an unsplit partial edge, but what it
        # returns must be a prefix of the reference answer — and whenever it
        # returns less, the next reference page must sit mid-edge (the trie
        # never misses a node boundary)
        assert got == want[:len(got)], (q, got, want)
        assert n == len(got) * ps


def test_trie_match_exhaustive_after_inserts():
    """Full-prompt matches (the serving access pattern: query == an inserted
    prompt) are always complete, partial edges included."""
    rng = np.random.default_rng(4)
    ps = 2
    pool = PagePool(512)
    trie = RadixTrie(pool, ps)
    first: dict = {}
    prompts = []
    for _ in range(40):
        toks = rng.integers(0, 3, int(rng.integers(ps, 13))).tolist()
        pages = pool.alloc(len(toks) // ps)
        trie.insert(toks[:(len(toks) // ps) * ps], pages)
        prompts.append(toks)
        for j in range(len(toks) // ps):
            first.setdefault(tuple(toks[:(j + 1) * ps]), pages[j])
    for toks in prompts:
        got, n = trie.match(toks)
        want = [first[tuple(toks[:(j + 1) * ps])]
                for j in range(len(toks) // ps)]
        assert got == want and n == len(want) * ps


# --------------------------------------------------------------------------
# fp2fx8 page round-trip bounds
# --------------------------------------------------------------------------


def test_fp2fx8_roundtrip_error_bounds():
    """Quantize/dequantize of page content: the per-(head, position) amax
    scale bounds the round-trip error by scale/2 (round-to-nearest on a
    uniform int8 grid), rows round-trip exactly at 0, and the raws use the
    full int8 range."""
    from repro.models.attention import fp2fx8_dequantize, fp2fx8_quantize
    rng = np.random.default_rng(5)
    for scale_mag in (1e-3, 1.0, 37.5):
        x = jnp.asarray(rng.normal(0, scale_mag, (3, 4, 16, 32)), F32)
        raw, s = fp2fx8_quantize(x)
        back = fp2fx8_dequantize(raw, s)
        assert raw.dtype == jnp.int8
        err = np.abs(np.asarray(back - x))
        bound = np.asarray(s)[..., None] / 2 + 1e-12
        assert np.all(err <= bound), (err.max(), bound.min())
    z = jnp.zeros((2, 2, 4, 8), F32)
    raw, s = fp2fx8_quantize(z)
    assert np.all(np.asarray(fp2fx8_dequantize(raw, s)) == 0.0)


@pytest.mark.parametrize("cache_dtype", ["float32", "fp2fx8"])
def test_block_paged_write_matches_token_by_token(cache_dtype):
    """The one-scatter chunk write lands every live page bit for bit where
    S sequential one-token writes put it: scattered block tables, ragged
    ``n_valid``, a gated-off row, and lanes past the table's extent.  Only
    the null page (the masked-write sink, never read) may differ."""
    from types import SimpleNamespace
    from repro.models.attention import (cache_update_block_paged,
                                        cache_update_paged, paged_cache_init)
    rng = np.random.default_rng(6)
    B, Hkv, D, ps, nb, S = 3, 2, 8, 4, 3, 7
    cfg = SimpleNamespace(n_kv_heads=Hkv, d_head=D)
    pool = paged_cache_init(cfg, B * nb, ps, cache_dtype)
    pool = {n: jnp.asarray(rng.normal(size=x.shape) * 3).astype(x.dtype)
            if x.dtype == F32 else x for n, x in pool.items()}
    bt = jnp.asarray(rng.permutation(B * nb) + 1, jnp.int32).reshape(B, nb)
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)) * 5, F32)
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), F32)
    pos = jnp.asarray([1, 9, 6], jnp.int32)    # row 1 runs past nb * ps
    n_valid = jnp.asarray([7, 6, 3], jnp.int32)
    gate = jnp.asarray([True, True, False])
    block = cache_update_block_paged(pool, k, v, pos, bt, n_valid, gate)
    seq = pool
    for j in range(S):
        g = gate & (j < n_valid) & (pos + j < nb * ps)
        seq = cache_update_paged(seq, k[:, :, j:j + 1], v[:, :, j:j + 1],
                                 jnp.clip(pos + j, 0, nb * ps - 1), bt, g)
    assert set(block) == set(seq)
    for name in block:
        a, b = np.asarray(block[name])[1:], np.asarray(seq[name])[1:]
        assert a.dtype == b.dtype and np.array_equal(a, b), name
    assert not np.array_equal(np.asarray(block["k"])[1:],
                              np.asarray(pool["k"])[1:])


# --------------------------------------------------------------------------
# paged decode kernel: bitwise equality with the contiguous split-K kernel
# --------------------------------------------------------------------------


def _seq_pages(k, ps):
    """(B, Hkv, Sk, D) -> sequential page pool (B * Sk/ps, Hkv, ps, D)."""
    B, Hkv, Sk, D = k.shape
    nb = Sk // ps
    kp = k.transpose(0, 2, 1, 3).reshape(B, nb, ps, Hkv, D)
    return kp.transpose(0, 1, 3, 2, 4).reshape(B * nb, Hkv, ps, D)


@pytest.mark.parametrize("quantized", [False, True])
def test_paged_kernel_bitwise_vs_contiguous(quantized):
    from repro.core.registry import hyft_config_for
    from repro.kernels.flash_attention import (flash_hyft_decode,
                                               flash_hyft_decode_paged)
    from repro.models.attention import fp2fx8_quantize
    cfg = hyft_config_for("hyft16")
    B, Hq, Hkv, D, ps, nb = 2, 4, 2, 16, 16, 4
    Sk = ps * nb
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, Hq, 1, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Hkv, Sk, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Hkv, Sk, D))
    mask = (jnp.arange(Sk)[None, :]
            < jnp.array([37, 64])[:, None]).astype(F32)
    ks = vs = kps = vps = None
    if quantized:
        k, ks = fp2fx8_quantize(k)
        v, vs = fp2fx8_quantize(v)
        kps = _seq_pages(ks[..., None], ps)[..., 0]
        vps = _seq_pages(vs[..., None], ps)[..., 0]
    dense = flash_hyft_decode(q, k, v, cfg, block_k=ps, interpret=True,
                              kv_len_mask=mask, k_scale=ks, v_scale=vs)
    bt = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    paged = flash_hyft_decode_paged(
        q, _seq_pages(k, ps), _seq_pages(v, ps), bt, cfg, interpret=True,
        kv_len_mask=mask, k_scale=kps, v_scale=vps)
    assert paged.shape == (B, Hq, 1, D)
    assert jnp.all(dense == paged), "paged kernel != contiguous split-K"


@pytest.mark.parametrize("grid", ["page_blocks", "per_split"])
@pytest.mark.parametrize("cache_dtype", ["bfloat16", "fp2fx8"])
@pytest.mark.parametrize("hq,hkv", [(4, 2), (2, 2), (6, 1)],
                         ids=["gqa", "mha", "gqa6"])
def test_paged_kernel_page_blocks_bitwise(hq, hkv, cache_dtype, grid,
                                          monkeypatch):
    """The paged kernel against ``flash_hyft_decode`` at block_k ==
    page_size on the same pages, gathered into contiguous stripes: 12
    pages in blocks of 8, so four padding page slots that must stay out of
    the merge; slot 0's mask ends mid-block, slot 1's on the block edge,
    and slot 2 is idle (its table all null page 0) with a stale mask that
    still marks 150 positions valid.  ``per_split`` lowers the VMEM cap
    below these shapes' plan, so the call takes the per-split grid that
    long contexts take."""
    from repro.core.registry import hyft_config_for
    from repro.kernels import flash_attention as fa
    from repro.models.attention import fp2fx8_quantize
    cfg = hyft_config_for("hyft16")
    B, D, ps, nb = 3, 16, 16, 12
    P, nblk, vmem = fa.paged_decode_plan(hq, hkv, D, ps, nb, jnp.int8,
                                         cache_dtype == "fp2fx8")
    assert (P, nblk) == (8, 2) and nblk * P > nb and vmem < fa.PAGED_VMEM_CAP
    paged_fn = fa.flash_hyft_decode_paged
    if grid == "per_split":  # unjitted, so the lowered cap is read
        monkeypatch.setattr(fa, "PAGED_VMEM_CAP", vmem - 1)
        paged_fn = paged_fn.__wrapped__
    n_pages = 1 + 2 * nb
    key = jax.random.PRNGKey(11)
    q = jax.random.normal(key, (B, hq, 1, D))
    kp = jax.random.normal(jax.random.fold_in(key, 1), (n_pages, hkv, ps, D))
    vp = jax.random.normal(jax.random.fold_in(key, 2), (n_pages, hkv, ps, D))
    # large values on the null page: a padding page slot that entered the
    # merge, even at its alpha of about 2^-54, would move the result
    vp = vp.at[0].multiply(1e12)
    ks = vs = None
    if cache_dtype == "fp2fx8":
        kp, ks = fp2fx8_quantize(kp)
        vp, vs = fp2fx8_quantize(vp)
    else:
        kp, vp = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
    seq = jnp.arange(1, n_pages, dtype=jnp.int32).reshape(2, nb)
    bt = jnp.concatenate([seq, jnp.zeros((1, nb), jnp.int32)])
    lens = jnp.array([100, P * ps, 150])
    mask = (jnp.arange(nb * ps)[None, :] < lens[:, None]).astype(F32)

    def stripes(pool):  # (n_pages, Hkv, ps, ...) -> (B, Hkv, nb * ps, ...)
        x = jnp.moveaxis(pool[bt], 2, 1)
        return x.reshape(B, hkv, nb * ps, *pool.shape[3:])
    dense = fa.flash_hyft_decode(
        q, stripes(kp), stripes(vp), cfg, block_k=ps, interpret=True,
        kv_len_mask=mask, k_scale=None if ks is None else stripes(ks),
        v_scale=None if vs is None else stripes(vs))
    paged = paged_fn(q, kp, vp, bt, cfg, interpret=True, kv_len_mask=mask,
                     k_scale=ks, v_scale=vs)
    assert paged.shape == (B, hq, 1, D)
    assert jnp.all(jnp.isfinite(paged))
    assert jnp.all(dense == paged), "paged kernel != per-page split-K"


def test_paged_kernel_invariant_to_page_placement():
    """Physically permuting the pool (with the block table following) must
    not change a bit — the kernel reads pages only through the table."""
    from repro.core.registry import hyft_config_for
    from repro.kernels.flash_attention import flash_hyft_decode_paged
    cfg = hyft_config_for("hyft16")
    B, Hq, Hkv, D, ps, nb = 2, 4, 2, 16, 8, 4
    key = jax.random.PRNGKey(7)
    q = jax.random.normal(key, (B, Hq, 1, D))
    kp = jax.random.normal(jax.random.fold_in(key, 1), (B * nb, Hkv, ps, D))
    vp = jax.random.normal(jax.random.fold_in(key, 2), (B * nb, Hkv, ps, D))
    bt = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    base = flash_hyft_decode_paged(q, kp, vp, bt, cfg, interpret=True)
    perm = jax.random.permutation(jax.random.fold_in(key, 3), B * nb)
    inv = jnp.argsort(perm)
    shuf = flash_hyft_decode_paged(q, kp[perm], vp[perm], inv[bt], cfg,
                                   interpret=True)
    assert jnp.all(base == shuf)


@pytest.mark.parametrize("cache_dtype", ["bfloat16", "fp2fx8"])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)], ids=["mha", "gqa4"])
@pytest.mark.parametrize("sq", [1, 5, 128, 200])
def test_paged_verify_page_blocks(sq, hq, hkv, cache_dtype, monkeypatch):
    """The paged verify kernel's page-block grid on a shuffled pool: 12
    pages in blocks of 8 (four padding page slots reading a poisoned null
    page), chunks whose rows start at 0, mid-page (37) and near the
    table's end (150).

    * the live-page plan from host lengths equals a count from the mask;
    * skipping the page blocks past each lane block's frontier gives
      bitwise the result of merging every page;
    * against the per-split grid it replaced (still run past
      ``PAGED_VMEM_CAP``): the same per-page splits and merge, so equal up
      to f32 rounding order — on the CPU the dot products and the XLA
      combine round in an order that depends on the program's shapes —
      which moves an output by a few steps of its 10-bit mantissa."""
    from repro.core.registry import hyft_config_for
    from repro.kernels import flash_attention as fa
    from repro.models.attention import fp2fx8_quantize
    cfg = hyft_config_for("hyft16")
    B, D, ps, nb = 3, 16, 16, 12
    quantized = cache_dtype == "fp2fx8"
    plan = fa.paged_verify_plan(hq, hkv, D, ps, nb, sq, jnp.int8, quantized)
    assert (plan.P, plan.nblk) == (8, 2) and plan.vmem < fa.PAGED_VMEM_CAP
    n_pages = 1 + B * nb
    key = jax.random.PRNGKey(sq * 10 + hq)
    q = jax.random.normal(key, (B, hq, sq, D))
    kp = jax.random.normal(jax.random.fold_in(key, 1), (n_pages, hkv, ps, D))
    vp = jax.random.normal(jax.random.fold_in(key, 2), (n_pages, hkv, ps, D))
    vp = vp.at[0].multiply(1e12)  # the null page: padding slots read it
    ks = vs = None
    if quantized:
        kp, ks = fp2fx8_quantize(kp)
        vp, vs = fp2fx8_quantize(vp)
    else:
        kp, vp = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
    perm = jax.random.permutation(jax.random.fold_in(key, 3), n_pages - 1)
    bt = (1 + perm).astype(jnp.int32).reshape(B, nb)
    start = np.array([0, 37, 150])
    # each lane's frontier as prefill_chunk builds it: kv_index <= start + t
    front = np.minimum(start[:, None] + np.arange(sq), nb * ps - 1)
    mask = jnp.arange(nb * ps)[None, None, :] <= front[:, :, None]
    kw = dict(interpret=True, block_tables=bt, k_scale=ks, v_scale=vs)
    verify = fa.flash_hyft_verify.__wrapped__  # reads the patches below
    out = verify(q, kp, vp, mask, cfg, **kw)
    assert out.shape == (B, hq, sq, D) and jnp.all(jnp.isfinite(out))

    live = fa.verify_live_pages(front, plan.lanes, ps)
    assert live.shape == (B, plan.nq)
    for b in range(B):
        for i in range(plan.nq):
            seen = np.nonzero(np.asarray(
                mask[b, i * plan.lanes:(i + 1) * plan.lanes]).any(0))[0]
            assert live[b, i] == seen[-1] // ps + 1
    assert live.min() < nb  # some lane blocks skip pages

    with monkeypatch.context() as mp:  # every page of the table is live
        mp.setattr(fa, "verify_live_pages",
                   lambda f, lanes, ps: jnp.full(
                       (f.shape[0], -(-f.shape[1] // lanes)), nb))
        whole = verify(q, kp, vp, mask, cfg, **kw)
    # a dead page weighs about 2^-46 here: bitwise wherever that is below
    # half an ulp, i.e. all but the results near 0 (an fp2fx8 V of 0 under
    # a lane's only visible key), where it is the masked keys' leak alone
    near_zero = jnp.abs(whole) < 2.0 ** -20
    assert jnp.all((out == whole) | near_zero), "skipping moved a bit"
    assert jnp.max(jnp.abs(out - whole)) < 2.0 ** -30

    monkeypatch.setattr(fa, "PAGED_VMEM_CAP", plan.vmem - 1)
    kept = verify(q, kp, vp, mask, cfg, **kw)
    # rounding apart: a few steps of the 10-bit mantissa, absolute at the
    # outputs' scale (about 1) where a lane's sum cancels toward 0
    np.testing.assert_allclose(out, kept, rtol=2.0 ** -8, atol=2.0 ** -12)
    assert jnp.mean(out == kept) > 0.99


# --------------------------------------------------------------------------
# paged serving: parity, prefix-cache skip, preemption
# --------------------------------------------------------------------------


def _setup(vocab=64, **kw):
    from repro.configs import get_config, smoke_config
    from repro.models import build_model
    from repro.models.layers import unbox
    cfg = smoke_config(get_config("qwen2-1.5b")).with_(
        softmax_impl="hyft16", vocab=vocab, **kw)
    model = build_model(cfg)
    params = unbox(model.init(jax.random.PRNGKey(0)))
    return cfg, model, params


def _requests(cfg, n, rng, plen=(3, 9), max_new=(3, 9)):
    from repro.serve.scheduler import Request
    return [Request(
        rid=rid,
        tokens=rng.integers(0, cfg.vocab, int(rng.integers(*plen))).astype(
            np.int32),
        max_new=int(rng.integers(*max_new))) for rid in range(n)]


def _solo(model, params, req, scfg):
    from repro.serve.engine import generate
    out = generate(model, params, {"tokens": jnp.asarray(req.tokens)[None]},
                   scfg, max_new=req.max_new)
    return np.asarray(out)[0].tolist()


@pytest.mark.parametrize("cache_dtype", ["float32", "fp2fx8"])
def test_paged_matches_dense_slot_pool(cache_dtype):
    """Greedy paged serving == dense slot pool == solo generate, token for
    token, over both cache formats (page placement is invisible)."""
    from repro.serve.scheduler import SlotPoolEngine
    cfg, model, params = _setup()
    reqs = _requests(cfg, 5, np.random.default_rng(0))
    outs = {}
    for layout in ("dense", "paged"):
        scfg = ServeConfig(max_len=32, cache_dtype=cache_dtype,
                           scheduler="continuous", n_slots=3, decode_burst=4,
                           kv_layout=layout, page_size=4)
        eng = SlotPoolEngine(model, params, scfg)
        done = eng.run(reqs)
        outs[layout] = {rid: c.tokens for rid, c in done.items()}
        if layout == "paged":
            assert eng.stats["pages_peak"] > 0
            assert eng.pool.pages_in_use == 0      # every page returned
    assert outs["paged"] == outs["dense"]
    solo_cfg = ServeConfig(max_len=32, cache_dtype=cache_dtype)
    for r in reqs:
        assert outs["paged"][r.rid] == _solo(model, params, r, solo_cfg)


def test_verify_page_block_counters():
    """Each paged chunk call counts the verify kernel's page blocks: all
    its grid holds (n_slots x lane blocks x page blocks, from the call's
    width) and the live ones, fewer once short prompts leave pages past
    every frontier; a tracer counter event carries both per call."""
    from repro.kernels import flash_attention as fa
    from repro.obs import Obs
    from repro.serve.scheduler import SlotPoolEngine
    cfg, model, params = _setup()
    scfg = ServeConfig(max_len=256, cache_dtype="fp2fx8",
                       scheduler="continuous", n_slots=3, decode_burst=4,
                       kv_layout="paged", page_size=16, prefill_chunk=4)
    eng = SlotPoolEngine(model, params, scfg, obs=Obs.enabled())
    eng.run(_requests(cfg, 4, np.random.default_rng(1)))
    ev = eng.obs.tracer.events
    widths = [e["args"]["width"] for e in ev if e["name"] == "prefill_chunk"]
    counts = [e["args"] for e in ev if e["name"] == "verify_page_blocks"]
    assert len(widths) == len(counts) == eng.stats["prefills"] > 0
    total = 0
    for w in widths:
        plan = fa.paged_verify_plan(cfg.n_heads, cfg.n_kv_heads, cfg.d_head,
                                    16, 16, w, jnp.int8, True)
        total += scfg.n_slots * plan.nq * plan.nblk
    st = eng.stats
    assert st["verify_page_blocks_total"] == total
    assert sum(c["total"] for c in counts) == total
    assert sum(c["live"] for c in counts) == st["verify_page_blocks_live"]
    assert 0 < st["verify_page_blocks_live"] < total  # 2 blocks, 1 live


def test_prefix_cache_skips_prefill_and_matches():
    """Identical prompts served one after another: later admissions must
    hit the radix trie, push ONLY the un-cached suffix through the model
    (prefill_tokens step count), and still emit identical tokens."""
    from repro.serve.scheduler import SlotPoolEngine
    cfg, model, params = _setup()
    rng = np.random.default_rng(1)
    shared = rng.integers(0, cfg.vocab, 12).astype(np.int32)
    from repro.serve.scheduler import Request
    reqs = [Request(rid=i, tokens=np.concatenate(
                [shared, rng.integers(0, cfg.vocab, 3).astype(np.int32)]),
            max_new=5) for i in range(4)]
    scfg = ServeConfig(max_len=32, cache_dtype="float32",
                       scheduler="continuous", n_slots=1, decode_burst=4,
                       kv_layout="paged", page_size=4, prefix_cache=True)
    eng = SlotPoolEngine(model, params, scfg)
    done = eng.run(reqs)
    st = eng.stats
    assert st["prefix_hits"] == 3                 # every follower hits
    assert st["cached_tokens"] == 3 * 12          # the shared 12-token head
    # the FLOP-skip proof: model-visible prefill steps cover only the
    # un-cached tokens, not the full prompts
    assert st["prefill_tokens"] == st["prompt_tokens"] - st["cached_tokens"]
    assert st["prompt_tokens"] == sum(len(r.tokens) for r in reqs)
    solo_cfg = ServeConfig(max_len=32, cache_dtype="float32")
    for r in reqs:
        assert done[r.rid].tokens == _solo(model, params, r, solo_cfg), \
            f"rid={r.rid}"


def test_prefix_cache_shares_pages_between_live_slots():
    """Concurrent requests with the same prompt hold the SAME physical
    pages (refcount > trie+1) while both decode — and the shared pages are
    never written past admission (copy-on-write by page granularity)."""
    from repro.serve.scheduler import Request, SlotPoolEngine
    cfg, model, params = _setup()
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg.vocab, 9).astype(np.int32)
    scfg = ServeConfig(max_len=32, cache_dtype="float32",
                       scheduler="continuous", n_slots=2, decode_burst=2,
                       kv_layout="paged", page_size=4, prefix_cache=True)
    eng = SlotPoolEngine(model, params, scfg)
    # admit A alone first (populates the trie), then B mid-decode of A
    reqs = [Request(rid=0, tokens=prompt, max_new=12),
            Request(rid=1, tokens=prompt, max_new=12, arrival=0.05)]
    done = eng.run(reqs)
    assert eng.stats["prefix_hits"] == 1
    assert eng.stats["cached_tokens"] == 8        # 2 full pages of 4
    assert done[0].tokens == done[1].tokens       # same prompt, same greedy
    solo_cfg = ServeConfig(max_len=32, cache_dtype="float32")
    assert done[0].tokens == _solo(model, params, reqs[0], solo_cfg)


def test_page_exhaustion_preempts_and_requeues():
    """A pool too small for three full sequences must preempt the
    latest-arrival slot, requeue it through admission, and still produce
    the exact greedy outputs at full length."""
    from repro.serve.scheduler import SlotPoolEngine
    cfg, model, params = _setup()
    rng = np.random.default_rng(3)
    reqs = _requests(cfg, 3, rng, plen=(6, 7), max_new=(10, 11))
    scfg = ServeConfig(max_len=32, cache_dtype="float32",
                       scheduler="continuous", n_slots=3, decode_burst=4,
                       kv_layout="paged", page_size=4, n_pages=9)
    eng = SlotPoolEngine(model, params, scfg)
    done = eng.run(reqs)
    assert eng.stats["preemptions"] >= 1
    solo_cfg = ServeConfig(max_len=32, cache_dtype="float32")
    for r in reqs:
        assert len(done[r.rid].tokens) == r.max_new
        assert done[r.rid].tokens == _solo(model, params, r, solo_cfg)
    assert eng.pool.pages_in_use == 0


def test_eviction_cannot_steal_matched_prefix_pages():
    """A prefix match under page pressure must never hand the matched pages
    back out as the same request's fresh tail pages: the match is pinned
    before allocation-triggered eviction runs (and dropped entirely when
    the pinned prefix is the only reclaimable memory), so outputs stay
    correct even when the cached prefix itself must be evicted."""
    from collections import deque
    from repro.serve.scheduler import Request, SlotPoolEngine
    cfg, model, params = _setup()
    rng = np.random.default_rng(6)
    q_head = rng.integers(0, cfg.vocab, 8).astype(np.int32)
    reqs = [
        Request(rid=0, tokens=rng.integers(0, cfg.vocab, 8).astype(np.int32),
                max_new=12),                  # long-runner pinning pool pages
        Request(rid=1, tokens=q_head, max_new=1),   # publishes q_head pages
        Request(rid=2, tokens=np.concatenate(
            [q_head, rng.integers(0, cfg.vocab, 8).astype(np.int32)]),
            max_new=4),                       # matches q_head under pressure
    ]
    scfg = ServeConfig(max_len=24, cache_dtype="float32",
                       scheduler="continuous", n_slots=2, decode_burst=4,
                       kv_layout="paged", page_size=4, n_pages=7,
                       prefix_cache=True)
    eng = SlotPoolEngine(model, params, scfg)
    # deterministic drive (run()'s admission depends on wall-clock arrivals):
    # grow rid 0's block table, publish rid 1's pages to the trie, then
    # admit rid 2 exactly when free pages < its un-matched demand
    eng.admit([reqs[0]], 0.0)
    eng._prefill_step(0.0)
    eng.burst(0.0)
    eng.burst(0.0)
    eng.admit([reqs[1]], 0.0)
    eng._prefill_step(0.0)
    assert eng.completions[1].tokens and int(eng.active.sum()) == 1
    assert eng.pool.free_pages < 2            # the pressure the bug needs
    eng.admit([reqs[2]], 0.0)
    # the buggy ordering hands the evicted prefix pages back as rid 2's
    # tail, aliasing one physical page at two virtual blocks — a slot's
    # block table must never contain duplicates
    for s in range(scfg.n_slots):
        pages = eng.slot_pages[s]
        assert len(pages) == len(set(pages)), f"slot {s} aliases {pages}"
    while eng.active.any() or eng.prefilling.any() or eng._queue:
        # drain, re-admitting requeues
        if eng._queue and any(rid is None for rid in eng.slot_rid):
            eng.admit([eng._queue.popleft()], 0.0)
        if eng.prefilling.any():
            eng._prefill_step(0.0)
        if eng.active.any():
            eng.burst(0.0)
    solo_cfg = ServeConfig(max_len=24, cache_dtype="float32")
    for r in reqs:
        assert eng.completions[r.rid].tokens == _solo(model, params, r,
                                                      solo_cfg), r.rid
    assert isinstance(eng._queue, deque) and not eng._queue


# --------------------------------------------------------------------------
# audit property tests: random admit/finish/preempt/evict/cancel sequences
# --------------------------------------------------------------------------


def _audit_sim(ops, n_pages=24, ps=2, vocab=3):
    """Drive PagePool + RadixTrie through a scheduler-shaped op sequence,
    auditing after EVERY op (DESIGN.md §13).  ``ops`` is a list of
    ``(kind, a, b)`` int triples; kind % 5 selects admit / finish /
    preempt / evict-storm / cancel — finish, preempt, and cancel all
    release a holder the same way (requeue is host-side bookkeeping), so
    the pool-level invariant they share is what's under test: refcounts
    recomputed from holders + trie edges always balance, and no page is
    ever double-freed or leaked."""
    pool = PagePool(n_pages)
    trie = RadixTrie(pool, ps)
    holders: list = []
    for kind, a, b in ops:
        k = kind % 5
        if k == 0:                        # admit: match, pin, alloc, publish
            n_tok = ps * (1 + a % 4) + b % ps
            toks = [(a * 7 + b * 3 + j) % vocab for j in range(n_tok)]
            matched, _ = trie.match(toks)
            # pin the match BEFORE any allocation-triggered eviction can
            # run — the ordering test_eviction_cannot_steal... guards
            for p in matched:
                pool.incref(p)
            nb_need = -(-n_tok // ps) - len(matched)
            tail = pool.alloc(nb_need) if nb_need > 0 else []
            if tail is None:
                trie.evict(nb_need)       # pressure path
                tail = pool.alloc(nb_need)
            if tail is None:              # admission deferred: unwind pins
                for p in matched:
                    pool.decref(p)
            else:
                pages = matched + tail
                holders.append(pages)
                nfull = n_tok // ps
                if nfull:
                    trie.insert(toks[:nfull * ps], pages[:nfull])
        elif k == 3:                      # eviction storm
            trie.evict(1 + a % 4)
        elif holders:                     # finish / preempt / cancel
            for p in holders.pop(a % len(holders)):
                pool.decref(p)
        pool.audit(holders, trie)
        trie.audit()
    for pages in holders:                 # drain: everything must come back
        for p in pages:
            pool.decref(p)
    pool.audit([], trie)
    trie.evict(1 << 30)
    assert pool.free_pages == n_pages


def test_audit_random_ops_seeded():
    """Seeded fallback for environments without hypothesis: 8 random
    40-op admit/finish/preempt/evict/cancel sequences, audits clean after
    every op and all pages recovered at drain."""
    rng = np.random.default_rng(9)
    for _ in range(8):
        ops = [tuple(int(x) for x in rng.integers(0, 64, 3))
               for _ in range(40)]
        _audit_sim(ops)


def test_audit_random_ops_hypothesis():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(max_examples=40, deadline=None)
    @given(st.lists(st.tuples(st.integers(0, 63), st.integers(0, 63),
                              st.integers(0, 63)), max_size=60))
    def check(ops):
        _audit_sim(ops)

    check()


def test_paged_config_validation():
    from repro.serve.scheduler import SlotPoolEngine
    cfg, model, params = _setup()
    with pytest.raises(ValueError):   # pool can't hold one request
        SlotPoolEngine(model, params, ServeConfig(
            max_len=32, kv_layout="paged", page_size=4, n_pages=4))
    with pytest.raises(ValueError):   # prefix cache needs the paged layout
        SlotPoolEngine(model, params, ServeConfig(
            max_len=32, kv_layout="dense", prefix_cache=True))
    with pytest.raises(ValueError):
        SlotPoolEngine(model, params, ServeConfig(
            max_len=32, kv_layout="banana"))
