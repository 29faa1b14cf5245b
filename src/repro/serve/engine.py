"""Serving engine: batched prefill + fully on-device decode.

``ServeConfig.decode_loop`` picks the loop:

  scan — the production path: the whole decode runs inside ONE jitted
         ``lax.scan`` (sampling included), so there is exactly one compile
         and zero per-token host round-trips.  The KV-cache buffers are
         donated into the loop so the scan's in-place ``dynamic_update_slice``
         writes reuse them instead of copying.
  host — one jitted step per token, dispatched from Python; the debugging
         fallback (inspectable per-token state) and the dispatch-overhead
         baseline the benchmark compares against.

Step/loop functions are compiled once per (model config, serve config
[, horizon]) and cached — repeated ``generate`` calls re-trace nothing.
Greedy decode (``temperature == 0``) never touches the PRNG: no split, no
key threading.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ServeConfig

I32 = jnp.int32

_PREFILL_CACHE: dict = {}
_STEP_CACHE: dict = {}
_LOOP_CACHE: dict = {}
_CACHE_CAP = 32  # compiled entries per cache; oldest evicted (re-jit on miss)


def _cache_put(cache: dict, key, value):
    """Insert with FIFO eviction so a long-lived server with many distinct
    (config, horizon) combinations doesn't retain executables unboundedly."""
    while len(cache) >= _CACHE_CAP:
        cache.pop(next(iter(cache)))
    cache[key] = value
    return value


def build_prefill(model):
    """Jit'd (params, cache, batch) -> (logits, cache, len).

    The eager prefill used to re-trace the whole stack op-by-op on every
    ``generate`` call; jitted + cached it compiles once per model config.
    The incoming (empty) cache is donated — prefill overwrites it anyway.
    """
    ck = model.cfg
    if ck not in _PREFILL_CACHE:
        def prefill(params, cache, batch):
            with jax.named_scope("prefill"):
                return model.prefill(params, cache, batch)
        return _cache_put(_PREFILL_CACHE, ck,
                          jax.jit(prefill, donate_argnums=(1,)))
    return _PREFILL_CACHE[ck]


def _sample(logits, key, temperature, top_k: int = 0, top_p: float = 1.0):
    """logits (B, V) -> token ids (B,).  Greedy when temperature == 0.

    ``top_k`` (0 = off) keeps only the k highest logits; ``top_p`` (1.0 =
    off) keeps the smallest set of tokens whose probability mass reaches p
    (the top token always survives).  Both filter the temperature-scaled
    logits, top-k first then the nucleus — the usual serving-stack order.
    """
    if top_k < 0:
        raise ValueError(f"top_k must be >= 0 (0 = off), got {top_k}")
    if not 0.0 < top_p <= 1.0:
        # top_p <= 0 would empty the nucleus and silently emit token 0
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if temperature <= 0:
        return jnp.argmax(logits, -1)
    logits = logits / temperature
    V = logits.shape[-1]
    use_k = bool(top_k) and 0 < top_k < V
    if use_k or top_p < 1.0:
        # one descending sort serves both filters — this runs inside the
        # jitted per-token decode loops
        srt = jnp.sort(logits, axis=-1)[..., ::-1]
        if use_k:
            logits = jnp.where(logits < srt[..., top_k - 1][..., None],
                               -jnp.inf, logits)
            # the nucleus is computed over the top-k-filtered distribution
            srt = jnp.where(jnp.arange(V) < top_k, srt, -jnp.inf)
        if top_p < 1.0:
            prob = jax.nn.softmax(srt, axis=-1)
            # keep while the mass BEFORE a token is < p: the minimal
            # nucleus, and the top token is always kept (its exclusive
            # prefix mass is 0)
            keep = (jnp.cumsum(prob, axis=-1) - prob) < top_p
            thresh = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1,
                             keepdims=True)
            logits = jnp.where(logits < thresh, -jnp.inf, logits)
    return jax.random.categorical(key, logits, -1)


def build_serve_step(model, scfg: ServeConfig):
    """Jit'd (params, cache, tokens1, pos, key) -> (next_token, cache).

    Cached per (model config, serve config): repeated ``generate`` calls
    reuse the same compiled step instead of re-jitting every time.  The
    cache is donated — the host loop rebinds it every token, so without
    donation each step copied the entire KV cache just to append one row.
    """
    ck = (model.cfg, scfg)
    if ck not in _STEP_CACHE:
        @functools.partial(jax.jit, donate_argnums=(1,))
        def step(params, cache, tokens1, pos, key):
            with jax.named_scope("serve_step"):
                logits, cache = model.decode_step(params, cache, tokens1,
                                                  pos)
                nxt = _sample(logits[:, -1, :], key, scfg.temperature,
                              scfg.top_k, scfg.top_p)
            return nxt.astype(I32)[:, None], cache
        _cache_put(_STEP_CACHE, ck, step)
    return _STEP_CACHE[ck]


def build_decode_loop(model, scfg: ServeConfig, steps: int):
    """Jit'd (params, cache, tok0, pos0, key) -> ((B, steps) tokens, cache).

    The whole decode is one ``lax.scan`` on device: each iteration appends
    to the KV cache at ``pos0 + i``, samples (or argmaxes) the next token,
    and feeds it back — no host in the loop.  The cache argument is donated
    so the scan updates its buffers in place.
    """
    ck = (model.cfg, scfg, steps)
    if ck not in _LOOP_CACHE:
        @functools.partial(jax.jit, donate_argnums=(1,))
        def loop(params, cache, tok0, pos0, key):
            def body(carry, i):
                with jax.named_scope("decode_step"):
                    cache_c, tok, key_c = carry
                    if scfg.temperature > 0:
                        key_c, sub = jax.random.split(key_c)
                    else:
                        sub = key_c
                    logits, cache_c = model.decode_step(params, cache_c,
                                                        tok, pos0 + i)
                    nxt = _sample(logits[:, -1, :], sub, scfg.temperature,
                                  scfg.top_k, scfg.top_p)
                    tok = nxt.astype(I32)[:, None]
                return (cache_c, tok, key_c), tok[:, 0]
            (cache, _, _), toks = jax.lax.scan(body, (cache, tok0, key),
                                               jnp.arange(steps, dtype=I32))
            # the final cache is returned so the donated input buffers have
            # an output to alias with (true in-place scan on TPU)
            return toks.T, cache
        _cache_put(_LOOP_CACHE, ck, loop)
    return _LOOP_CACHE[ck]


_CHUNK_CACHE: dict = {}


def build_prefill_chunk(model, scfg: ServeConfig, width: int):
    """Jit'd chunked attend-at-offset prefill over a (slot-pool) cache.

    (params, cache, toks (B, width), start (B,), n_valid (B,), gate (B,)) ->
    (last_logits (B, V), cache).  One ``model.prefill_chunk`` call writes
    row ``b``'s first ``n_valid[b]`` tokens at positions ``start[b] ..`` and
    attends each against the full cached history under its own causal
    frontier; the returned logits are each gated row's lane ``n_valid - 1``
    — the next-token logits after its chunk.  Rows with ``gate`` False
    compute but never write, so the rest of the pool is untouched — a long
    prompt admits as a *sequence* of these calls (start advancing by the
    chunk width) interleaved with decode bursts, and prefix-cache hits skip
    straight to their un-cached suffix.  This one executable replaced the
    dense group prefill, the paged cold prefill + page copy, the
    teacher-forced suffix loop, and the spec drafter's sync path.
    """
    ck = (model.cfg, scfg, width)
    if ck in _CHUNK_CACHE:
        return _CHUNK_CACHE[ck]

    @functools.partial(jax.jit, donate_argnums=(1,))
    def chunk(params, cache, toks, start, n_valid, gate):
        with jax.named_scope("prefill_chunk"):
            logits, cache = model.prefill_chunk(params, cache, toks, start,
                                                lengths=n_valid,
                                                write_mask=gate)
            pick = jnp.maximum(n_valid - 1, 0).astype(I32)[:, None, None]
            last = jnp.take_along_axis(logits, pick, axis=1)[:, 0]
        return last.astype(jnp.float32), cache

    return _cache_put(_CHUNK_CACHE, ck, chunk)


def generate(model, params, batch: dict, scfg: ServeConfig, max_new: int,
             key=None, tracer=None):
    """Prefill the prompt then decode ``max_new`` tokens. Returns (B, max_new).

    ``tracer``: optional ``repro.obs.trace.Tracer`` — the host decode loop
    and the prefill/scan dispatches run under spans when provided."""
    if tracer is None:
        from repro.obs.trace import NULL_TRACER
        tracer = NULL_TRACER
    key = key if key is not None else jax.random.PRNGKey(0)
    from repro.models import resolve_attn_mode
    model = resolve_attn_mode(model, scfg.attn_mode)
    B = batch["tokens"].shape[0]
    cache = model.init_cache(params, B, scfg.max_len, scfg.cache_dtype)
    if model.init_paged_cache is not None:
        # attention families prefill through the SAME chunked
        # attend-at-offset primitive the slot-pool scheduler admits with —
        # write-then-attend against the cache, so solo outputs match pooled
        # serving by construction for every cache dtype (fp2fx8 included:
        # the prompt reads quantized KV exactly like decode does)
        toks = jnp.asarray(batch["tokens"], I32)
        S = toks.shape[1]
        lens = batch.get("lengths")
        nv = (jnp.asarray(lens, I32) if lens is not None
              else jnp.full((B,), S, I32))
        pc = build_prefill_chunk(model, scfg, S)
        last, cache = pc(params, cache, toks, jnp.zeros((B,), I32), nv,
                         jnp.ones((B,), bool))
        pos = S
    else:
        logits, cache, pos = build_prefill(model)(params, cache, batch)
        last = logits[:, -1, :] if logits.ndim == 3 else logits
    # the FIRST generated token comes from the prefill logits — it must be
    # sampled too when temperature > 0 (it used to be unconditionally argmax,
    # which made every decode start greedy)
    if scfg.temperature > 0:
        key, sub = jax.random.split(key)
    else:
        sub = key
    tok = _sample(last, sub, scfg.temperature, scfg.top_k,
                  scfg.top_p).astype(I32)[:, None]

    if scfg.decode_loop == "host":
        out = [tok]
        step = build_serve_step(model, scfg)
        with tracer.span("decode_host_loop", steps=max_new - 1):
            for i in range(max_new - 1):
                if scfg.temperature > 0:
                    key, sub = jax.random.split(key)
                else:
                    sub = key
                tok, cache = step(params, cache, tok, pos + i, sub)
                out.append(tok)
        return jnp.concatenate(out, axis=1)

    if max_new <= 1:
        return tok
    with tracer.span("decode_scan", steps=max_new - 1):
        loop = build_decode_loop(model, scfg, max_new - 1)
        toks, _ = loop(params, cache, tok, pos, key)
    return jnp.concatenate([tok, toks], axis=1)
