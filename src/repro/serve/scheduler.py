"""Continuous-batching scheduler: a slot-pool KV cache serving ragged traffic.

The lockstep ``engine.generate`` path serves ONE rectangular batch: every
sequence prefills together, decodes for the same horizon, and EOS is
ignored.  Real traffic is ragged — prompts of different lengths arriving at
different times, finishing after different numbers of tokens.  This module
serves that shape of load with three pieces:

  slot pool    — the KV cache is allocated ONCE with a fixed batch (slot)
                 dimension ``n_slots`` (dense or fp2fx8 layout); per-slot
                 host state tracks ``length`` (next write position),
                 ``active``, ``prefilling``, and the remaining token
                 ``budget``.  A request occupies a slot for exactly its own
                 lifetime.
  chunked prefill — admission is host bookkeeping only; the prompt tokens
                 are pushed through ``engine.build_prefill_chunk`` (the
                 chunked attend-at-offset primitive, DESIGN.md §12) IN
                 PLACE over the slot's own cache rows: every prefilling row
                 writes up to ``ServeConfig.prefill_chunk`` tokens at its
                 own offset per call, multiple short prompts pack into one
                 bucketed call (``pack_prefill``), and long prompts span
                 several calls interleaved with decode bursts — so decode
                 never stalls longer than one chunk, and prompts longer
                 than any single bucket still serve.  Each completed row's
                 first token comes from its lane ``length - 1`` logits.
  masked burst — decode advances ALL slots in one jitted ``lax.scan`` of
                 ``decode_burst`` steps: each step writes KV at per-slot
                 positions (``cache_update_ragged``), attends under the
                 per-slot ``kv_len_mask`` (arange <= length), samples, and
                 detects EOS / budget exhaustion ON DEVICE — a finished
                 slot's ``write_mask`` goes False, so it stops mutating its
                 cache mid-burst while its neighbours keep decoding.  The
                 host only sees the emitted tokens and the final per-slot
                 state, frees finished slots, and admits queued requests
                 into them before the next burst (insertion prefill).

``ServeConfig.scheduler`` picks the admission policy:

  continuous — admit into freed slots mid-decode; EOS (``eos_id``) frees a
               slot as soon as it fires.
  lockstep   — drain the whole pool before admitting the next group and
               ignore EOS: the PR 2 rectangular baseline generalized to
               ragged prompts, using the *same* burst arithmetic, so a
               benchmark comparison isolates the scheduling policy.
  spec       — continuous admission + speculative decode bursts
               (``repro.serve.spec``, DESIGN.md §11): each burst drafts up
               to ``draft_k`` tokens per slot (ragged across the batch),
               verifies them in ONE prefill-shaped model call, keeps the
               longest accepted prefix (EOS/budget on accepted tokens
               only), and rolls the KV back — greedy outputs are
               token-for-token identical to continuous/vanilla decode.

``ServeConfig.kv_layout`` picks the cache layout (DESIGN.md §10):

  dense — one (max_len,) KV stripe per slot (the PR 3 layout): memory
          scales with the worst case whether or not a request uses it.
  paged — a global pool of fixed-size pages (``repro.serve.kvpool``) with
          per-slot block tables: admission allocates just the prompt's
          pages, decode bursts append pages on demand, exhaustion preempts
          the LATEST-ARRIVAL slot — arrival order is the priority, ties by
          rid — (requeued through normal admission with its generated
          tokens folded into the prompt — greedy continuation is
          identical), and ``prefix_cache`` shares the pages of previously
          seen prompt prefixes through a radix trie, so cached tokens skip
          prefill entirely (only the un-cached suffix goes through
          ``prefill_chunk`` calls).

Greedy (temperature == 0) outputs are token-for-token identical to a solo
``engine.generate`` run of the same prompt — padding, slot position, and
pool neighbours are all invisible to a sequence's arithmetic.  The one
exception is the MoE family: capacity-bounded expert routing dispatches
tokens batch-globally, so any *batched* serving (this scheduler AND the
rectangular lockstep engine) couples a sequence's outputs to its
neighbours' tokens — inherent to dropped-token routing, not to the
scheduler.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ServeConfig
from repro.kernels import flash_attention as fa
from repro.obs import Obs, compile_watch
from repro.obs import numerics as obs_numerics
from repro.serve import engine, kvpool

I32 = jnp.int32
PAD = -1  # emitted-token filler for slots that were idle during a burst step


@dataclasses.dataclass
class Request:
    """One generation request. ``arrival`` is in seconds after ``run()``
    starts (0 = already queued); requests must be submitted in arrival
    order.  ``deadline`` (seconds after run() start, like ``arrival``) is a
    hard TTL: a request still unfinished at its deadline is expired with a
    structured ``deadline`` failure and its slot/pages are freed within one
    burst (DESIGN.md §13)."""
    rid: int
    tokens: Any                       # (prompt_len,) int token ids
    max_new: int
    frames: Any = None                # encdec: (frontend_len, frontend_dim)
    arrival: float = 0.0
    deadline: Optional[float] = None
    # internal: a preempted request requeued mid-generation (its prompt
    # already carries the tokens generated so far; outputs are appended)
    resume: bool = False


@dataclasses.dataclass
class FailureInfo:
    """Why a request ended without running to EOS/budget (DESIGN.md §13).

    ``reason`` is one of: ``invalid`` (malformed request rejected at
    submission), ``queue_full`` (admission backpressure), ``deadline``
    (TTL expired), ``numeric_fault`` (non-finite logits survived the
    quarantine -> fp32-retry ladder), ``retries_exhausted`` (the request
    was requeued — preemption or quarantine — more than
    ``ServeConfig.max_retries`` times).  The partial tokens generated
    before the failure stay on the ``Completion``."""
    reason: str
    detail: str = ""
    retries: int = 0


@dataclasses.dataclass
class Completion:
    rid: int
    tokens: list                      # generated ids (includes EOS if hit)
    prompt_len: int
    finished_at: float                # seconds after run() start
    arrival: float = 0.0
    # per-token DISPATCH stamps (seconds after run() start): the ``now``
    # the producing prefill/burst call was started with, before its
    # device work ran — tokens of one call share one stamp.  The serving
    # benchmark keys its own re-timing on these stamps.
    token_times: list = dataclasses.field(default_factory=list)
    # per-token DELIVERY times (seconds after run() start): when the host
    # held the token, after the producing call's device-to-host copy.
    # delivered_times[0] - arrival is the TTFT; successive diffs are the
    # inter-token (TBT) gaps the chunked-prefill scheduling bounds.
    delivered_times: list = dataclasses.field(default_factory=list)
    # robustness outcome: every request terminates with a definite one —
    # ok (finished), cancelled (host cancel/shutdown, partial tokens), or
    # failure (structured reason, partial tokens)
    cancelled: bool = False
    failure: Optional[FailureInfo] = None

    @property
    def ok(self) -> bool:
        return not self.cancelled and self.failure is None

    @property
    def latency(self) -> float:
        return self.finished_at - self.arrival

    @property
    def ttft(self) -> Optional[float]:
        """Time to first token (its delivery), or None when the request
        never emitted one (failed/cancelled/zero-token) — aggregations must
        skip None rather than fold total latency into the TTFT
        percentiles."""
        if not self.delivered_times:
            return None
        return self.delivered_times[0] - self.arrival


def _bucket(n: int, lo: int = 4) -> int:
    """Next power of two >= n (>= lo) — bounds the number of distinct
    prefill compilations for ragged prompt lengths / admission group sizes."""
    b = lo
    while b < n:
        b *= 2
    return b


_BURST_CACHE: dict = {}
_SCATTER_CACHE: dict = {}
_AXES_CACHE: dict = {}


def exec_key_cfg(scfg: ServeConfig) -> ServeConfig:
    """The ServeConfig that keys the compiled serving executables (decode
    burst, prefill chunk, spec step).  Burst compilations depend on the
    decode arithmetic, not the admission policy: lockstep mode ignores EOS,
    so normalize both fields and let the schedulers share one compiled
    burst (spec honors EOS like continuous).
    The chunk-scheduling knobs are admission policy too — a prefill-chunk
    executable is keyed by its width alone, so chunked and whole-prompt
    runs share compilations — and so are the host-only robustness knobs
    (audit cadence, queue bound, retry budget): none of them changes the
    burst arithmetic."""
    eos = scfg.eos_id if scfg.scheduler in ("continuous", "spec") else None
    return dataclasses.replace(scfg, scheduler="", eos_id=eos,
                               prefill_chunk=0, pack_prefill=True,
                               audit=False, max_queue=0, max_retries=0)


TTL_NONE = 1 << 30  # "no deadline" sentinel: never decrements to zero


def build_burst(model, scfg: ServeConfig, steps: int):
    """Jit'd (params, cache, tok, lengths, active, budget, ttl, key) ->
    (emitted (steps, slots), oks (steps, slots), cache, tok, lengths,
    active, budget, ttl, key, tstats).

    One ``lax.scan`` of ``steps`` masked decode steps.  Every slot computes
    every step (uniform shapes), but only active slots write their KV
    (``write_mask``), consume budget, advance their length, or emit a token
    (idle rows emit PAD).  EOS, budget exhaustion, and TTL expiry flip
    ``active`` on device; the freed slot's cache is untouched from that
    step on.  ``ttl`` is the per-slot step allowance the host derived from
    the request's wall-clock deadline (``TTL_NONE`` = no deadline): a slot
    whose allowance runs out stops decoding MID-BURST instead of overrunning
    its deadline by up to ``steps`` tokens.  ``oks`` is the per-step numeric
    health bit — False where an ACTIVE slot's next-token logits went
    non-finite (the host quarantines that slot; idle rows report True) —
    the cheap all-finite reduction the robustness layer keys on
    (DESIGN.md §13).  ``tstats`` is the per-burst hybrid-format telemetry
    dict (DESIGN.md §15): empty when ``scfg.telemetry`` is off (the flag is
    part of the compile key), else the softmax-input exponent range over
    the burst plus fp2fx8 scale/saturation stats of the final cache —
    computed in-jit at the cost of a few row reductions per step.
    """
    kcfg = exec_key_cfg(scfg)
    eos = kcfg.eos_id
    ck = (model.cfg, kcfg, steps)
    if ck in _BURST_CACHE:
        return _BURST_CACHE[ck]

    @functools.partial(jax.jit, donate_argnums=(1,))
    def burst(params, cache, tok, lengths, active, budget, ttl, key):
        def body(carry, _):
            cache_c, tok_c, len_c, act_c, bud_c, ttl_c, key_c = carry
            if scfg.temperature > 0:
                key_c, sub = jax.random.split(key_c)
            else:
                sub = key_c
            with jax.named_scope("burst_step"):
                logits, cache_c = model.decode_step(params, cache_c, tok_c,
                                                    len_c, write_mask=act_c)
            last = logits[:, -1, :]
            ok = jnp.isfinite(last).all(-1) | ~act_c
            nxt = engine._sample(last, sub, scfg.temperature,
                                 scfg.top_k, scfg.top_p).astype(I32)
            emit = jnp.where(act_c, nxt, PAD)
            bud_c = bud_c - act_c.astype(I32)
            len_c = len_c + act_c.astype(I32)
            ttl_c = ttl_c - act_c.astype(I32)
            alive = act_c & (bud_c > 0) & (ttl_c > 0)
            if eos is not None:
                alive = alive & (nxt != eos)
            tok_c = jnp.where(act_c, nxt, tok_c[:, 0])[:, None]
            ys = (emit, ok)
            if kcfg.telemetry:
                ys = ys + (obs_numerics.logit_stats(last, act_c),)
            return (cache_c, tok_c, len_c, alive, bud_c, ttl_c, key_c), ys

        carry, ys = jax.lax.scan(
            body, (cache, tok, lengths, active, budget, ttl, key), None,
            length=steps)
        cache, tok, lengths, active, budget, ttl, key = carry
        if kcfg.telemetry:
            emits, oks, zs = ys
            tstats = dict(obs_numerics.reduce_logit_stats(zs),
                          **obs_numerics.format_stats(cache))
        else:
            emits, oks = ys
            tstats = {}
        # returning the cache gives the donated input buffers an output to
        # alias with (true in-place burst on TPU)
        return emits, oks, cache, tok, lengths, active, budget, ttl, key, \
            tstats

    return engine._cache_put(_BURST_CACHE, ck, burst)


def _cache_batch_axes(model, params, max_len, dtype):
    """Per-leaf slot (batch) axis of the serving cache, discovered by
    diffing the abstract shapes at two batch sizes — layer-stacked leaves
    carry the batch on axis 1, the encoder memory on axis 0, etc."""
    ck = (model.cfg, max_len, str(dtype))
    if ck in _AXES_CACHE:
        return _AXES_CACHE[ck]
    s1 = jax.eval_shape(
        functools.partial(model.init_cache, params, 1, max_len, dtype))
    s2 = jax.eval_shape(
        functools.partial(model.init_cache, params, 2, max_len, dtype))

    def ax(a, b):
        for i, (x, y) in enumerate(zip(a.shape, b.shape)):
            if x != y:
                return i
        raise ValueError(f"no batch axis in cache leaf {a.shape}")

    return engine._cache_put(_AXES_CACHE, ck, jax.tree.map(ax, s1, s2))


def build_scatter(model, axes, max_len, dtype):
    """Jit'd (pool, new, slot_idx) -> pool with ``new``'s first
    ``len(slot_idx)`` batch rows written into the pool's slots.  The pool is
    donated — admission rewrites the slot rows in place."""
    ck = (model.cfg, max_len, str(dtype))
    if ck in _SCATTER_CACHE:
        return _SCATTER_CACHE[ck]

    @functools.partial(jax.jit, donate_argnums=(0,))
    def scatter(pool, new, slot_idx):
        # slot_idx is always padded to n_slots rows (duplicates carry the
        # same payload, so repeated writes are benign) — ONE compilation
        # regardless of how many slots an admission actually fills
        def s(p, n, ax):
            pm = jnp.moveaxis(p, ax, 0)
            nm = jnp.moveaxis(n, ax, 0)
            pm = pm.at[slot_idx].set(nm.astype(pm.dtype))
            return jnp.moveaxis(pm, 0, ax)

        return jax.tree.map(s, pool, new, axes)

    return engine._cache_put(_SCATTER_CACHE, ck, scatter)


_ENCODE_CACHE: dict = {}


def build_encode(model):
    """Jit'd (params, frames) -> encoder memory — chunked encdec admission
    runs the encoder once per admitted group and installs the memory rows
    into the slot cache before any ``prefill_chunk`` call (one compile per
    bucketed group shape)."""
    ck = model.cfg
    if ck in _ENCODE_CACHE:
        return _ENCODE_CACHE[ck]
    return engine._cache_put(
        _ENCODE_CACHE, ck, jax.jit(lambda p, fr: model.encode(p, fr)))


# legacy ``stats`` keys, now counters/gauges in the Obs metrics registry
# (the ``SlotPoolEngine.stats`` property reconstructs the old dict) — the
# README "Observability" section documents the key -> metric mapping
_STAT_COUNTERS = (
    "admitted", "bursts", "prefills", "burst_steps", "slot_steps_active",
    "tokens_emitted", "prompt_tokens", "prefill_tokens", "cached_tokens",
    "prefix_hits", "preemptions", "model_calls", "spec_steps",
    "draft_tokens", "accepted_tokens", "rejected", "expired", "cancelled",
    "quarantines", "fp32_retries", "failures", "stragglers", "audits",
    "verify_page_blocks_live", "verify_page_blocks_total")
_STAT_GAUGES = ("peak_active", "pages_peak")


class SlotPoolEngine:
    """Host-side scheduler around the slot-pool cache and the jitted burst.

    Pool state lives as numpy mirrors (tiny vectors) updated from each
    burst's outputs; the KV cache itself never leaves the device and is
    donated through every burst/scatter call.
    """

    def __init__(self, model, params, scfg: ServeConfig, key=None,
                 draft=None, chaos=None, obs: Optional[Obs] = None):
        from repro.distributed.fault_tolerance import StragglerMonitor
        from repro.models import resolve_attn_mode
        self.model = resolve_attn_mode(model, scfg.attn_mode)
        self.params = params
        self.scfg = scfg
        # observability bundle (DESIGN.md §15): a fresh disabled-tracer Obs
        # per engine by default, so benchmark engines never share counters
        self.obs = obs if obs is not None else Obs()
        self.key = key if key is not None else jax.random.PRNGKey(0)
        n = scfg.n_slots
        if scfg.max_queue < 0:
            raise ValueError("max_queue must be >= 0 (0 = unbounded)")
        if scfg.max_retries < 0:
            raise ValueError("max_retries must be >= 0")
        # fault-injection harness (repro/serve/chaos.py): consulted at the
        # named injection points when attached; None in production
        self.chaos = chaos
        if scfg.scheduler not in ("continuous", "lockstep", "spec"):
            raise ValueError(f"unknown scheduler {scfg.scheduler!r}")
        if scfg.kv_layout not in ("dense", "paged"):
            raise ValueError(f"unknown kv_layout {scfg.kv_layout!r}")
        self.spec = scfg.scheduler == "spec"
        self.drafter = None
        if self.spec:
            if scfg.temperature > 0:
                raise ValueError(
                    "scheduler='spec' is greedy-only (temperature == 0): "
                    "sampled speculative acceptance needs distribution-"
                    "level rejection sampling, not the top-k/top-p filters")
            if self.model.init_paged_cache is None:
                raise ValueError(
                    "scheduler='spec' needs an attention-family model "
                    "(dense/moe/vlm): SSM/hybrid/encdec state has no O(1) "
                    "rollback, so those families serve non-speculatively")
            if scfg.draft_k < 1:
                raise ValueError("draft_k must be >= 1")
            from repro.serve import spec as spec_mod
            self.drafter = spec_mod.make_drafter(scfg, self.model.cfg,
                                                 draft=draft)
        self.paged = scfg.kv_layout == "paged"
        self.trie = None
        if self.paged:
            if self.model.init_paged_cache is None:
                raise ValueError(
                    "kv_layout='paged' needs an attention-family model "
                    "(dense/moe/vlm); SSM/hybrid/encdec serve dense")
            if scfg.page_size < 1:
                raise ValueError("page_size must be >= 1")
            self.n_blocks = -(-scfg.max_len // scfg.page_size)
            n_pages = scfg.n_pages or n * self.n_blocks
            if n_pages < self.n_blocks:
                raise ValueError(
                    f"n_pages={n_pages} cannot hold one max_len={scfg.max_len}"
                    f" request ({self.n_blocks} pages of {scfg.page_size})")
            self.pool = kvpool.PagePool(n_pages)
            if scfg.prefix_cache:
                self.trie = kvpool.RadixTrie(self.pool, scfg.page_size)
            self.slot_pages: list[list] = [[] for _ in range(n)]
            self.block_tables = np.zeros((n, self.n_blocks), np.int32)
            self.cache = dict(
                self.model.init_paged_cache(params, n_pages, scfg.page_size,
                                            scfg.cache_dtype),
                block_tables=jnp.asarray(self.block_tables))
        else:
            if scfg.prefix_cache:
                raise ValueError("prefix_cache requires kv_layout='paged'")
            self.cache = self.model.init_cache(params, n, scfg.max_len,
                                               scfg.cache_dtype)
        if scfg.prefill_chunk < 0:
            raise ValueError("prefill_chunk must be >= 0 (0 = whole prompt)")
        self.lengths = np.zeros(n, np.int32)
        self.active = np.zeros(n, bool)
        self.prefilling = np.zeros(n, bool)   # admitted, prompt not yet fed
        self.budget = np.zeros(n, np.int32)
        self.last_tok = np.zeros(n, np.int32)
        self.slot_rid: list[Optional[int]] = [None] * n
        # the prompt a slot was admitted with (a preempted resume carries
        # its generated tokens folded in) — chunk admission slices pending
        # tokens out of it, and trie publication reads it at completion
        self.slot_prompt: list[Optional[np.ndarray]] = [None] * n
        self.outputs: dict[int, list] = {}
        self.out_times: dict[int, list] = {}  # per-token dispatch stamps
        self.deliv_times: dict[int, list] = {}  # per-token delivery times
        # request-life events (DESIGN.md §15), on the tracer's clock: when
        # each rid's current wait in the queue began (a requeue), and when
        # its current admission began with whether it is a resume
        self._queued_at: dict[int, float] = {}
        self._admitted_at: dict[int, tuple] = {}
        self.requests: dict[int, Request] = {}
        self.completions: dict[int, Completion] = {}
        self._queue: deque = deque()   # arrived, waiting (bounded)
        self._pending: deque = deque()  # submitted, not yet arrived
        # chunk prefill writes attention rows in place (the kv_index <=
        # position mask hides a previous occupant's stale KV), but
        # recurrent-state families CONTINUE from the slot's stored state,
        # so their admission scatters fresh zero rows first
        self._needs_reset = self.model.init_paged_cache is None
        self._encode = (build_encode(self.model)
                        if self.model.encode is not None else None)
        # the scatter doubles as the dense quarantine scrub, so attention
        # families build it lazily on the first fault (_scrub_dense_slot)
        self._axes = self._scatter = None
        if not self.paged and self._needs_reset:
            self._axes = _cache_batch_axes(self.model, params, scfg.max_len,
                                           scfg.cache_dtype)
            self._scatter = build_scatter(self.model, self._axes,
                                          scfg.max_len, scfg.cache_dtype)
        if self.spec:
            from repro.serve import spec as spec_mod
            self._spec_step = spec_mod.build_spec_step(
                self.model, exec_key_cfg(scfg), scfg.draft_k)
        else:
            self._burst = build_burst(self.model, scfg,
                                      max(1, scfg.decode_burst))
        self._eos = (scfg.eos_id
                     if scfg.scheduler in ("continuous", "spec") else None)
        # --- robustness state (DESIGN.md §13) ---
        self.retries: dict[int, int] = {}        # requeues per rid
        self.numeric_faults: dict[int, int] = {}  # quarantines per rid
        self._cancels: set = set()               # rids to cancel next check
        # page lists held by parties other than slots/trie (the chaos
        # harness's pool squeeze) — folded into audit recomputation
        self._extra_holders: list = []
        # burst wall-time EMA + outlier flagging; also the per-step time
        # estimate behind the device-side deadline TTL
        self.straggler = StragglerMonitor()
        self._step_ema = 0.0
        self._t0: Optional[float] = None         # run() epoch, for shutdown
        self._t0_tr: Optional[float] = None      # the same on tracer clock
        # the fp32 fallback engine must fail structurally, never recurse
        self._allow_fp32_retry = True
        self._zero_pages = None                  # lazy jitted page scrub
        # --- metrics (DESIGN.md §15) ---
        # the legacy ``stats`` dict is now a read-only view over the
        # registry (see the ``stats`` property); every counter/gauge lives
        # under serve.<key> with scheduler+family labels
        self._labels = dict(scheduler=scfg.scheduler,
                            family=self.model.cfg.family)
        reg = self.obs.metrics
        self._counters = {
            k: reg.counter(f"serve.{k}", **self._labels)
            for k in _STAT_COUNTERS}
        self._gauges = {
            k: reg.gauge(f"serve.{k}", **self._labels)
            for k in _STAT_GAUGES + ("queue_depth", "slot_occupancy",
                                     "pages_in_use")}
        self._hists = {
            k: reg.histogram(f"serve.{k}", **self._labels)
            for k in ("ttft_s", "tbt_s", "burst_wall_s")}
        # fp→fx convert volume at the §14 boundaries: elements quantized
        # per KV-cache token write (k + v rows), counted host-side
        self._quantized = scfg.cache_dtype == "fp2fx8"
        if self._quantized:
            cfg = self.model.cfg
            heads = getattr(cfg, "n_kv_heads", None) or getattr(
                cfg, "n_heads", 1)
            self._converts_per_tok = (2 * cfg.n_layers * heads
                                      * getattr(cfg, "d_head", 1))
            self.obs.numerics.kv_int8_total = obs_numerics.int8_size(
                self.cache)
        else:
            self._converts_per_tok = 0

    # -- metrics helpers (DESIGN.md §15) --------------------------------

    def _count(self, key: str, n: int = 1) -> None:
        self._counters[key].inc(n)

    def _peak(self, key: str, v: float) -> None:
        self._gauges[key].track_max(v)

    @property
    def stats(self) -> dict:
        """Back-compat view: the legacy ad-hoc stats dict, reconstructed
        read-only from the metrics registry."""
        d = {}
        for k in ("admitted", "bursts", "prefills", "burst_steps",
                  "slot_steps_active"):
            d[k] = self._counters[k].value
        d["peak_active"] = int(self._gauges["peak_active"].value)
        for k in ("tokens_emitted", "prompt_tokens", "prefill_tokens",
                  "cached_tokens", "prefix_hits", "preemptions"):
            d[k] = self._counters[k].value
        d["pages_peak"] = int(self._gauges["pages_peak"].value)
        for k in ("model_calls", "spec_steps", "draft_tokens",
                  "accepted_tokens", "rejected", "expired", "cancelled",
                  "quarantines", "fp32_retries", "failures", "stragglers",
                  "audits", "verify_page_blocks_live",
                  "verify_page_blocks_total"):
            d[k] = self._counters[k].value
        return d

    def _record_completion(self, c: Completion) -> None:
        """Latency histograms at completion time — TTFT (skipping None)
        and per-gap TBT, both from delivery times — so metric aggregates
        reconcile with post-hoc numbers computed from the Completion
        records by construction."""
        t = c.ttft
        if t is not None:
            self._hists["ttft_s"].observe(t)
        tt = c.delivered_times
        for i in range(1, len(tt)):
            self._hists["tbt_s"].observe(tt[i] - tt[i - 1])

    def _count_converts(self, n_tokens: int) -> None:
        """fp→fx convert volume for ``n_tokens`` KV-cache token writes
        (the §14 quantize boundary; no-op for unquantized caches)."""
        if self._quantized and n_tokens > 0:
            self.obs.numerics.add_converts(n_tokens * self._converts_per_tok)

    # -- warmup --------------------------------------------------------

    def prewarm(self, max_prompt_len: int, frontend=None) -> None:
        """Compile every executable a run can hit — the burst and the
        prefill-chunk call at every width admission can bucket to (plus the
        encoder + zero-row scatter for recurrent-state families).

        Admission shapes depend on arrival timing (how many requests are
        queued when slots free up), so without this a *timed* run may pay a
        jit trace mid-flight.  Chunk calls always run all ``n_slots`` rows
        and are keyed by width alone, so the warm grid is one-dimensional —
        far fewer compilations than the old (group, prompt) bucket grid.
        ``frontend``: (frontend_len, frontend_dim) for encdec models.
        """
        scfg = self.scfg
        n = scfg.n_slots
        tracer = self.obs.tracer
        with compile_watch(tracer, enabled=tracer.enabled), \
                tracer.span("prewarm", max_prompt_len=max_prompt_len):
            cap = min(_bucket(max_prompt_len), scfg.max_len)
            c0 = scfg.prefill_chunk
            widths, b = set(), 4
            while b < cap:
                widths.add(min(c0, b) if c0 > 0 else b)
                b *= 2
            widths.add(min(c0, cap) if c0 > 0 else cap)
            if frontend is not None and self._encode is not None:
                g, g_top = 1, _bucket(n, lo=1)
                while True:
                    jax.block_until_ready(self._encode(
                        self.params, jnp.zeros((g,) + tuple(frontend))))
                    if g >= g_top:
                        break
                    g *= 2
            if not self.paged and self._needs_reset:
                fresh = self.model.init_cache(self.params, n, scfg.max_len,
                                              scfg.cache_dtype)
                self.cache = self._scatter(self.cache, fresh,
                                           jnp.arange(n, dtype=I32))
            for w in sorted(widths):
                pc = engine.build_prefill_chunk(
                    self.model, exec_key_cfg(scfg), w)
                args = (self.params, self.cache, jnp.zeros((n, w), I32),
                        jnp.zeros(n, I32), jnp.ones(n, I32),
                        jnp.zeros(n, bool))
                # gate all-False: every row computes but none writes, so the
                # live pool is untouched — no scratch/restore dance needed
                out, self.cache = pc(*args)
                jax.block_until_ready(out)
            if self.spec:
                K = self.scfg.draft_k
                args = (self.params, self.cache, jnp.zeros((n, 1), I32),
                        jnp.zeros((n, K), I32), jnp.zeros(n, I32),
                        jnp.zeros(n, I32), jnp.zeros(n, bool),
                        jnp.zeros(n, I32))
                out = self._spec_step(*args)
                self.cache = out[1]
            else:
                args = (self.params, self.cache, jnp.zeros((n, 1), I32),
                        jnp.zeros(n, I32), jnp.zeros(n, bool),
                        jnp.zeros(n, I32), jnp.full(n, TTL_NONE, I32),
                        jax.random.PRNGKey(0))
                out = self._burst(*args)
                self.cache = out[2]
            jax.block_until_ready(out[0])

    # -- admission -----------------------------------------------------

    def _first_token(self, last):
        """Sample (temperature > 0) or argmax the FIRST generated token from
        the (B, V) next-token logits a completed prefill returned — same
        contract as ``engine.generate``."""
        if self.scfg.temperature > 0:
            self.key, sub = jax.random.split(self.key)
            return engine._sample(last, sub, self.scfg.temperature,
                                  self.scfg.top_k, self.scfg.top_p)
        return jnp.argmax(last, -1)

    def _register(self, r: Request) -> None:
        """First sighting of a request: create its output/trace records (a
        resume keeps the ORIGINAL request — its prompt, arrival, and
        deadline — so preemption folding and TTL stay anchored to it)."""
        if r.rid not in self.requests:
            self.requests[r.rid] = r
            self.outputs[r.rid] = []
            self.out_times[r.rid] = []
            self.deliv_times[r.rid] = []

    def _start_prefill(self, s: int, r: Request, start: int) -> None:
        """Host bookkeeping that puts ``r`` into slot ``s`` in the
        ``prefilling`` state with ``start`` tokens already cached (prefix
        hits); ``_prefill_step`` feeds the rest chunk by chunk."""
        if not r.resume:
            self._register(r)
            self._count("admitted")
        tracer = self.obs.tracer
        t = tracer.now()
        if self._t0_tr is not None:
            # queued from arrival, or from the requeue for a resume
            tq = self._queued_at.pop(r.rid, self._t0_tr + r.arrival)
            tracer.complete("request_queued", tq, t, rid=r.rid,
                            resume=r.resume)
        self._admitted_at[r.rid] = (t, r.resume)
        self.slot_rid[s] = r.rid
        self.slot_prompt[s] = np.asarray(r.tokens, np.int32)
        self.lengths[s] = start
        self.active[s] = False
        self.prefilling[s] = True
        self.budget[s] = r.max_new
        self._drafter_reset(s)
        self._count("prompt_tokens", len(r.tokens))
        self._count("prefill_tokens", len(r.tokens) - start)
        self._count_converts(len(r.tokens) - start)

    def admit(self, reqs: list[Request], now: float) -> None:
        """Admit ``reqs`` into free slots — host bookkeeping only: per-slot
        prompt/offset state, page allocation + prefix-cache matching
        (paged), and a fresh zero row + encoder memory for recurrent-state
        families.  The prompts are then fed through chunked
        ``_prefill_step`` calls interleaved with decode bursts; a row whose
        request completes on its first token (EOS or ``max_new == 1``)
        frees its slot at that point."""
        if not reqs:
            return
        with self.obs.tracer.span("admit", n=len(reqs),
                                  rids=[r.rid for r in reqs]):
            free = [s for s in range(self.scfg.n_slots)
                    if self.slot_rid[s] is None]
            assert len(reqs) <= len(free), \
                "admitting more requests than slots"
            if self.paged:
                self._admit_paged(reqs, free)
            else:
                self._admit_dense(reqs, free)

    def _admit_dense(self, reqs, free):
        scfg = self.scfg
        n = scfg.n_slots
        if self._needs_reset:
            # SSM/hybrid/encdec chunk-prefill through gated decode steps,
            # which CONTINUE from the slot's stored recurrent state — wipe
            # the admitted rows (and install encoder memory) before the
            # first chunk.  Attention rows skip this: the kv_index <=
            # position mask already hides a previous occupant's stale KV.
            fresh = self.model.init_cache(self.params, n, scfg.max_len,
                                          scfg.cache_dtype)
            if reqs[0].frames is not None:
                if any(r.frames is None for r in reqs):
                    raise ValueError("mixed group: some requests carry "
                                     "encoder frames and some do not")
                g = _bucket(len(reqs), lo=1)
                fr = np.stack([np.asarray(r.frames) for r in reqs])
                fr = np.concatenate(
                    [fr, np.repeat(fr[:1], g - len(reqs), 0)], 0)
                mem = np.asarray(self._encode(self.params, jnp.asarray(fr)))
                memp = np.array(fresh["memory"])
                memp[:len(reqs)] = mem[:len(reqs)].astype(memp.dtype)
                fresh = dict(fresh, memory=jnp.asarray(memp))
            # row j -> slot free[j]; pad both index vectors to n_slots by
            # repeating the LAST pair (duplicate writes of identical rows
            # are benign) so the jitted scatter compiles exactly once
            order = np.arange(n, dtype=np.int32)
            order[len(reqs):] = len(reqs) - 1
            slots = np.array(free[:len(reqs)]
                             + [free[len(reqs) - 1]] * (n - len(reqs)),
                             np.int32)
            picked = jax.tree.map(
                lambda leaf, ax: jnp.take(leaf, jnp.asarray(order), axis=ax),
                fresh, self._axes)
            self.cache = self._scatter(self.cache, picked,
                                       jnp.asarray(slots))
        for j, r in enumerate(reqs):
            self._start_prefill(free[j], r, 0)

    # -- paged admission (page allocation + prefix cache) --------------

    def _alloc_pages(self, n: int) -> Optional[list]:
        """``n`` pages from the pool, evicting prefix-cache LRU leaves on
        shortage.  None when the demand cannot be met even after eviction
        (the caller requeues or preempts)."""
        if n <= 0:
            return []
        pages = self.pool.alloc(n)
        if pages is None and self.trie is not None:
            with self.obs.tracer.span("evict",
                                      short=n - self.pool.free_pages):
                self.trie.evict(n - self.pool.free_pages)
            pages = self.pool.alloc(n)
        return pages

    def _drafter_reset(self, s: int) -> None:
        """A slot changed owner: wipe any drafter state tied to it (the
        model drafter's synced-length watermark; the n-gram drafter is
        stateless)."""
        if self.drafter is not None:
            self.drafter.reset_slot(s)

    def _release_slot_pages(self, s: int) -> None:
        for p in self.slot_pages[s]:
            self.pool.decref(p)
        self.slot_pages[s] = []
        self.block_tables[s, :] = 0

    def _admit_paged(self, reqs, free):
        """Paged admission: allocate each prompt's pages (reusing cached
        prefix pages when the radix trie matches) and install the block
        table — no model call here.  Cold rows start their chunked prefill
        at offset 0, hit rows at the matched length: the cached tokens
        never touch the model, and the un-cached suffix flows through the
        same ``prefill_chunk`` calls as everything else.
        """
        ps = self.scfg.page_size
        plans, leftover = [], []
        for i, r in enumerate(reqs):
            toks = np.asarray(r.tokens, np.int32)
            matched_pages: list = []
            matched = 0
            if self.trie is not None:
                # match on tokens[:-1]: at least one suffix token always
                # remains to produce the first generated token's logits
                matched_pages, matched = self.trie.match(toks[:-1].tolist())
                for p in matched_pages:
                    # pin BEFORE _alloc_pages: its trie eviction would
                    # otherwise free the just-matched (trie-only) pages and
                    # could hand them straight back as this request's tail
                    self.pool.incref(p)
            need = -(-len(toks) // ps)
            new = self._alloc_pages(need - len(matched_pages))
            if new is None and matched_pages:
                # under pressure the pinned prefix may be the only memory
                # left: drop the match and admit cold, letting eviction
                # reclaim it (correct, just uncached)
                for p in matched_pages:
                    self.pool.decref(p)
                matched_pages, matched = [], 0
                new = self._alloc_pages(need)
            if new is None:  # page exhaustion: try again after some free up
                leftover = reqs[i:]
                break
            plans.append((r, matched, list(matched_pages) + new))
        if leftover:
            self._queue.extendleft(reversed(leftover))
        for j, (r, matched, pages) in enumerate(plans):
            s = free[j]
            self._start_prefill(s, r, matched)
            # prefill_chunk writes through the block table: install it (and
            # the page ownership) before the first chunk runs
            self.slot_pages[s] = list(pages)
            self.block_tables[s, :] = 0
            self.block_tables[s, :len(pages)] = pages
            self._count("cached_tokens", matched)
            if matched:
                self._count("prefix_hits")
        self._peak("pages_peak", self.pool.pages_in_use)

    # -- chunked prefill ------------------------------------------------

    def _prefill_step(self, now: float) -> None:
        """Feed every prefilling slot its next chunk through ONE
        ``engine.build_prefill_chunk`` call (packed; ``pack_prefill=False``
        feeds only the earliest-arrival slot — an ablation knob).  The call
        width is ``prefill_chunk`` (0 = whole remaining prompt) capped to
        the bucketed longest remainder; rows that finish inside this chunk
        take their first generated token from the returned last-lane logits
        and flip to ``active`` (or free immediately on EOS / budget 1)."""
        scfg = self.scfg
        n = scfg.n_slots
        rows = [s for s in range(n) if self.prefilling[s]]
        if not rows:
            return
        tracer = self.obs.tracer
        with tracer.span("prefill_pack"):
            if not scfg.pack_prefill:
                rows = [min(rows, key=lambda s: (
                    self.requests[self.slot_rid[s]].arrival,
                    self.slot_rid[s]))]
            rem = {s: len(self.slot_prompt[s]) - int(self.lengths[s])
                   for s in rows}
            cap = min(_bucket(max(rem.values())), scfg.max_len)
            width = min(scfg.prefill_chunk, cap) if scfg.prefill_chunk > 0 \
                else cap
            toks = np.zeros((n, width), np.int32)
            n_valid = np.ones(n, np.int32)
            gate = np.zeros(n, bool)
            for s in rows:
                part = self.slot_prompt[s][int(self.lengths[s]):
                                           int(self.lengths[s]) + width]
                toks[s, :len(part)] = part
                n_valid[s] = len(part)
                gate[s] = True
            if self.paged:
                self.cache["block_tables"] = jnp.asarray(self.block_tables)
        with tracer.span("prefill_chunk", width=width, rows=len(rows)):
            if self.paged:
                self._count_verify_blocks(width)
            pc = engine.build_prefill_chunk(self.model,
                                            exec_key_cfg(scfg), width)
            # hand the call a copy of the host mirror: on the CPU backend
            # jnp.asarray may alias a numpy buffer, and self.lengths is
            # advanced below while the dispatched call can still read it
            last, self.cache = pc(self.params, self.cache,
                                  jnp.asarray(toks),
                                  jnp.asarray(self.lengths.copy()),
                                  jnp.asarray(n_valid), jnp.asarray(gate))
        with tracer.span("prefill_finish"):
            self._count("prefills")
            for s in rows:
                self.lengths[s] += min(rem[s], width)
            # numeric health: every gated row's last-lane logits must be
            # finite — a poisoned KV page / fp2fx8 scale row surfaces here
            # before the slot ever decodes, and the quarantine ladder
            # takes it
            finite = np.asarray(jnp.isfinite(last).all(-1))
            bad = [s for s in rows if not finite[s]]
            for s in bad:
                self._quarantine(s, now, where="prefill")
            fin = [s for s in rows if rem[s] <= width and s not in bad]
            if fin:
                tok0 = np.asarray(self._first_token(last), np.int32)
                held, t_held = self._now(), tracer.now()
                for s in fin:
                    rid = self.slot_rid[s]
                    t_adm, resume = self._admitted_at.pop(rid)
                    tracer.complete("request_prefill", t_adm, t_held,
                                    rid=rid, resume=resume)
                    self._finish_prefill(s, int(tok0[s]), now, held)
            self._peak("peak_active", int(self.active.sum()))
            self._audit_check()

    def _count_verify_blocks(self, width: int) -> None:
        """Page blocks the chunk call's paged verify kernel computes
        (``verify_page_blocks_live``) and all its grid holds
        (``verify_page_blocks_total``), per (slot, lane block) of one
        layer's call: planned here from the slots' lengths with the helpers
        the kernel's wrapper plans with from its mask (every lane ``t`` of
        slot ``b`` sees ``kv_index <= lengths[b] + t``).  Both are counters
        of the metrics registry and a tracer counter event; past the VMEM
        cap the per-split grid visits every page, so live == total."""
        cfg, ps = self.model.cfg, self.scfg.page_size
        plan = fa.paged_verify_plan(
            cfg.n_heads, cfg.n_kv_heads, cfg.d_head, ps, self.n_blocks, width,
            self.cache["blocks"]["k"].dtype, self._quantized)
        front = np.minimum(self.lengths[:, None] + np.arange(width),
                           self.n_blocks * ps - 1)
        pages = fa.verify_live_pages(front, plan.lanes, ps)
        total = pages.size * plan.nblk
        live = (int((-(-pages // plan.P)).sum())
                if plan.vmem <= fa.PAGED_VMEM_CAP else total)
        self._count("verify_page_blocks_live", live)
        self._count("verify_page_blocks_total", total)
        self.obs.tracer.counter("verify_page_blocks", live=live, total=total)

    def _finish_prefill(self, s: int, tok0: int, now: float,
                        held: float) -> None:
        """Slot ``s``'s whole prompt is cached and its first generated
        token is in hand (since ``held``, on the run clock): publish the
        prompt's full pages to the prefix cache, emit the token, and either
        activate the slot for decode or free it (EOS / budget exhausted on
        the very first token)."""
        self.prefilling[s] = False
        rid = self.slot_rid[s]
        if self.trie is not None:
            # publish the admitted prompt's FULL pages (partial tail pages
            # are never shared — decode writes into them); insert before
            # any done-row release so adopted pages survive it
            ptoks = self.slot_prompt[s]
            nfull = len(ptoks) // self.scfg.page_size
            if nfull:
                self.trie.insert(
                    [int(t) for t in ptoks[:nfull * self.scfg.page_size]],
                    self.slot_pages[s][:nfull])
            self._peak("pages_peak", self.pool.pages_in_use)
        self.outputs[rid].append(tok0)
        self.out_times[rid].append(now)
        self.deliv_times[rid].append(held)
        self._count("tokens_emitted")
        done = (self.budget[s] <= 1
                or (self._eos is not None and tok0 == self._eos))
        if done:
            self._finish(rid, now)
            self.slot_rid[s] = None
            self.slot_prompt[s] = None
            if self.paged:
                self._release_slot_pages(s)
            return
        self.budget[s] -= 1
        self.last_tok[s] = tok0
        self.active[s] = True

    def _now(self) -> float:
        """Seconds since run() started (0 before/outside a run)."""
        return time.perf_counter() - self._t0 if self._t0 is not None else 0.0

    def _free_slot(self, s: int) -> None:
        """Detach slot ``s`` from its request and return its resources."""
        self.active[s] = False
        self.prefilling[s] = False
        self.slot_rid[s] = None
        self.slot_prompt[s] = None
        if self.paged:
            self._release_slot_pages(s)

    def _fail(self, rid: int, reason: str, now: float,
              detail: str = "") -> None:
        """Terminate ``rid`` with a structured failure — the partial tokens
        generated so far stay on the Completion (DESIGN.md §13)."""
        r = self.requests[rid]
        self.completions[rid] = Completion(
            rid=rid, tokens=self.outputs.get(rid, []),
            prompt_len=len(r.tokens), finished_at=now, arrival=r.arrival,
            token_times=list(self.out_times.get(rid, [])),
            delivered_times=list(self.deliv_times.get(rid, [])),
            failure=FailureInfo(reason=reason, detail=detail,
                                retries=self.retries.get(rid, 0)))
        self._count("failures")
        self._record_completion(self.completions[rid])

    def _requeue(self, s: int, now: float) -> bool:
        """Push slot ``s``'s request back to the queue FRONT with the
        tokens generated so far folded into the prompt (the preemption /
        quarantine resume path — greedy continuation is token-for-token
        identical).  Bounded: a request requeued more than ``max_retries``
        times fails structurally instead, converting pressure livelock
        into a definite outcome.  The slot itself is NOT freed here."""
        rid = self.slot_rid[s]
        nret = self.retries.get(rid, 0) + 1
        self.retries[rid] = nret
        if nret > self.scfg.max_retries:
            self._fail(rid, "retries_exhausted", now,
                       detail=f"requeued {nret} times")
            return False
        orig = self.requests[rid]
        done = self.outputs[rid]
        toks = np.concatenate([np.asarray(orig.tokens, np.int32),
                               np.asarray(done, np.int32)])
        # remaining budget from the HOST trace, not the device budget
        # mirror: a quarantined slot's garbage steps already burned device
        # budget the request never received tokens for
        self._queue.appendleft(Request(
            rid=rid, tokens=toks, max_new=orig.max_new - len(done),
            frames=orig.frames, arrival=orig.arrival,
            deadline=orig.deadline, resume=True))
        self._queued_at[rid] = self.obs.tracer.now()
        return True

    def _preempt_latest(self) -> bool:
        """Page exhaustion mid-decode: free the latest-arrival occupied
        slot (ties by rid) — decoding or mid-prefill — and requeue its
        request through the normal admission path with the tokens generated
        so far folded into the prompt (greedy continuation is
        token-for-token identical); a request past its retry budget fails
        structurally instead.  Returns True if a slot was freed."""
        cands = [s for s in range(self.scfg.n_slots)
                 if self.active[s] or self.prefilling[s]]
        if not cands:
            return False
        s = max(cands, key=lambda c: (self.requests[self.slot_rid[c]].arrival,
                                      self.slot_rid[c]))
        self.obs.tracer.instant("preempt", rid=self.slot_rid[s], slot=s)
        self._requeue(s, self._now())
        self._free_slot(s)
        self._count("preemptions")
        self._audit_check()
        return True

    def _ensure_burst_pages(self, steps: int) -> None:
        """Grow every active slot's block table to cover its next ``steps``
        decode writes.  Exhaustion evicts prefix-cache LRU pages first
        (inside ``_alloc_pages``), then preempts the latest-arrival slot
        and retries — the freed pages unblock the rest of the pool."""
        while True:
            short = False
            for s in range(self.scfg.n_slots):
                if not self.active[s]:
                    continue
                horizon = int(self.lengths[s]) + min(steps,
                                                     int(self.budget[s]))
                nb_need = min(-(-horizon // self.scfg.page_size),
                              self.n_blocks)
                have = len(self.slot_pages[s])
                new = self._alloc_pages(nb_need - have)
                if new is None:
                    short = True
                    break
                if new:
                    self.block_tables[s, have:have + len(new)] = new
                    self.slot_pages[s].extend(new)
            if not short:
                self._peak("pages_peak", self.pool.pages_in_use)
                return
            if not self._preempt_latest():
                return

    def _finish(self, rid: int, now: float) -> None:
        r = self.requests[rid]
        self.completions[rid] = Completion(
            rid=rid, tokens=self.outputs[rid], prompt_len=len(r.tokens),
            finished_at=now, arrival=r.arrival,
            token_times=list(self.out_times[rid]),
            delivered_times=list(self.deliv_times[rid]))
        self._record_completion(self.completions[rid])

    # -- decode --------------------------------------------------------

    def _ttl_vector(self, now: float) -> np.ndarray:
        """Per-slot decode-step allowance derived from wall-clock deadlines:
        with a warm per-step time estimate (the straggler monitor's EMA), a
        deadlined slot gets ``floor(remaining / est)`` steps so the burst
        cannot overrun its deadline by up to ``decode_burst`` tokens (min 1
        — the host-side ``_expire`` sweep catches the already-late case
        before the burst); without an estimate, ``TTL_NONE`` and the host
        expires between bursts."""
        n = self.scfg.n_slots
        ttl = np.full(n, TTL_NONE, np.int32)
        if self._step_ema <= 0:
            return ttl
        for s in range(n):
            rid = self.slot_rid[s]
            if rid is None or not self.active[s]:
                continue
            d = self.requests[rid].deadline
            if d is not None:
                ttl[s] = int(np.clip((d - now) / self._step_ema, 1,
                                     TTL_NONE))
        return ttl

    def _observe_burst(self, dt: float, steps: int) -> None:
        """Feed the burst wall time to the straggler monitor (outlier
        bursts are flagged, not folded into the EMA) and refresh the
        per-step estimate the deadline TTL uses."""
        self._hists["burst_wall_s"].observe(dt)
        if self.straggler.observe(dt):
            self._count("stragglers")
        if self.straggler.ema > 0 and steps > 0:
            self._step_ema = self.straggler.ema / steps

    def _expire_slot(self, s: int, now: float) -> None:
        """Slot ``s``'s request passed its deadline: structured ``deadline``
        failure with the tokens generated so far; slot + pages freed."""
        rid = self.slot_rid[s]
        d = self.requests[rid].deadline
        self.obs.tracer.instant("expire", rid=rid, slot=s)
        self._fail(rid, "deadline", now, detail=f"deadline {d:.3f}s")
        self._free_slot(s)
        self._count("expired")

    def burst(self, now: float) -> None:
        """One jitted burst of ``decode_burst`` masked steps + host
        bookkeeping: append emitted tokens, finalize newly freed slots.
        Paged mode first appends the pages the burst will write (possibly
        preempting) and refreshes the device block tables.  In spec mode
        the burst is ONE speculative step: draft, verify, accept, roll
        back.  Robustness (DESIGN.md §13): deadlined slots carry a TTL the
        device decrements alongside budget; per-step finite flags come back
        with the tokens, and a slot whose logits went non-finite keeps only
        its finite-prefix tokens and is quarantined."""
        if self.spec:
            self._spec_burst(now)
            return
        tracer = self.obs.tracer
        with tracer.span("burst_prep"):
            if self.chaos is not None:
                self.chaos.fire(self, "pre_burst")
            if self.paged:
                self._ensure_burst_pages(max(1, self.scfg.decode_burst))
                if not self.active.any():  # everyone preempted: no burst
                    return
                self.cache["block_tables"] = jnp.asarray(self.block_tables)
            ttl = self._ttl_vector(now)
            was_active = self.active.copy()
        with tracer.span("decode_burst", active=int(self.active.sum())):
            t_in = time.perf_counter()
            emits, oks, self.cache, tok, lengths, active, budget, ttl_out, \
                self.key, tstats = self._burst(
                    self.params, self.cache,
                    jnp.asarray(self.last_tok)[:, None],
                    jnp.asarray(self.lengths),
                    jnp.asarray(self.active),
                    jnp.asarray(self.budget),
                    jnp.asarray(ttl), self.key)
            emits = np.asarray(emits)                   # (steps, n_slots)
            held = self._now()
            oks = np.asarray(oks)                       # (steps, n_slots)
            ttl_out = np.asarray(ttl_out)
            # np.array (not asarray): jax exports read-only views, but
            # admission writes per-slot entries into these host mirrors
            self.lengths = np.array(lengths)
            self.active = np.array(active)
            self.budget = np.array(budget)
            self.last_tok = np.array(tok)[:, 0]
            dt = time.perf_counter() - t_in  # np.asarray blocked above
            self._observe_burst(dt, emits.shape[0])
        with tracer.span("burst_emit"):
            if tstats:
                self.obs.numerics.update(tstats)
            self._count("bursts")
            self._count("burst_steps", emits.shape[0])
            self._count("model_calls", emits.shape[0])
            n_active_steps = int((emits != PAD).sum())
            self._count("slot_steps_active", n_active_steps)
            self._count_converts(n_active_steps)
            for s in np.nonzero(was_active)[0]:
                col = emits[:, s]
                bad = np.nonzero(~oks[:, s])[0]
                # keep only the finite-prefix tokens: the first non-finite
                # step's sample (and everything after) is garbage
                col = col[:int(bad[0])] if bad.size else col
                toks = col[col != PAD].tolist()
                rid = self.slot_rid[s]
                self.outputs[rid].extend(toks)
                self.out_times[rid].extend([now] * len(toks))
                self.deliv_times[rid].extend([held] * len(toks))
                self._count("tokens_emitted", len(toks))
                if bad.size:
                    self._quarantine(s, now, where="burst")
                    continue
                if not self.active[s]:                  # freed on device
                    hit_eos = (self._eos is not None and toks
                               and toks[-1] == self._eos)
                    if (ttl_out[s] <= 0 and self.budget[s] > 0
                            and not hit_eos):
                        self._expire_slot(s, now)       # deadline TTL
                    else:
                        self._finish(rid, now)
                        self._free_slot(s)
            self._audit_check()

    # -- speculative decode (repro/serve/spec.py; DESIGN.md §11) --------

    def _spec_burst(self, now: float) -> None:
        """One speculative step over the whole pool: host-side drafting
        (per-slot ragged lengths), ONE jitted verify call scoring
        ``draft_k + 1`` lanes per slot, longest-accepted-prefix emission
        with EOS/budget on accepted tokens only, then KV rollback — dense
        slots rewind by length alone; paged slots also un-append the tail
        pages the rejected lanes wrote into."""
        scfg = self.scfg
        K = scfg.draft_k
        tracer = self.obs.tracer
        with tracer.span("burst_prep"):
            if self.chaos is not None:
                self.chaos.fire(self, "pre_burst")
            if self.paged:
                # verify writes lanes L..L+m (m <= min(K, budget-1)): cover
                # the worst case before the call, preempting on pool
                # exhaustion
                self._ensure_burst_pages(K + 1)
                if not self.active.any():  # everyone preempted: no burst
                    return
                self.cache["block_tables"] = jnp.asarray(self.block_tables)
            n = scfg.n_slots
            want = np.zeros(n, np.int32)
            contexts: list = [None] * n
            for s in range(n):
                if not self.active[s]:
                    continue
                # drafts past budget-1 can never be emitted, and the verify
                # write frontier must stay inside max_len
                want[s] = max(0, min(K, int(self.budget[s]) - 1,
                                     scfg.max_len - 1 - int(self.lengths[s])))
                rid = self.slot_rid[s]
                contexts[s] = np.concatenate(
                    [np.asarray(self.requests[rid].tokens, np.int32),
                     np.asarray(self.outputs[rid], np.int32)])
            calls0 = self.drafter.model_calls
            draft, n_draft = self.drafter.draft_batch(contexts, want, K)
            # a model drafter's teacher-sync/draft-loop invocations count
            # too, so tokens-per-model-call never overstates the
            # amortization
            self._count("model_calls", self.drafter.model_calls - calls0)
            if self.chaos is not None:
                # drafter-desync fault: junk drafts are REJECTED by exact
                # verification, so outputs are provably unchanged
                draft, n_draft = self.chaos.corrupt_drafts(self, draft,
                                                           n_draft, want)
            was_active = self.active.copy()
        with tracer.span("spec_verify", active=int(self.active.sum())):
            t_in = time.perf_counter()
            emitted, self.cache, tok, lengths, active, budget, n_acc, ok, \
                tstats = self._spec_step(
                    self.params, self.cache,
                    jnp.asarray(self.last_tok)[:, None],
                    jnp.asarray(draft), jnp.asarray(n_draft),
                    jnp.asarray(self.lengths),
                    jnp.asarray(self.active),
                    jnp.asarray(self.budget))
            emitted = np.asarray(emitted)               # (n_slots, K + 1)
            held = self._now()
            n_acc = np.asarray(n_acc)
            ok = np.asarray(ok)                         # per-slot finite bit
            self.lengths = np.array(lengths)
            self.active = np.array(active)
            self.budget = np.array(budget)
            self.last_tok = np.array(tok)[:, 0]
            dt = time.perf_counter() - t_in
            self._observe_burst(dt, 1)
        with tracer.span("burst_emit"):
            if tstats:
                self.obs.numerics.update(tstats)
            self._count("bursts")
            self._count("burst_steps")
            self._count("spec_steps")
            self._count("model_calls")
            for s in np.nonzero(was_active)[0]:
                if not ok[s]:
                    # non-finite verify logits poison every lane's argmax:
                    # no token from this step can be trusted, so emit
                    # nothing and quarantine (the finite prefix already in
                    # outputs stands)
                    self._quarantine(s, now, where="spec")
                    continue
                row = emitted[s]
                row = row[row != PAD].tolist()
                rid = self.slot_rid[s]
                self.outputs[rid].extend(row)
                self.out_times[rid].extend([now] * len(row))
                self.deliv_times[rid].extend([held] * len(row))
                self._count("tokens_emitted", len(row))
                self._count("draft_tokens", int(n_draft[s]))
                self._count("accepted_tokens", int(n_acc[s]))
                self._count_converts(len(row))
                if row:
                    self._count("slot_steps_active")
                if not self.active[s]:                  # freed on device
                    self._finish(rid, now)
                    self._free_slot(s)
            if self.paged:
                self._rollback_spec_pages()
            self._audit_check()

    def _rollback_spec_pages(self) -> None:
        """Un-append tail pages past each active slot's post-acceptance
        length — the rejected verify lanes' pages.  Refcount-correct by
        construction: only pages popped off the slot's OWN table are
        decref'd, so a page the radix trie also references survives at the
        trie's count; and since lengths never shrink, the keep point can
        never reach back into the prompt's (possibly trie-shared) pages —
        only ever into this burst's fresh appends."""
        ps = self.scfg.page_size
        for s in range(self.scfg.n_slots):
            if not self.active[s]:
                continue
            keep = -(-int(self.lengths[s]) // ps)
            while len(self.slot_pages[s]) > keep:
                p = self.slot_pages[s].pop()
                self.block_tables[s, len(self.slot_pages[s])] = 0
                self.pool.decref(p)

    # -- robustness: quarantine, scrub, degradation ladder (§13) --------

    def _scrub_dense_slot(self, s: int) -> None:
        """Overwrite slot ``s``'s dense cache rows with freshly initialized
        ones — stale NaN/Inf KV would otherwise poison the slot's NEXT
        occupant through the ``0 * NaN = NaN`` path of masked attention
        (scores are masked with NEG_BIG, but a non-finite V row still
        reaches the ``probs @ v`` contraction)."""
        scfg = self.scfg
        n = scfg.n_slots
        if self._scatter is None:
            self._axes = _cache_batch_axes(self.model, self.params,
                                           scfg.max_len, scfg.cache_dtype)
            self._scatter = build_scatter(self.model, self._axes,
                                          scfg.max_len, scfg.cache_dtype)
        fresh = self.model.init_cache(self.params, n, scfg.max_len,
                                      scfg.cache_dtype)
        self.cache = self._scatter(self.cache, fresh,
                                   jnp.full(n, s, dtype=I32))

    def _scrub_slot_pages(self, s: int) -> None:
        """Zero slot ``s``'s EXCLUSIVE pages (refcount 1) before they go
        back to the pool, so a poisoned row cannot leak to the page's next
        owner.  Trie-shared prompt pages (refcount > 1) are read-only
        replays of clean prefill writes and stay — zeroing them would
        corrupt other requests' cached prefixes."""
        pages = [p for p in self.slot_pages[s] if self.pool.refs[p] == 1]
        if not pages:
            return
        if self._zero_pages is None:
            @functools.partial(jax.jit, donate_argnums=(0,))
            def zp(blocks, idx):
                return jax.tree.map(
                    lambda lf: lf.at[:, idx].set(jnp.zeros((), lf.dtype)),
                    blocks)
            self._zero_pages = zp
        # pad to n_blocks with the null page: one compilation, and writes
        # at page 0 land in the never-read sink
        idx = np.full(self.n_blocks, kvpool.NULL_PAGE, np.int32)
        idx[:len(pages)] = pages
        self.cache["blocks"] = self._zero_pages(self.cache["blocks"],
                                                jnp.asarray(idx))

    def _quarantine(self, s: int, now: float, where: str = "") -> None:
        """Slot ``s`` produced non-finite logits: scrub its KV, free it,
        and walk the degradation ladder (DESIGN.md §13) — first fault:
        requeue and recompute from the prompt + finite-prefix tokens
        (greedy outputs unchanged); repeat fault: ONE retry on the unfused
        fp32 dense path; still faulting: structured ``numeric_fault``.
        Exactly the silent-corruption shape fp2fx conversion invites —
        ``core/numerics.py`` saturates ±inf and maps NaN -> 0, so a bad
        scale row degrades accuracy silently while the logits go bad
        loudly; this is where the loud signal is caught."""
        rid = self.slot_rid[s]
        nf = self.numeric_faults.get(rid, 0) + 1
        self.numeric_faults[rid] = nf
        self._count("quarantines")
        # annotate the decision with the numeric stats that triggered
        # it (the last telemetry burst's exponent/scale readings)
        ev = self.obs.numerics.record_quarantine(rid, where or "burst")
        self.obs.tracer.instant("quarantine", slot=s, fault=nf, **ev)
        if self.paged:
            self._scrub_slot_pages(s)
        else:
            self._scrub_dense_slot(s)
        if nf == 1:
            self._requeue(s, now)  # may fail structurally on the retry cap
        elif nf == 2 and self._allow_fp32_retry:
            self._fp32_retry(rid, now)
        else:
            self._fail(rid, "numeric_fault", now,
                       detail=f"non-finite logits at {where} (fault {nf})")
        self._free_slot(s)
        self._audit_check()

    def _fp32_retry(self, rid: int, now: float) -> None:
        """Second numeric fault for ``rid``: re-run it solo on the unfused
        fp32 dense path — a fresh engine, fresh cache, no prefix sharing,
        no chaos — continuing from the finite-prefix tokens already
        emitted.  A clean retry completes the request (greedy outputs
        identical to a fault-free run); a retry that faults again surfaces
        a structured ``numeric_fault``."""
        self._count("fp32_retries")
        orig = self.requests[rid]
        done = list(self.outputs[rid])
        sched = ("continuous"
                 if self.scfg.scheduler in ("continuous", "spec")
                 else "lockstep")
        sub = dataclasses.replace(
            self.scfg, cache_dtype="float32", attn_mode="unfused",
            kv_layout="dense", prefix_cache=False, n_slots=1,
            scheduler=sched, audit=False, max_queue=0, n_pages=0)
        eng = SlotPoolEngine(self.model, self.params, sub)
        eng._allow_fp32_retry = False   # the fallback never recurses
        toks = np.concatenate([np.asarray(orig.tokens, np.int32),
                               np.asarray(done, np.int32)])
        rem = (orig.deadline - now) if orig.deadline is not None else None
        comp = eng.run([Request(rid=rid, tokens=toks,
                                max_new=orig.max_new - len(done),
                                frames=orig.frames, deadline=rem)])[rid]
        fin = self._now()
        self.outputs[rid].extend(comp.tokens)
        self.out_times[rid].extend([fin] * len(comp.tokens))
        self.deliv_times[rid].extend([fin] * len(comp.tokens))
        if comp.failure is None:
            self._finish(rid, fin)
        else:
            reason = ("deadline" if comp.failure.reason == "deadline"
                      else "numeric_fault")
            self._fail(rid, reason, fin,
                       detail=f"fp32 retry: {comp.failure.reason}")

    # -- robustness: cancellation, deadlines, shutdown, audits (§13) ----

    def cancel(self, rid: int) -> None:
        """Request host-side cancellation of ``rid``: honored at the next
        scheduling checkpoint (between bursts), emitting a partial
        Completion with ``cancelled=True``."""
        self._cancels.add(rid)

    def _cancel_done(self, rid: int, now: float) -> None:
        r = self.requests[rid]
        self.completions[rid] = Completion(
            rid=rid, tokens=self.outputs.get(rid, []),
            prompt_len=len(r.tokens), finished_at=now, arrival=r.arrival,
            token_times=list(self.out_times.get(rid, [])),
            delivered_times=list(self.deliv_times.get(rid, [])),
            cancelled=True)
        self._count("cancelled")
        self._record_completion(self.completions[rid])

    def _apply_cancels(self, now: float) -> None:
        if not self._cancels:
            return
        todo, self._cancels = self._cancels, set()
        for rid in todo:
            if rid in self.completions or rid not in self.requests:
                continue  # already terminal / never submitted
            for s in range(self.scfg.n_slots):
                if self.slot_rid[s] == rid:
                    self._free_slot(s)
                    break
            self._queue = deque(r for r in self._queue if r.rid != rid)
            self._pending = deque(r for r in self._pending if r.rid != rid)
            self._cancel_done(rid, now)
        self._audit_check()

    def _expire(self, now: float) -> None:
        """Host-side deadline sweep over slots and the waiting queue.  The
        device TTL bounds mid-burst overrun; this sweep guarantees an
        already-late request is expired at the next scheduling checkpoint
        even when the step-time estimate is cold."""
        for s in range(self.scfg.n_slots):
            rid = self.slot_rid[s]
            if rid is None:
                continue
            d = self.requests[rid].deadline
            if d is not None and now >= d:
                self._expire_slot(s, now)
        late = [r for r in self._queue
                if r.deadline is not None and now >= r.deadline]
        if late:
            gone = {r.rid for r in late}
            self._queue = deque(r for r in self._queue if r.rid not in gone)
            for r in late:
                self._register(r)
                self._fail(r.rid, "deadline", now, detail="expired in queue")
                self._count("expired")
        self._audit_check()

    def shutdown(self) -> dict[int, Completion]:
        """Drain: every in-flight or queued request without a completion is
        terminated as cancelled with its partial tokens, and all slots and
        pages are freed — the graceful KeyboardInterrupt path
        (launch/serve.py, examples/serve_decode.py).  Idempotent; returns
        the completions map."""
        now = self._now()
        for s in range(self.scfg.n_slots):
            rid = self.slot_rid[s]
            if rid is not None:
                self._free_slot(s)
                if rid not in self.completions:
                    self._cancel_done(rid, now)
        for r in list(self._queue) + list(self._pending):
            if r.rid not in self.completions:
                self._register(r)
                self._cancel_done(r.rid, now)
        self._queue.clear()
        self._pending.clear()
        self._audit_check()
        return self.completions

    def _audit_check(self) -> None:
        """Recompute pool/trie refcounts from live slots + trie edges and
        cross-check the free list (``kvpool.PagePool.audit``).  Called at
        every admission / finish / preemption / quarantine / expiry
        checkpoint when ``ServeConfig.audit`` is on, so bookkeeping drift
        surfaces AT the mutation that caused it, not requests later.  The
        chaos harness's squeezed pages ride along as extra holders."""
        if not self.scfg.audit or not self.paged:
            return
        self._count("audits")
        for s in range(self.scfg.n_slots):
            if self.slot_rid[s] is None and self.slot_pages[s]:
                raise kvpool.AuditError(
                    f"freed slot {s} still holds pages {self.slot_pages[s]}")
        self.pool.audit(list(self.slot_pages) + list(self._extra_holders),
                        self.trie)

    # -- the serving loop ----------------------------------------------

    def run(self, requests: list[Request]) -> dict[int, Completion]:
        """Serve ``requests`` (sorted by ``arrival``) until every one has a
        DEFINITE outcome — finished, cancelled, or structured failure
        (DESIGN.md §13).  Malformed requests fail individually with reason
        ``invalid`` instead of aborting the whole batch.

        With the tracer enabled, the whole run is under a compile watch: a
        mid-flight XLA compile (a retrace the prewarm missed) shows up as a
        backdated "compile" span in the trace (DESIGN.md §15)."""
        tracer = self.obs.tracer
        with compile_watch(tracer, enabled=tracer.enabled):
            return self._run(requests)

    def _schedule(self, now: float, continuous: bool) -> None:
        """One scheduling checkpoint of the serving loop: cancels and
        deadline expiry, arrivals into the queue, admission into free
        slots, then the load gauges and the metrics snapshot."""
        if self.chaos is not None:
            self.chaos.fire(self, "tick")
        self._apply_cancels(now)
        self._expire(now)
        # arrivals move into the BOUNDED waiting queue: admission
        # backpressure rejects (reason "queue_full") instead of letting
        # the queue grow without limit; requeues from preemption /
        # quarantine bypass this — they already held an admission
        while self._pending and self._pending[0].arrival <= now:
            r = self._pending.popleft()
            if (self.scfg.max_queue
                    and len(self._queue) >= self.scfg.max_queue):
                self._fail(r.rid, "queue_full", now,
                           detail=f"{len(self._queue)} waiting")
                self._count("rejected")
            else:
                self._queue.append(r)
        free = sum(1 for rid in self.slot_rid if rid is None)
        busy = self.active.any() or self.prefilling.any()
        can_admit = continuous or not busy
        batch = []
        while can_admit and self._queue and len(batch) < free:
            batch.append(self._queue.popleft())
        if batch:
            # page-starved admissions requeue their tail to the front
            self.admit(batch, self._now())
            self._audit_check()
        # per-iteration load gauges + periodic metrics snapshot export
        self._gauges["queue_depth"].set(len(self._queue))
        self._gauges["slot_occupancy"].set(
            sum(1 for rid in self.slot_rid if rid is not None))
        if self.paged:
            self._gauges["pages_in_use"].set(self.pool.pages_in_use)
        self.obs.maybe_snapshot()

    def _run(self, requests: list[Request]) -> dict[int, Completion]:
        ok_reqs = []
        for r in sorted(requests, key=lambda r: r.arrival):
            self._register(r)
            if r.max_new < 1:
                self._fail(r.rid, "invalid", 0.0,
                           detail=f"max_new {r.max_new} < 1")
            elif len(r.tokens) + r.max_new > self.scfg.max_len:
                self._fail(r.rid, "invalid", 0.0,
                           detail=f"prompt {len(r.tokens)} + max_new "
                                  f"{r.max_new} exceeds max_len "
                                  f"{self.scfg.max_len}")
            else:
                ok_reqs.append(r)
        self._pending = deque(ok_reqs)
        self._queue = deque()
        tracer = self.obs.tracer
        self._t0 = t0 = time.perf_counter()
        self._t0_tr = tracer.now()
        continuous = self.scfg.scheduler in ("continuous", "spec")
        while (self._pending or self._queue or self.active.any()
               or self.prefilling.any()):
            with tracer.span("schedule"):
                self._schedule(time.perf_counter() - t0, continuous)
            if self.prefilling.any():
                # at most ONE chunk per loop iteration: a long prompt's
                # prefill interleaves with the decode bursts below instead
                # of stalling them for the whole prompt
                self._prefill_step(time.perf_counter() - t0)
            if self.active.any():
                self.burst(time.perf_counter() - t0)
            elif (not self.prefilling.any() and not self._queue
                    and self._pending):
                # idle: wait for the next arrival
                with tracer.span("wait_arrival"):
                    now = time.perf_counter() - t0
                    time.sleep(max(0.0, min(
                        self._pending[0].arrival - now, 0.01)))
        self.obs.maybe_snapshot(force=True)
        return self.completions


def serve(model, params, requests: list[Request], scfg: ServeConfig,
          key=None, draft=None, chaos=None) -> dict[int, Completion]:
    """One-shot entry: build a slot-pool engine, serve, return completions.
    ``draft``: optional (model, params) pair for ``spec_mode="model"``;
    ``chaos``: optional ``repro.serve.chaos.ChaosMonkey`` fault injector."""
    eng = SlotPoolEngine(model, params, scfg, key=key, draft=draft,
                         chaos=chaos)
    eng.run(requests)
    return eng.completions
