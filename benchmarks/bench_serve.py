"""Serving benchmark: continuous vs lockstep, paged+prefix-cache vs dense,
speculative vs plain continuous decode, chunked vs whole-prompt prefill.

Four workloads through ``repro.serve.scheduler``:

  mixed-length Poisson — the PR 3 comparison: ``lockstep`` admission (drain
      the slot pool between groups) vs ``continuous`` (admit into freed
      slots mid-decode).  Both share the same jitted burst/prefill
      executables, so the comparison isolates the scheduling policy.
  shared-prefix — N requests drawn from K distinct system prompts (a long
      shared head + a short unique tail), served by the dense slot pool and
      by the paged layout with the radix-trie prefix cache
      (``kv_layout="paged"``, ``prefix_cache=True``).  The paged engine
      admits followers by reusing the cached prefix pages and pushes only
      the unique tail through the model; the benchmark records the
      prefix-hit rate, peak pages in use, preemption count, and tokens/sec
      against the dense baseline that re-prefills every prompt in full.
  repetitive/agentic — prompts shaped like boilerplate edits / tool-call
      loops (a short "line" motif tiled several times + a unique tail),
      the high n-gram-hit-rate regime speculative decoding exists for.
      Served by plain continuous decode and by ``scheduler="spec"``
      (n-gram self-drafting, one-call verify bursts); the benchmark
      records the acceptance rate and tokens-per-model-call alongside
      tokens/sec.  Greedy outputs are identical by construction, so the
      comparison isolates the decode strategy.
  mixed long/short — short interactive prompts share the pool with long
      ones (the head-of-line-blocking regime chunked prefill exists for),
      served whole-prompt (``prefill_chunk=0``: an admitted prompt's whole
      prefill runs as one call before the next decode burst) and chunked
      (``prefill_chunk=N``: at most N prompt tokens between bursts).
      Outputs are identical; the benchmark records TTFT and
      time-between-tokens (TBT) p50/p99, where bounded prefill stalls show
      up directly as a lower TBT tail.
  chaos (``--chaos``) — the robustness contract under seeded fault
      injection (``repro.serve.chaos``, DESIGN.md §13): four serving
      configs (dense fp32, dense fp2fx8, paged+prefix, speculative) each
      run fault-free and then under a ``FaultPlan`` mixing forced
      preemptions, NaN/Inf KV poison, trie-eviction storms, page-pool
      squeezes, drafter desync, stragglers, and cancellations — with
      ``audit=True`` so pool/trie refcounts are recomputed at every
      checkpoint.  CI asserts every request reaches a DEFINITE outcome,
      non-poisoned completions are token-identical to the fault-free run,
      and the audits stayed clean.  All requests arrive at t=0 with no
      deadlines, making the scheduling sequence wall-clock-free and the
      fault replay deterministic.

Reports aggregate tokens/sec, request latency p50/p99 (completion − Poisson
arrival), TTFT/TBT percentiles, and mean slot occupancy; results land in
``BENCH_serve.json`` (CI runs ``--smoke`` and asserts continuous >=
lockstep, paged+prefix >= dense, and chunked p99 TBT < whole-prompt on
their respective workloads).

An observability section (``--trace``, DESIGN.md §15) serves traced
workloads with the span tracer + metrics JSONL export on: it measures the
tracer's wall-clock overhead (CI asserts < 5%), writes a Perfetto-loadable
``TRACE_serve.json`` covering the span taxonomy, reconciles the metrics
registry against the post-hoc ``Completion`` records, and reports the
hybrid-format numeric telemetry (softmax exponent range, fp2fx8 scale
histogram, int8 saturation) from a ``telemetry=True`` fp2fx8 engine under
NaN poison — including the numeric stats attached to each quarantine.

Absolute numbers are CPU times (Pallas in interpreter mode; on TPU it is
the compiled path) — read the relative trends.  Every engine's one-time
warm-up (``prewarm``'s executable compilation plus first-run runtime setup:
XLA thread pools, allocator arenas) is timed explicitly and reported as
``warmup_s`` per engine, so the serving wall-clock numbers exclude it and
sections stay comparable whether run standalone (``--prefix-only`` /
``--spec-only`` / ``--chunked-only``, the CI jobs' shape) or in one sweep;
``--merge`` lets standalone runs update one shared JSON.
"""
from __future__ import annotations

import time

import jax
import numpy as np


def _build(vocab=128, n_layers=2):
    from repro.configs import get_config, smoke_config
    from repro.models import build_model
    from repro.models.layers import unbox
    cfg = smoke_config(get_config("olmo-1b")).with_(
        softmax_impl="hyft16", vocab=vocab, n_layers=n_layers)
    model = build_model(cfg)
    params = unbox(model.init(jax.random.PRNGKey(0)))
    return cfg, model, params


def make_workload(cfg, n, rng, plen, new, rate_hz):
    """``n`` requests: prompt length U[plen], output budget U[new] (the
    mixed-horizon shape lockstep handles worst), exponential interarrivals
    at ``rate_hz`` (Poisson process)."""
    from repro.serve.scheduler import Request
    arrivals = np.cumsum(rng.exponential(1.0 / rate_hz, n))
    return [Request(
        rid=i,
        tokens=rng.integers(0, cfg.vocab,
                            int(rng.integers(plen[0], plen[1] + 1))).astype(
                                np.int32),
        max_new=int(rng.integers(new[0], new[1] + 1)),
        arrival=float(arrivals[i])) for i in range(n)]


def make_prefix_workload(cfg, n, k_prompts, rng, prefix_len, tail, new,
                         rate_hz):
    """``n`` requests over ``k_prompts`` distinct system prompts: each
    prompt is a shared ``prefix_len``-token head + a ``tail``-token unique
    suffix — the shape a prefix cache exists for (the dense baseline
    re-prefills the shared head for every request)."""
    from repro.serve.scheduler import Request
    heads = [rng.integers(0, cfg.vocab, prefix_len).astype(np.int32)
             for _ in range(k_prompts)]
    arrivals = np.cumsum(rng.exponential(1.0 / rate_hz, n))
    return [Request(
        rid=i,
        tokens=np.concatenate(
            [heads[i % k_prompts],
             rng.integers(0, cfg.vocab, tail).astype(np.int32)]),
        max_new=int(rng.integers(new[0], new[1] + 1)),
        arrival=float(arrivals[i])) for i in range(n)]


def make_repetitive_workload(cfg, n, rng, motif_len, reps, tail, new,
                             rate_hz):
    """``n`` requests with code-ish repetitive prompts: a ``motif_len``
    "line" tiled ``reps`` times + a ``tail``-token unique suffix.  The
    trailing n-gram of such a context almost always recurs earlier, so the
    prompt-lookup drafter stays hot — the agentic/templated-output regime
    speculative decoding targets."""
    from repro.serve.scheduler import Request
    arrivals = np.cumsum(rng.exponential(1.0 / rate_hz, n))
    reqs = []
    for i in range(n):
        motif = rng.integers(0, cfg.vocab, motif_len).astype(np.int32)
        toks = np.concatenate(
            [np.tile(motif, reps),
             rng.integers(0, cfg.vocab, tail).astype(np.int32)])
        reqs.append(Request(rid=i, tokens=toks,
                            max_new=int(rng.integers(new[0], new[1] + 1)),
                            arrival=float(arrivals[i])))
    return reqs


def _latency_stats(done):
    """TTFT (first token − arrival) and TBT (successive token-emission
    gaps, pooled across requests) percentiles, in milliseconds.  Requests
    that never emitted a token (cancelled / failed before their first
    emission) have ``ttft is None`` and are skipped."""
    ttft = np.array([c.ttft for c in done.values() if c.ttft is not None])
    if ttft.size == 0:
        ttft = np.zeros(1)
    gaps = [np.diff(c.delivered_times) for c in done.values()
            if len(c.delivered_times) > 1]
    tbt = np.concatenate(gaps) if gaps else np.zeros(1)
    return {"ttft_p50_ms": float(np.percentile(ttft, 50) * 1e3),
            "ttft_p99_ms": float(np.percentile(ttft, 99) * 1e3),
            "tbt_p50_ms": float(np.percentile(tbt, 50) * 1e3),
            "tbt_p99_ms": float(np.percentile(tbt, 99) * 1e3)}


def run_engine(model, params, reqs, scfg, obs=None):
    """Serve ``reqs`` on a prewarmed engine; returns (metrics dict,
    completions dict) — callers compare completions across engines."""
    from repro.serve.scheduler import SlotPoolEngine
    eng = SlotPoolEngine(model, params, scfg, obs=obs)
    # compile every admission/burst shape up front: admission group shapes
    # depend on wall-clock arrival timing, so an untimed warmup run would
    # not reliably cover them and a mid-run trace would pollute the timing
    t_w = time.perf_counter()
    eng.prewarm(max(len(r.tokens) for r in reqs))
    warmup = time.perf_counter() - t_w
    t0 = time.perf_counter()
    done = eng.run(reqs)
    wall = time.perf_counter() - t0
    tokens = sum(len(c.tokens) for c in done.values())
    lat = np.array([c.latency for c in done.values()])
    st = eng.stats
    occ = (st["slot_steps_active"] /
           max(1, st["burst_steps"] * scfg.n_slots))
    out = {"scheduler": scfg.scheduler, "kv_layout": scfg.kv_layout,
           "prefill_chunk": scfg.prefill_chunk,
           "warmup_s": warmup, "wall_s": wall, "tokens": tokens,
           "tokens_per_s": tokens / wall,
           "p50_ms": float(np.percentile(lat, 50) * 1e3),
           "p99_ms": float(np.percentile(lat, 99) * 1e3),
           "occupancy": occ, "bursts": st["bursts"],
           "prefills": st["prefills"],
           "prefill_tokens": st["prefill_tokens"],
           "model_calls": st["model_calls"],
           "tokens_per_model_call": (st["tokens_emitted"] /
                                     max(1, st["model_calls"]))}
    out.update(_latency_stats(done))
    if scfg.scheduler == "spec":
        out.update(
            acceptance_rate=(st["accepted_tokens"] /
                             max(1, st["draft_tokens"])),
            draft_tokens=st["draft_tokens"],
            accepted_tokens=st["accepted_tokens"],
            spec_steps=st["spec_steps"])
    if scfg.kv_layout == "paged":
        out.update(
            prefix_hit_rate=st["cached_tokens"] / max(1, st["prompt_tokens"]),
            cached_tokens=st["cached_tokens"],
            pages_peak=st["pages_peak"],
            preemptions=st["preemptions"])
    return out, done


def make_mixed_workload(cfg, n, rng, short, long_, frac_long, new, rate_hz):
    """``n`` requests mixing short interactive prompts (length U[short])
    with long ones (U[long_], probability ``frac_long``) — the
    head-of-line-blocking shape where a long arrival's whole-prompt prefill
    stalls every in-flight decode, which chunked prefill bounds."""
    from repro.serve.scheduler import Request
    arrivals = np.cumsum(rng.exponential(1.0 / rate_hz, n))
    reqs = []
    for i in range(n):
        plen = (int(rng.integers(long_[0], long_[1] + 1))
                if rng.random() < frac_long
                else int(rng.integers(short[0], short[1] + 1)))
        reqs.append(Request(
            rid=i, tokens=rng.integers(0, cfg.vocab, plen).astype(np.int32),
            max_new=int(rng.integers(new[0], new[1] + 1)),
            arrival=float(arrivals[i])))
    return reqs


def run(report, smoke: bool = False, prefix_only: bool = False,
        spec_only: bool = False, chunked_only: bool = False,
        chaos_only: bool = False, obs_only: bool = False,
        trace_out: str = "TRACE_serve.json",
        metrics_out: str = "METRICS_serve.jsonl"):
    """Returns the machine-readable results dict (also printed as CSV).

    ``prefix_only`` runs just the shared-prefix section, ``spec_only`` just
    the repetitive/speculative section, ``chunked_only`` just the mixed
    long/short chunked-prefill section, ``chaos_only`` just the
    fault-injection robustness section, and ``obs_only`` just the
    observability section — the CI jobs each assert on one comparison and
    need not pay for the others.
    """
    from repro.configs.base import ServeConfig
    cfg, model, params = _build()
    if chaos_only:
        return _run_chaos(report, {}, cfg, model, params, smoke)
    if obs_only:
        return _run_obs(report, {}, cfg, model, params, smoke,
                        trace_out=trace_out, metrics_out=metrics_out)
    # arrival rate is set well above the service rate so a queue builds —
    # the regime where the admission policy matters (an unsaturated pool
    # admits small groups either way and the two schedulers converge)
    if smoke:
        n, plen, new, rate, slots, burst = 12, (4, 12), (4, 32), 200.0, 4, 4
    else:
        n, plen, new, rate, slots, burst = 32, (4, 16), (8, 128), 100.0, 8, 8
    # one dedicated rng per section: a section's workload is identical
    # whether it runs standalone (--prefix-only/--spec-only, the CI jobs)
    # or as part of the full sweep, so --merge'd JSONs stay comparable
    rng = np.random.default_rng(0)
    results: dict = {}
    if chunked_only:
        return _run_chunked(report, results, cfg, model, params,
                            np.random.default_rng(3), smoke)
    if not prefix_only and not spec_only:
        reqs = make_workload(cfg, n, rng, plen, new, rate)
        max_len = plen[1] + new[1] + 1
        results["workload"] = {
            "requests": n, "prompt_len": list(plen), "max_new": list(new),
            "poisson_rate_hz": rate, "n_slots": slots,
            "decode_burst": burst,
            "total_tokens": sum(r.max_new for r in reqs)}
        report(f"bench_serve,workload,requests={n},prompts={plen},"
               f"new={new},slots={slots}")
        results["engines"] = {}
        for mode in ("lockstep", "continuous"):
            scfg = ServeConfig(max_len=max_len, cache_dtype="float32",
                               scheduler=mode, n_slots=slots,
                               decode_burst=burst)
            r, _ = run_engine(model, params, reqs, scfg)
            results["engines"][mode] = r
            report(f"bench_serve,{mode},"
                   f"tokens_per_s={r['tokens_per_s']:.1f},"
                   f"p50_ms={r['p50_ms']:.0f},p99_ms={r['p99_ms']:.0f},"
                   f"occupancy={r['occupancy']:.2f}")
        speed = (results["engines"]["continuous"]["tokens_per_s"] /
                 results["engines"]["lockstep"]["tokens_per_s"])
        results["continuous_vs_lockstep"] = speed
        report(f"bench_serve,speedup,continuous_vs_lockstep={speed:.2f}")

    # ---- shared-prefix workload: paged + prefix cache vs dense ----------
    if spec_only:
        return _run_spec(report, results, cfg, model, params,
                         np.random.default_rng(2), smoke, burst)
    if smoke:
        pn, kpr, pref, tail, pnew, prate, pslots = 12, 2, 48, 4, (4, 12), \
            200.0, 4
    else:
        pn, kpr, pref, tail, pnew, prate, pslots = 32, 3, 96, 8, (8, 32), \
            100.0, 8
    prng = np.random.default_rng(1)
    preqs = make_prefix_workload(cfg, pn, kpr, prng, pref, tail, pnew,
                                 prate)
    pmax_len = pref + tail + pnew[1] + 1
    results["prefix_workload"] = {
        "requests": pn, "distinct_prompts": kpr, "prefix_len": pref,
        "tail_len": tail, "max_new": list(pnew), "poisson_rate_hz": prate,
        "n_slots": pslots, "page_size": 16,
        "total_tokens": sum(r.max_new for r in preqs)}
    report(f"bench_serve,prefix_workload,requests={pn},prompts={kpr},"
           f"prefix={pref},tail={tail}")
    results["prefix_engines"] = {}
    for name, kw in (("dense", dict(kv_layout="dense")),
                     ("paged_prefix", dict(kv_layout="paged", page_size=16,
                                           prefix_cache=True))):
        scfg = ServeConfig(max_len=pmax_len, cache_dtype="float32",
                           scheduler="continuous", n_slots=pslots,
                           decode_burst=burst, **kw)
        r, _ = run_engine(model, params, preqs, scfg)
        results["prefix_engines"][name] = r
        extra = (f",hit_rate={r['prefix_hit_rate']:.2f},"
                 f"pages_peak={r['pages_peak']},"
                 f"preemptions={r['preemptions']}"
                 if name == "paged_prefix" else "")
        report(f"bench_serve,prefix_{name},"
               f"tokens_per_s={r['tokens_per_s']:.1f},"
               f"prefill_tokens={r['prefill_tokens']}{extra}")
    pspeed = (results["prefix_engines"]["paged_prefix"]["tokens_per_s"] /
              results["prefix_engines"]["dense"]["tokens_per_s"])
    results["paged_prefix_vs_dense"] = pspeed
    report(f"bench_serve,speedup,paged_prefix_vs_dense={pspeed:.2f}")
    if prefix_only:
        return results
    results = _run_spec(report, results, cfg, model, params,
                        np.random.default_rng(2), smoke, burst)
    return _run_chunked(report, results, cfg, model, params,
                        np.random.default_rng(3), smoke)


def _run_spec(report, results, cfg, model, params, rng, smoke, burst):
    """Repetitive/agentic workload: speculative vs plain continuous decode.

    Both engines share admission policy, slot count, and layout — the only
    difference is the decode strategy, so tokens/sec isolates what the
    accepted drafts buy and ``tokens_per_model_call`` shows the
    amortization directly.
    """
    from repro.configs.base import ServeConfig
    # arrival rate is set high enough that BOTH engines run compute-bound:
    # spec drains the queue fast enough that at the other sections' rates
    # it goes arrival-limited and the ratio collapses toward 1 by
    # construction, not by decode speed
    if smoke:
        sn, motif, reps, stail, snew, srate, sslots, skk = \
            12, 6, 4, 4, (8, 24), 400.0, 4, 4
    else:
        sn, motif, reps, stail, snew, srate, sslots, skk = \
            32, 8, 6, 8, (16, 64), 500.0, 8, 4
    sreqs = make_repetitive_workload(cfg, sn, rng, motif, reps, stail, snew,
                                     srate)
    smax_len = motif * reps + stail + snew[1] + 1
    results["spec_workload"] = {
        "requests": sn, "motif_len": motif, "reps": reps, "tail_len": stail,
        "max_new": list(snew), "poisson_rate_hz": srate, "n_slots": sslots,
        "draft_k": skk, "total_tokens": sum(r.max_new for r in sreqs)}
    report(f"bench_serve,spec_workload,requests={sn},motif={motif}x{reps},"
           f"tail={stail},draft_k={skk}")
    results["spec_engines"] = {}
    for name, kw in (("baseline", dict(scheduler="continuous")),
                     ("spec", dict(scheduler="spec", draft_k=skk))):
        scfg = ServeConfig(max_len=smax_len, cache_dtype="float32",
                           n_slots=sslots, decode_burst=burst, **kw)
        r, _ = run_engine(model, params, sreqs, scfg)
        results["spec_engines"][name] = r
        extra = (f",acceptance={r['acceptance_rate']:.2f},"
                 f"tok_per_call={r['tokens_per_model_call']:.2f}"
                 if name == "spec" else
                 f",tok_per_call={r['tokens_per_model_call']:.2f}")
        report(f"bench_serve,spec_{name},"
               f"tokens_per_s={r['tokens_per_s']:.1f},"
               f"model_calls={r['model_calls']}{extra}")
    sspeed = (results["spec_engines"]["spec"]["tokens_per_s"] /
              results["spec_engines"]["baseline"]["tokens_per_s"])
    results["spec_vs_baseline"] = sspeed
    report(f"bench_serve,speedup,spec_vs_baseline={sspeed:.2f}")
    return results


def _run_chunked(report, results, cfg, model, params, rng, smoke):
    """Mixed long/short workload: chunked vs whole-prompt prefill.

    Same admission policy, slots, and decode bursts — the only difference
    is ``prefill_chunk``, so the TBT tail isolates what bounding the
    per-burst prefill stall buys: in whole-prompt mode a long arrival's
    entire prefill runs between two decode bursts and every in-flight
    request's inter-token gap eats it; chunked mode caps that stall at one
    chunk's worth of tokens.  Outputs are identical by construction (the
    chunk split is invisible to the arithmetic) — recorded in the results
    so CI can assert it.
    """
    from repro.configs.base import ServeConfig
    if smoke:
        cn, cshort, clong, cfrac, cnew, crate, cslots, cburst, chunk = \
            10, (3, 8), (48, 72), 0.3, (8, 24), 150.0, 4, 4, 8
    else:
        cn, cshort, clong, cfrac, cnew, crate, cslots, cburst, chunk = \
            24, (4, 12), (96, 128), 0.3, (16, 48), 80.0, 8, 8, 16
    creqs = make_mixed_workload(cfg, cn, rng, cshort, clong, cfrac, cnew,
                                crate)
    cmax_len = clong[1] + cnew[1] + 1
    results["chunked_workload"] = {
        "requests": cn, "short_len": list(cshort), "long_len": list(clong),
        "frac_long": cfrac, "max_new": list(cnew), "poisson_rate_hz": crate,
        "n_slots": cslots, "decode_burst": cburst, "prefill_chunk": chunk,
        "total_tokens": sum(r.max_new for r in creqs)}
    report(f"bench_serve,chunked_workload,requests={cn},short={cshort},"
           f"long={clong},chunk={chunk}")
    results["chunked_engines"] = {}
    outs = {}
    for name, pchunk in (("whole_prompt", 0), ("chunked", chunk)):
        scfg = ServeConfig(max_len=cmax_len, cache_dtype="float32",
                           scheduler="continuous", n_slots=cslots,
                           decode_burst=cburst, prefill_chunk=pchunk)
        r, done = run_engine(model, params, creqs, scfg)
        results["chunked_engines"][name] = r
        outs[name] = {rid: c.tokens for rid, c in done.items()}
        report(f"bench_serve,chunked_{name},"
               f"tokens_per_s={r['tokens_per_s']:.1f},"
               f"ttft_p50_ms={r['ttft_p50_ms']:.0f},"
               f"ttft_p99_ms={r['ttft_p99_ms']:.0f},"
               f"tbt_p50_ms={r['tbt_p50_ms']:.1f},"
               f"tbt_p99_ms={r['tbt_p99_ms']:.1f}")
    results["chunked_outputs_equal"] = outs["chunked"] == outs["whole_prompt"]
    ratio = (results["chunked_engines"]["whole_prompt"]["tbt_p99_ms"] /
             max(1e-9, results["chunked_engines"]["chunked"]["tbt_p99_ms"]))
    results["whole_prompt_vs_chunked_tbt_p99"] = ratio
    report(f"bench_serve,chunked,outputs_equal="
           f"{results['chunked_outputs_equal']},"
           f"tbt_p99_whole_over_chunked={ratio:.2f}")
    return results


def _run_obs(report, results, cfg, model, params, smoke,
             trace_out="TRACE_serve.json",
             metrics_out="METRICS_serve.jsonl"):
    """Observability section (DESIGN.md §15): tracer overhead, trace span
    coverage, metrics↔completions reconciliation, numeric telemetry.

    Four measurements:

      overhead — the SAME deterministic workload (every arrival at t=0, so
          the admission sequence is wall-clock-free) served with the tracer
          off and on, interleaved, best-of-3 fresh engines each; CI asserts
          the traced wall is < 5% over the untraced one.
      coverage — one shared ``Obs`` bundle (tracer + metrics JSONL export)
          traces a paged+prefix engine under a chaos plan (forced
          preemptions, eviction storms, pool squeezes, NaN poison) and then
          a speculative engine, so the single ``TRACE_serve.json`` covers
          admit / prefill_chunk / decode_burst / spec_verify / compile /
          preempt / evict / quarantine.
      reconciliation — per-engine (the metrics carry scheduler+family
          labels) the registry's token counter and TTFT/TBT histograms are
          checked against the post-hoc ``Completion`` records: counts and
          sums must match exactly, percentiles to the sketch's ~2.5%
          relative error.
      numerics — a dense fp2fx8 engine with ``telemetry=True`` under a
          NaN-poison plan: softmax-input exponent range pre/post
          max-subtraction, KV scale histogram, int8 saturation, convert
          volume — and every quarantine annotated with the numeric stats
          in force when it fired.
    """
    import os

    from repro.configs.base import ServeConfig
    from repro.obs import Obs
    from repro.serve.chaos import ChaosMonkey, FaultPlan
    from repro.serve.scheduler import Request, SlotPoolEngine

    if smoke:
        n, slots, burst, plen, new = 10, 4, 4, (4, 12), (6, 16)
    else:
        n, slots, burst, plen, new = 24, 6, 4, (4, 16), (8, 32)
    rng = np.random.default_rng(5)
    reqs = [Request(
        rid=i,
        tokens=rng.integers(0, cfg.vocab,
                            int(rng.integers(plen[0], plen[1] + 1))).astype(
                                np.int32),
        max_new=int(rng.integers(new[0], new[1] + 1)), arrival=0.0)
        for i in range(n)]
    max_len = plen[1] + new[1] + 1
    base = dict(max_len=max_len, cache_dtype="float32",
                scheduler="continuous", n_slots=slots, decode_burst=burst)
    obs_res: dict = {"workload": {"requests": n, "n_slots": slots,
                                  "decode_burst": burst,
                                  "prompt_len": list(plen),
                                  "max_new": list(new)}}

    # ---- tracer overhead: off vs on, interleaved, best-of-3 -------------
    def _timed(obs):
        eng = SlotPoolEngine(model, params, ServeConfig(**base), obs=obs)
        eng.prewarm(max(len(r.tokens) for r in reqs))
        t0 = time.perf_counter()
        done = eng.run(reqs)
        return time.perf_counter() - t0, done

    w_off, w_on = [], []
    for _ in range(3):
        w, _d = _timed(None)
        w_off.append(w)
        w, _d = _timed(Obs.enabled())
        w_on.append(w)
    overhead = min(w_on) / max(1e-9, min(w_off)) - 1.0
    obs_res["overhead"] = {"wall_off_s": min(w_off), "wall_on_s": min(w_on),
                           "frac": overhead}
    report(f"bench_serve,obs_overhead,off_s={min(w_off):.3f},"
           f"on_s={min(w_on):.3f},frac={overhead:+.3f}")

    # ---- span coverage + metrics reconciliation (one shared bundle) -----
    if os.path.exists(metrics_out):
        os.remove(metrics_out)  # JSONL export appends
    obs = Obs.enabled(metrics_path=metrics_out, snapshot_every_s=0.25)
    prng = np.random.default_rng(6)
    preqs = make_prefix_workload(cfg, n, 2, prng, 16, 6, new, 10000.0)
    plan = FaultPlan(seed=21, preempt_rate=0.40, evict_storm_rate=0.20,
                     squeeze_rate=0.20, squeeze_frac=0.5, squeeze_hold=2,
                     nan_kv_rate=0.15, max_faults=16)
    scfg_p = ServeConfig(max_len=16 + 6 + new[1] + 1, cache_dtype="float32",
                         scheduler="continuous", n_slots=slots,
                         decode_burst=burst, kv_layout="paged", page_size=8,
                         prefix_cache=True, prefill_chunk=8, audit=True)
    eng_p = SlotPoolEngine(model, params, scfg_p, chaos=ChaosMonkey(plan),
                           obs=obs)
    eng_p.prewarm(max(len(r.tokens) for r in preqs))
    done_p = eng_p.run(preqs)
    sreqs = make_repetitive_workload(cfg, n, np.random.default_rng(7), 6, 4,
                                     4, new, 10000.0)
    scfg_s = ServeConfig(max_len=6 * 4 + 4 + new[1] + 1,
                         cache_dtype="float32", scheduler="spec", draft_k=4,
                         n_slots=slots, decode_burst=burst)
    eng_s = SlotPoolEngine(model, params, scfg_s, obs=obs)
    eng_s.prewarm(max(len(r.tokens) for r in sreqs))
    done_s = eng_s.run(sreqs)
    obs.tracer.write(trace_out)
    kinds = sorted(obs.tracer.span_kinds())
    obs_res["trace"] = {"path": trace_out, "events": len(obs.tracer.events),
                        "span_kinds": kinds}
    report(f"bench_serve,obs_trace,events={len(obs.tracer.events)},"
           f"kinds={'|'.join(kinds)}")

    def _reconcile(scfg, done):
        lab = dict(scheduler=scfg.scheduler, family=cfg.family)
        m = obs.metrics
        tok = m.find("serve.tokens_emitted", **lab).value
        actual = sum(len(c.tokens) for c in done.values())
        ttfts = np.array([c.ttft for c in done.values()
                          if c.ttft is not None])
        gaps = [np.diff(c.delivered_times) for c in done.values()
                if len(c.delivered_times) > 1]
        tbts = np.concatenate(gaps) if gaps else np.zeros(0)
        out = {"metric_tokens_emitted": tok, "completion_tokens": actual,
               "tokens_match": tok == actual}
        for key, vals in (("ttft", ttfts), ("tbt", tbts)):
            h = m.find(f"serve.{key}_s", **lab)
            s = h.summary() if h is not None else {"count": 0, "sum": 0.0,
                                                   "p50": 0.0}
            out[key] = {
                "metric_count": s["count"], "posthoc_count": int(vals.size),
                "metric_sum_s": s["sum"],
                "posthoc_sum_s": float(vals.sum()),
                "metric_p50_s": s["p50"],
                "posthoc_p50_s": float(np.percentile(vals, 50))
                if vals.size else 0.0}
        return out

    obs_res["reconcile"] = {"paged_chaos": _reconcile(scfg_p, done_p),
                            "spec": _reconcile(scfg_s, done_s)}
    with open(metrics_out) as f:
        obs_res["metrics_snapshots"] = sum(1 for _ in f)
    for name, r in obs_res["reconcile"].items():
        report(f"bench_serve,obs_reconcile_{name},"
               f"metric_tokens={r['metric_tokens_emitted']},"
               f"completion_tokens={r['completion_tokens']},"
               f"ttft_n={r['ttft']['metric_count']}/"
               f"{r['ttft']['posthoc_count']},"
               f"tbt_n={r['tbt']['metric_count']}/"
               f"{r['tbt']['posthoc_count']}")

    # ---- hybrid-format numeric telemetry under NaN poison ---------------
    nplan = FaultPlan(seed=22, nan_kv_rate=0.25, max_faults=6)
    scfg_n = ServeConfig(max_len=max_len, cache_dtype="fp2fx8",
                         scheduler="continuous", n_slots=slots,
                         decode_burst=burst, telemetry=True)
    eng_n = SlotPoolEngine(model, params, scfg_n,
                           chaos=ChaosMonkey(nplan), obs=Obs())
    eng_n.prewarm(max(len(r.tokens) for r in reqs))
    done_n = eng_n.run(reqs)
    num = eng_n.obs.numerics.summary()
    obs_res["numerics"] = num
    obs_res["numerics"]["ok"] = sum(1 for c in done_n.values() if c.ok)
    obs_res["numerics"]["quarantines"] = eng_n.stats["quarantines"]
    report(f"bench_serve,obs_numerics,z_max={num.get('z_max')},"
           f"zsub_min={num.get('zsub_min')},"
           f"kv_saturation_rate={num.get('kv_saturation_rate', 0):.4f},"
           f"converts={num.get('converts', 0)},"
           f"quarantine_events={len(num.get('quarantine_events', []))}")
    results["obs"] = obs_res
    return results


def _run_chaos(report, results, cfg, model, params, smoke):
    """Fault-injection robustness section (DESIGN.md §13).

    Each serving config runs the SAME workload twice on fresh engines: once
    fault-free (the oracle) and once with a seeded :class:`FaultPlan` and
    ``audit=True``.  The contract under test:

      definite   — every submitted rid ends with exactly one Completion
                   (finished, cancelled, or a structured failure) — no
                   hangs, no silently dropped requests.
      identical  — every ok completion whose KV was never poisoned emits
                   tokens identical to the fault-free run (preemptions,
                   evictions, squeezes, junk drafts, and stragglers are
                   invisible to the arithmetic).  Poisoned rids recover
                   through quarantine -> re-prefill and usually ALSO match
                   (reported separately as ``poisoned_match``) but the
                   strict gate excludes them: the fp32 retry rung of the
                   degradation ladder is allowed to differ.
      audited    — pool/trie refcounts recomputed from live slots + trie
                   edges at every admission/finish/preemption checkpoint;
                   any drift raises AuditError and fails the bench.

    Every request arrives at t=0 with no deadline, so the scheduling
    sequence is wall-clock-free and a fixed seed replays identical faults.
    """
    from repro.configs.base import ServeConfig
    from repro.serve.chaos import ChaosMonkey, FaultPlan
    from repro.serve.scheduler import Request, SlotPoolEngine

    if smoke:
        n, slots, burst, head, tail, new = 10, 4, 4, 16, (3, 6), (6, 16)
    else:
        n, slots, burst, head, tail, new = 20, 6, 4, 24, (4, 10), (8, 32)

    def prefix_reqs():
        # two shared 'system prompt' heads + unique tails: populates the
        # radix trie (so eviction storms have something to evict) while
        # keeping prompts short; all-zero arrivals for determinism
        r = np.random.default_rng(7)
        heads = [r.integers(0, cfg.vocab, head).astype(np.int32)
                 for _ in range(2)]
        return [Request(
            rid=i,
            tokens=np.concatenate(
                [heads[i % 2],
                 r.integers(0, cfg.vocab,
                            int(r.integers(tail[0], tail[1] + 1))).astype(
                                np.int32)]),
            max_new=int(r.integers(new[0], new[1] + 1)),
            arrival=0.0) for i in range(n)]

    def repetitive_reqs():
        # tiled-motif prompts keep the n-gram drafter hot so the
        # drafter-desync fault actually has drafts to corrupt
        r = np.random.default_rng(8)
        reqs = []
        for i in range(n):
            motif = r.integers(0, cfg.vocab, 6).astype(np.int32)
            toks = np.concatenate(
                [np.tile(motif, 4),
                 r.integers(0, cfg.vocab, 4).astype(np.int32)])
            reqs.append(Request(rid=i, tokens=toks,
                                max_new=int(r.integers(new[0], new[1] + 1)),
                                arrival=0.0))
        return reqs

    configs = [
        ("dense_fp32", prefix_reqs,
         dict(cache_dtype="float32", scheduler="continuous"),
         FaultPlan(seed=11, preempt_rate=0.15, nan_kv_rate=0.10,
                   cancel_rate=0.04, straggle_rate=0.10, straggle_s=0.01,
                   max_faults=6)),
        # fp2fx8: int8 raws cannot hold a NaN, so the poison lands in the
        # fp32 scale rows — the hybrid-format silent-corruption shape the
        # numeric guards exist for
        ("dense_fp2fx8", prefix_reqs,
         dict(cache_dtype="fp2fx8", scheduler="continuous"),
         FaultPlan(seed=12, preempt_rate=0.10, nan_kv_rate=0.15,
                   max_faults=6)),
        ("paged_prefix", prefix_reqs,
         dict(cache_dtype="float32", scheduler="continuous",
              kv_layout="paged", page_size=8, prefix_cache=True),
         FaultPlan(seed=13, preempt_rate=0.10, evict_storm_rate=0.15,
                   squeeze_rate=0.15, squeeze_frac=0.5, squeeze_hold=2,
                   nan_kv_rate=0.10, cancel_rate=0.04, max_faults=8)),
        ("spec", repetitive_reqs,
         dict(cache_dtype="float32", scheduler="spec", draft_k=4),
         FaultPlan(seed=14, drafter_junk_rate=0.4, preempt_rate=0.10,
                   cancel_rate=0.04, max_faults=8)),
    ]

    def _serve(scfg, reqs, plan=None):
        monkey = ChaosMonkey(plan) if plan is not None else None
        eng = SlotPoolEngine(model, params, scfg, chaos=monkey)
        t_w = time.perf_counter()
        eng.prewarm(max(len(r.tokens) for r in reqs))
        warmup = time.perf_counter() - t_w
        t0 = time.perf_counter()
        done = eng.run(reqs)
        return done, eng, monkey, time.perf_counter() - t0, warmup

    results["chaos"] = {
        "workload": {"requests": n, "n_slots": slots, "decode_burst": burst,
                     "prefix_head": head, "tail_len": list(tail),
                     "max_new": list(new)},
        "configs": {}}
    report(f"bench_serve,chaos_workload,requests={n},slots={slots},"
           f"head={head},tail={tail}")
    for name, mk, kw, plan in configs:
        reqs = mk()
        max_len = max(len(r.tokens) + r.max_new for r in reqs) + 1
        scfg = ServeConfig(max_len=max_len, n_slots=slots,
                           decode_burst=burst, audit=True, **kw)
        base_done, _, _, _, _ = _serve(scfg, reqs)
        done, eng, monkey, wall, warmup = _serve(scfg, reqs, plan)
        rids = {r.rid for r in reqs}
        definite = set(done) == rids
        oks = {rid: c for rid, c in done.items() if c.ok}
        clean = {rid: c for rid, c in oks.items()
                 if rid not in monkey.faulted_rids}
        match = all(c.tokens == base_done[rid].tokens
                    for rid, c in clean.items())
        poisoned = {rid: c for rid, c in oks.items()
                    if rid in monkey.faulted_rids}
        poisoned_match = all(c.tokens == base_done[rid].tokens
                             for rid, c in poisoned.items())
        st = eng.stats
        r = {"requests": n, "ok": len(oks),
             "cancelled": sum(1 for c in done.values() if c.cancelled),
             "failed": sum(1 for c in done.values()
                           if c.failure is not None),
             "definite": definite, "outputs_match": match,
             "poisoned": len(poisoned), "poisoned_match": poisoned_match,
             "faults": monkey.summary(), "audits": st["audits"],
             "quarantines": st["quarantines"],
             "fp32_retries": st["fp32_retries"],
             "preemptions": st["preemptions"], "wall_s": wall,
             "warmup_s": warmup}
        results["chaos"]["configs"][name] = r
        report(f"bench_serve,chaos_{name},ok={len(oks)}/{n},"
               f"cancelled={r['cancelled']},failed={r['failed']},"
               f"faults={monkey.n_faults},quarantines={r['quarantines']},"
               f"definite={definite},outputs_match={match},"
               f"audits={r['audits']}")
    return results


if __name__ == "__main__":
    import argparse
    import json
    import os

    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="BENCH_serve.json")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: smaller workload, shorter horizons")
    ap.add_argument("--prefix-only", action="store_true",
                    help="run only the shared-prefix (paged vs dense) "
                         "section, skipping the Poisson scheduler comparison")
    ap.add_argument("--spec-only", action="store_true",
                    help="run only the repetitive-workload (speculative vs "
                         "continuous) section")
    ap.add_argument("--chunked-only", action="store_true",
                    help="run only the mixed long/short-prompt (chunked vs "
                         "whole-prompt prefill) section")
    ap.add_argument("--chaos", action="store_true",
                    help="run only the fault-injection robustness section "
                         "(seeded FaultPlan per serving config, audits on)")
    ap.add_argument("--trace", action="store_true",
                    help="run only the observability section: tracer "
                         "overhead, Perfetto trace + metrics JSONL export, "
                         "metrics reconciliation, fp2fx8 numeric telemetry")
    ap.add_argument("--trace-out", default="TRACE_serve.json",
                    help="Chrome trace-event JSON output path (--trace)")
    ap.add_argument("--metrics-out", default="METRICS_serve.jsonl",
                    help="metrics JSONL snapshot output path (--trace)")
    ap.add_argument("--merge", action="store_true",
                    help="update an existing --json file in place (a "
                         "section-only run keeps the other sections' "
                         "results, so each section can be measured in its "
                         "own fresh process)")
    ap.add_argument("--xla-profile", default=None, metavar="DIR",
                    help="jax.profiler capture window around the bench "
                         "(xplane + trace.json.gz under DIR)")
    ap.add_argument("--ledger", default="auto",
                    help="ledger path ('auto' = next to --json, 'none' to "
                         "skip the append)")
    args = ap.parse_args()
    from repro.obs import ledger, profile
    with profile.xla_profile(args.xla_profile):
        res = run(print, smoke=args.smoke, prefix_only=args.prefix_only,
                  spec_only=args.spec_only, chunked_only=args.chunked_only,
                  chaos_only=args.chaos, obs_only=args.trace,
                  trace_out=args.trace_out, metrics_out=args.metrics_out)
    out: dict = {}
    if args.merge and os.path.exists(args.json):
        with open(args.json) as f:
            out = json.load(f)
    out.update(res)
    out.pop("provenance", None)  # re-stamped below: merged result is new
    ledger.finalize(args.json, "serve", out,
                    mode="smoke" if args.smoke else "full",
                    ledger_path=None if args.ledger == "none"
                    else args.ledger)
    print(f"# wrote {args.json}")
