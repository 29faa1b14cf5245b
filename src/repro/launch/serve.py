"""Serving launcher CLI: lockstep batch decode or continuous batching.

  # uniform rectangular batch, one on-device lax.scan (PR 2 fast path)
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
      --batch 4 --prefill 16 --max-new 16 --softmax hyft16

  # continuous batching: ragged prompts, slot-pool KV cache, EOS early-exit
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
      --scheduler continuous --n-slots 4 --batch 8 --max-new 24 --eos-id 7

  # speculative decoding: n-gram self-drafting + one-call verify bursts
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
      --scheduler spec --draft-k 4 --n-slots 4 --batch 8 --max-new 24
"""
import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--softmax", default="hyft16")
    ap.add_argument("--attn-mode", default=None,
                    choices=["unfused", "chunked", "kernel"],
                    help="attention path; 'kernel' = split-K fused Pallas decode")
    ap.add_argument("--cache-dtype", default="float32",
                    help="KV cache storage: jnp dtype name or 'fp2fx8' "
                         "(int8 FP2FX raws + per-head scales)")
    ap.add_argument("--decode-loop", default="scan",
                    choices=["scan", "host"],
                    help="'scan' = one on-device lax.scan; 'host' = "
                         "per-token jitted steps (debug)")
    ap.add_argument("--scheduler", default="lockstep",
                    choices=["lockstep", "continuous", "spec"],
                    help="'continuous' = slot-pool continuous batching with "
                         "ragged prompts and EOS early-exit; 'lockstep' = "
                         "one rectangular batch (PR 2 fast path); 'spec' = "
                         "continuous admission + speculative decode bursts "
                         "(draft K tokens, verify in one model call)")
    ap.add_argument("--spec-mode", default="ngram",
                    choices=["ngram", "model"],
                    help="drafter for --scheduler spec: 'ngram' = "
                         "deterministic prompt-lookup self-drafting; "
                         "'model' = a small zoo model (--draft-model)")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="draft tokens verified per slot per spec step")
    ap.add_argument("--ngram-max", type=int, default=3,
                    help="longest trailing n-gram the lookup drafter matches")
    ap.add_argument("--draft-model", default=None,
                    help="zoo arch for --spec-mode model (random init: a "
                         "demo drafter — acceptance floor is chance)")
    ap.add_argument("--n-slots", type=int, default=4,
                    help="slot-pool size for --scheduler continuous")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="stop token: a continuous-batching slot that emits "
                         "it is freed immediately")
    ap.add_argument("--decode-burst", type=int, default=8,
                    help="jitted decode steps between admission checks")
    ap.add_argument("--kv-layout", default="dense",
                    choices=["dense", "paged"],
                    help="'paged' = fixed-size KV pages from a global pool "
                         "with per-slot block tables (attention families; "
                         "decode appends pages on demand, exhaustion "
                         "preempts the latest-arrival slot)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page for --kv-layout paged")
    ap.add_argument("--n-pages", type=int, default=0,
                    help="usable pages in the pool (0 = auto: n_slots * "
                         "ceil(max_len / page_size))")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix-trie prompt prefix cache: admissions "
                         "sharing a cached prefix reuse its pages and skip "
                         "prefill for the cached tokens (paged only)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="max prompt tokens per prefill_chunk call (0 = "
                         "whole prompt in one call): long prompts split "
                         "into chunks interleaved with decode bursts, so "
                         "in-flight decode never stalls longer than one "
                         "chunk")
    ap.add_argument("--pack-prefill", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="pack every prefilling slot into one bucketed "
                         "chunk call (--no-pack-prefill = one prompt at a "
                         "time in arrival order, an ablation knob)")
    ap.add_argument("--audit", action="store_true",
                    help="recompute page-pool/radix-trie refcounts at every "
                         "admission/finish/preemption checkpoint and fail "
                         "loudly on drift (DESIGN.md §13)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bounded admission queue: arrivals past this many "
                         "waiting requests fail with reason 'queue_full' "
                         "(0 = unbounded)")
    ap.add_argument("--max-retries", type=int, default=32,
                    help="per-request requeue budget (preemptions + numeric "
                         "quarantines) before a structured "
                         "'retries_exhausted' failure")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request TTL in seconds: a request unfinished "
                         "at its deadline fails with reason 'deadline' and "
                         "frees its slot/pages within one burst")
    ap.add_argument("--batch", type=int, default=4,
                    help="lockstep batch size / continuous request count")
    ap.add_argument("--prefill", type=int, default=16,
                    help="prompt length (continuous: the maximum; prompts "
                         "are ragged in [prefill//2, prefill])")
    ap.add_argument("--max-new", type=int, default=16,
                    help="decode horizon (continuous: the maximum; horizons "
                         "are ragged in [max_new//2, max_new])")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0,
                    help="sampling: keep only the k highest logits "
                         "(0 = off; temperature > 0 only)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling: keep the smallest token set "
                         "with probability mass >= p (1.0 = off)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON of the serve "
                         "(spans: admit/prefill/burst/spec-verify/compile/"
                         "preempt/evict/quarantine) to PATH — load it in "
                         "ui.perfetto.dev (DESIGN.md §15)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="append periodic metrics-registry JSONL snapshots "
                         "to PATH and print the end-of-run metrics report")
    ap.add_argument("--xla-profile", default=None, metavar="DIR",
                    help="jax.profiler programmatic capture around the "
                         "serve: xplane + trace.json.gz artifacts land "
                         "under DIR (view with tensorboard/xprof; "
                         "DESIGN.md §16)")
    ap.add_argument("--telemetry", action="store_true",
                    help="fold per-burst device-side numeric stats (softmax "
                         "exponent range, fp2fx8 scale histogram, int8 "
                         "saturation) into the burst outputs and print the "
                         "numerics summary (retraces the burst)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config, smoke_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.configs.base import ServeConfig
    from repro.models import build_model
    from repro.models.layers import unbox
    from repro.serve.engine import generate
    from repro.serve.scheduler import Request, SlotPoolEngine

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    cfg = cfg.with_(softmax_impl=args.softmax)
    model = build_model(cfg)
    root = jax.random.PRNGKey(args.seed)
    init_key, data_key, sample_key = jax.random.split(root, 3)
    params = unbox(model.init(init_key))

    scfg = ServeConfig(batch=args.batch, prefill_len=args.prefill,
                       max_len=args.prefill + args.max_new + 1,
                       cache_dtype=args.cache_dtype,
                       temperature=args.temperature,
                       top_k=args.top_k,
                       top_p=args.top_p,
                       attn_mode=args.attn_mode,
                       decode_loop=args.decode_loop,
                       scheduler=args.scheduler,
                       n_slots=args.n_slots,
                       eos_id=args.eos_id,
                       decode_burst=args.decode_burst,
                       kv_layout=args.kv_layout,
                       page_size=args.page_size,
                       n_pages=args.n_pages,
                       prefix_cache=args.prefix_cache,
                       prefill_chunk=args.prefill_chunk,
                       pack_prefill=args.pack_prefill,
                       spec_mode=args.spec_mode,
                       draft_k=args.draft_k,
                       ngram_max=args.ngram_max,
                       draft_model=args.draft_model,
                       audit=args.audit,
                       max_queue=args.max_queue,
                       max_retries=args.max_retries,
                       telemetry=args.telemetry)

    from repro.obs import Obs
    from repro.obs.profile import xla_profile
    obs = None
    if args.trace or args.metrics_out:
        obs = Obs.enabled(metrics_path=args.metrics_out)
        obs.tracer.enabled = args.trace is not None

    # the paged layout, prefix cache, spec decoding, and chunked prefill
    # live in the slot-pool scheduler, so those flags route through it even
    # under --scheduler lockstep (the rectangular generate path below is
    # dense-only, non-speculative, and would silently ignore them)
    if (args.scheduler in ("continuous", "spec")
            or args.kv_layout != "dense" or args.prefix_cache
            or args.prefill_chunk > 0):
        rng = np.random.default_rng(args.seed)
        reqs = []
        for rid in range(args.batch):
            plen = int(rng.integers(max(1, args.prefill // 2),
                                    args.prefill + 1))
            frames = None
            if cfg.family == "encdec":
                frames = np.asarray(jax.random.normal(
                    jax.random.fold_in(data_key, rid),
                    (cfg.frontend_len, cfg.frontend_dim)))
            reqs.append(Request(
                rid=rid,
                tokens=rng.integers(0, cfg.vocab, plen).astype(np.int32),
                max_new=int(rng.integers(max(1, args.max_new // 2),
                                         args.max_new + 1)),
                frames=frames, deadline=args.deadline))
        eng = SlotPoolEngine(model, params, scfg, key=sample_key, obs=obs)
        if obs is not None:
            # compile (and §16 cost-record) every executable up front, so
            # the trace separates compile spans from steady-state serving
            # and the cost book has rows for the roofline counter tracks
            eng.prewarm(max(len(r.tokens) for r in reqs))
        try:
            with xla_profile(args.xla_profile):
                done = eng.run(reqs)
        except KeyboardInterrupt:
            # graceful drain: in-flight slots free, every unfinished
            # request gets a partial Completion with cancelled=True —
            # no traceback, no lost work (DESIGN.md §13)
            done = eng.shutdown()
            print("\ninterrupted: drained "
                  f"{sum(1 for c in done.values() if c.cancelled)} "
                  "in-flight/queued requests as cancelled")
        for rid in sorted(done):
            c = done[rid]
            tag = ("" if c.ok else " CANCELLED" if c.cancelled
                   else f" FAILED({c.failure.reason})")
            print(f"[{rid}] prompt={c.prompt_len} new={len(c.tokens)}"
                  f"{tag} {c.tokens}")
        if args.scheduler == "spec":
            st = eng.stats
            acc = st["accepted_tokens"] / max(1, st["draft_tokens"])
            print(f"spec: steps={st['spec_steps']} "
                  f"drafted={st['draft_tokens']} "
                  f"accepted={st['accepted_tokens']} (rate {acc:.2f}) "
                  f"tokens/model-call="
                  f"{st['tokens_emitted'] / max(1, st['model_calls']):.2f}")
        if args.trace:
            eng.obs.tracer.write(args.trace)
            print(f"# wrote trace {args.trace} "
                  f"({len(eng.obs.tracer.events)} events; load in "
                  f"ui.perfetto.dev)")
        if args.metrics_out:
            print(eng.obs.metrics.report())
            print(f"# wrote metrics {args.metrics_out}")
        if args.telemetry:
            print(f"numerics: {eng.obs.numerics.summary()}")
        if args.xla_profile:
            print(f"# wrote xla profile under {args.xla_profile} "
                  "(xplane + trace.json.gz; view with xprof/tensorboard)")
        return

    batch = {"tokens": jax.random.randint(
        data_key, (args.batch, args.prefill), 0, cfg.vocab, jnp.int32)}
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            data_key, (args.batch, cfg.frontend_len, cfg.frontend_dim))
    # the sampling key derives from --seed (it used to be dropped, so
    # --temperature runs always sampled with the hardcoded PRNGKey(0))
    with xla_profile(args.xla_profile):
        out = generate(model, params, batch, scfg, max_new=args.max_new,
                       key=sample_key,
                       tracer=obs.tracer if obs is not None else None,
                       profile=obs.profile if obs is not None else None)
        jax.block_until_ready(out)
    for i, row in enumerate(out.tolist()):
        print(f"[{i}] {row}")
    if args.xla_profile:
        print(f"# wrote xla profile under {args.xla_profile} "
              "(xplane + trace.json.gz; view with xprof/tensorboard)")
    if args.trace:
        obs.tracer.write(args.trace)
        print(f"# wrote trace {args.trace} ({len(obs.tracer.events)} "
              f"events; load in ui.perfetto.dev)")
    if args.metrics_out:
        print("# --metrics-out: serve.* metrics live in the slot-pool "
              "scheduler; rerun with --scheduler continuous|spec")


if __name__ == "__main__":
    main()
