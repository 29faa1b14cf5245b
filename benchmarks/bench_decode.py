"""Decode benchmarks: attention-op latency + end-to-end decode throughput.

Two sections, both emitted as text lines via ``report`` AND returned as a
dict (``benchmarks/run.py`` and the ``__main__`` entry persist it to
``BENCH_decode.json``):

  op  — masked Sq=1 decode attention across the modes: unfused, chunked,
        monolithic fused kernel, split-K decode kernel, and split-K over the
        fp2fx8-quantized (int8 + per-head scale) KV cache.  The Sk=2048
        masked shape is the acceptance case the split-K kernel must handle
        without falling back.
  e2e — ``serve.engine.generate`` tokens/sec on a tiny model: the per-token
        host dispatch loop vs the on-device ``lax.scan`` loop, dense vs
        fp2fx8 cache.  This measures exactly what the scanned loop exists
        for: killing the per-token Python round-trip.
  numerics — hybrid-format telemetry (``ServeConfig.telemetry``, DESIGN.md
        §15) from a tiny slot-pool serve, fp32 vs fp2fx8 cache: the
        realized softmax-input exponent range pre/post max-subtraction (the
        quantity the paper's hybrid-format argument rests on), the fp2fx8
        KV scale histogram + int8 saturation rate, and the §14
        format-boundary convert volume.

Absolute numbers are CPU times (Pallas in interpreter mode; on TPU it is the
compiled path) — read the relative trends.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core.hyft import HYFT32
from repro.kernels import ops
from repro.models.attention import (chunked_hyft_attention, fp2fx8_quantize,
                                    unfused_attention)

F32 = jnp.float32
OP_SHAPES = [  # (B, Hq, Hkv, Sk, D, valid_len)
    (4, 8, 4, 512, 64, 300),
    (1, 16, 8, 2048, 64, 1500),
]


def _time(fn, *args, iters=10):
    jax.block_until_ready(fn(*args))  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def _op_section(report, shapes, iters):
    rows = []
    key = jax.random.PRNGKey(0)
    for B, Hq, Hkv, Sk, D, valid in shapes:
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (B, Hq, 1, D), F32)
        k = jax.random.normal(ks[1], (B, Hkv, Sk, D), F32)
        v = jax.random.normal(ks[2], (B, Hkv, Sk, D), F32)
        mask = (jnp.arange(Sk)[None, :] < valid).astype(F32).repeat(B, 0)
        kr, ksc = fp2fx8_quantize(k)
        vr, vsc = fp2fx8_quantize(v)

        modes = {
            "unfused": jax.jit(lambda q, k, v, m: unfused_attention(
                q, k, v, "hyft32", causal=False, kv_len_mask=m > 0)),
            "kernel": jax.jit(lambda q, k, v, m: ops.hyft_attention(
                q, k, v, HYFT32, causal=False, kv_len_mask=m)),
            "chunked": jax.jit(lambda q, k, v, m: chunked_hyft_attention(
                q, k, v, HYFT32, False, min(512, Sk), 0, m)),
            "splitk": jax.jit(lambda q, k, v, m: ops.hyft_decode_attention(
                q, k, v, HYFT32, kv_len_mask=m)),
            "splitk_fp2fx8": jax.jit(
                lambda q, kr, vr, m, ksc=ksc, vsc=vsc:
                ops.hyft_decode_attention(q, kr, vr, HYFT32, kv_len_mask=m,
                                          k_scale=ksc, v_scale=vsc)),
        }
        shape = f"B{B}xH{Hq}xS{Sk}(valid={valid})xD{D}"
        base = None
        for name, fn in modes.items():
            args = (q, kr, vr, mask) if name == "splitk_fp2fx8" else (q, k, v, mask)
            us = _time(fn, *args, iters=iters)
            base = us if name == "unfused" else base
            rows.append({"mode": name, "shape": shape, "us_per_step": us,
                         "vs_unfused": us / base})
            report(f"bench_decode,{name},shape={shape},us_per_step={us:.1f},"
                   f"vs_unfused={us / base:.2f}")
    return rows


def _e2e_section(report, max_new, batch):
    from repro.configs import get_config, smoke_config
    from repro.configs.base import ServeConfig
    from repro.models import build_model
    from repro.models.layers import unbox
    from repro.serve.engine import generate

    cfg = smoke_config(get_config("olmo-1b")).with_(
        softmax_impl="hyft16", vocab=128, n_layers=2)
    model = build_model(cfg)
    params = unbox(model.init(jax.random.PRNGKey(0)))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, 8), 0,
                                cfg.vocab, jnp.int32)
    b = {"tokens": tokens}

    rows = []
    for loop, cache_dtype in (("host", "float32"), ("scan", "float32"),
                              ("scan", "fp2fx8")):
        scfg = ServeConfig(max_len=8 + max_new + 1, cache_dtype=cache_dtype,
                           decode_loop=loop)
        out = generate(model, params, b, scfg, max_new=max_new)  # compile
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        out = generate(model, params, b, scfg, max_new=max_new)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        tps = batch * max_new / dt
        rows.append({"loop": loop, "cache": cache_dtype,
                     "tokens_per_s": tps,
                     "us_per_token": dt / (batch * max_new) * 1e6})
        report(f"bench_decode_e2e,loop={loop},cache={cache_dtype},"
               f"tokens_per_s={tps:.1f},us_per_token={dt / (batch * max_new) * 1e6:.1f}")
    return rows


def _numerics_section(report, batch, max_new):
    """Serve a tiny workload with ``telemetry=True`` and report the
    per-burst device-side numeric stats the hybrid-format design rests on.
    The fp32 and fp2fx8 engines see the same prompts, so the z-range rows
    are directly comparable and the fp2fx8 row adds the KV-quantization
    telemetry (scale spread, saturation, convert volume)."""
    import numpy as np

    from repro.configs import get_config, smoke_config
    from repro.configs.base import ServeConfig
    from repro.models import build_model
    from repro.models.layers import unbox
    from repro.serve.scheduler import Request, SlotPoolEngine

    cfg = smoke_config(get_config("olmo-1b")).with_(
        softmax_impl="hyft16", vocab=128, n_layers=2)
    model = build_model(cfg)
    params = unbox(model.init(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(9)
    reqs = [Request(rid=i,
                    tokens=rng.integers(0, cfg.vocab,
                                        int(rng.integers(4, 12))).astype(
                                            np.int32),
                    max_new=max_new, arrival=0.0) for i in range(batch)]
    rows = {}
    for cache_dtype in ("float32", "fp2fx8"):
        scfg = ServeConfig(max_len=12 + max_new + 1, cache_dtype=cache_dtype,
                           scheduler="continuous", n_slots=batch,
                           decode_burst=4, telemetry=True)
        eng = SlotPoolEngine(model, params, scfg)
        eng.prewarm(max(len(r.tokens) for r in reqs))
        eng.run(reqs)
        s = eng.obs.numerics.summary()
        rows[cache_dtype] = s
        extra = (f",kv_saturation_rate={s.get('kv_saturation_rate', 0):.4f},"
                 f"kv_scale_bins={len(s.get('kv_scale_hist', {}))}"
                 if cache_dtype == "fp2fx8" else "")
        report(f"bench_decode_numerics,cache={cache_dtype},"
               f"z_max={s['z_max']:.2f},z_min={s['z_min']:.2f},"
               f"zsub_min={s['zsub_min']:.2f},"
               f"converts={s.get('converts', 0)}{extra}")
    return rows


def run(report, quick: bool = False):
    """Run all sections; returns the machine-readable results dict."""
    shapes = OP_SHAPES[1:] if quick else OP_SHAPES  # keep the Sk=2048 case
    results = {
        "op": _op_section(report, shapes, iters=3 if quick else 10),
        "e2e": _e2e_section(report, max_new=16 if quick else 32,
                            batch=2 if quick else 4),
        "numerics": _numerics_section(report, batch=2 if quick else 4,
                                      max_new=8 if quick else 16),
    }
    return results


if __name__ == "__main__":
    import argparse

    from repro.obs import ledger, profile

    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="BENCH_decode.json")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: fewer iters, Sk=2048 op shape only")
    ap.add_argument("--xla-profile", default=None, metavar="DIR",
                    help="jax.profiler capture window around the bench "
                         "(xplane + trace.json.gz under DIR)")
    ap.add_argument("--ledger", default="auto",
                    help="ledger path ('auto' = next to --json, 'none' to "
                         "skip the append)")
    args = ap.parse_args()
    with profile.xla_profile(args.xla_profile):
        res = run(print, quick=args.quick)
    ledger.finalize(args.json, "decode", res,
                    mode="smoke" if args.quick else "full",
                    ledger_path=None if args.ledger == "none"
                    else args.ledger)
    print(f"# wrote {args.json}")
