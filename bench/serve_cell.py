"""A serving cell: the program's slot-pool engine under an open-loop stream.

Set-up makes the weights from the seed, builds the engine as the
configuration describes it, compiles every executable the cell's traffic
can reach (``prewarm`` at the mix's longest prompt) and serves two short
warm-up requests, so that no program compiles inside the window.  The
window is one ``SlotPoolEngine.run`` over the requests due in
``seconds``; the run goes on until every request has its answer.

Delivery times are taken from outside the program: the engine stamps each
token with the time its call *began*, so the harness wraps the two calls
that produce tokens (a prefill chunk and a decode burst) and maps every
stamp to the end of the call that began at it, when the host holds the
token.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import costs, traffic


def build(m: dict, serve: dict, seed: int):
    """The program's engine over the benchmark's weights, as configured."""
    import jax
    from repro.configs import get_config
    from repro.configs.base import ServeConfig
    from repro.models import build_model
    from repro.models.layers import unbox
    from repro.obs import Obs
    from repro.obs.trace import Tracer
    from repro.serve.scheduler import SlotPoolEngine

    from bench import weights
    cfg = model_config(get_config, m, serve)
    model = build_model(cfg)
    params = weights.make(m, serve["param_dtype"], seed)
    weights.check_layout(params, jax.eval_shape(
        lambda: unbox(model.init(jax.random.PRNGKey(0)))))
    scfg = ServeConfig(
        max_len=serve["max_len"], cache_dtype=serve["cache_dtype"],
        attn_mode=serve["attn_mode"], scheduler=serve["scheduler"],
        n_slots=serve["n_slots"], kv_layout=serve["kv_layout"],
        page_size=serve["page_size"], prefill_chunk=serve["prefill_chunk"],
        decode_burst=serve["decode_burst"])
    eng = SlotPoolEngine(model, params, scfg, key=weights.key_of(seed, 7),
                         obs=Obs(tracer=Tracer(enabled=True)))
    return eng, params


SIZE_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_head",
             "d_ff", "vocab", "act", "mlp_gated", "norm", "rope_theta",
             "max_seq", "tie_embeddings", "softmax_impl")


def model_config(get_config, m: dict, dtypes: dict):
    """The program's model configuration with every size taken from the
    configuration file ``m`` (the file is what runs)."""
    return get_config(m["model"]).with_(
        **{k: m[k] for k in SIZE_KEYS}, param_dtype=dtypes["param_dtype"],
        compute_dtype=dtypes["compute_dtype"], attn_mode=dtypes["attn_mode"])


class Delivery:
    """Wraps the engine's token-producing calls and records, for each call,
    the stamp it was given and the time it returned (both on the engine's
    run clock).  Each call is also a ``bench.<call>`` span on the
    profiler's clock, which the trace reduction reads."""

    def __init__(self, eng):
        self.eng = eng
        self.end_of: dict = {}        # stamp -> end of the call
        self.calls: list = []         # (kind, stamp, end)
        for name in ("_prefill_step", "burst"):
            setattr(eng, name, self._wrap(name, getattr(eng, name)))

    def unwrap(self) -> None:
        for name in ("_prefill_step", "burst"):
            delattr(self.eng, name)

    def _wrap(self, kind, fn):
        import jax
        eng = self.eng

        def timed(now):
            with jax.profiler.TraceAnnotation("bench." + kind.lstrip("_")):
                fn(now)
            end = time.perf_counter() - eng._t0
            if now in self.end_of:
                raise RuntimeError(f"two calls stamped {now}")
            self.end_of[now] = end
            self.calls.append((kind, now, end))
        return timed

    def times(self, stamps) -> list:
        return [self.end_of[s] for s in stamps]


def warm_up(eng, vocab: int, seed: int) -> None:
    """Serve two short requests so that the host-side operations of a
    finished prefill and of a burst are compiled before the window."""
    from repro.serve.scheduler import Request
    g = traffic.rng(seed, 2)
    burst = eng.scfg.decode_burst
    eng.run([Request(rid=-1 - i, tokens=g.integers(0, vocab, 16 + i,
                                                   dtype=np.int32),
                     max_new=2 * burst + 1) for i in range(2)])


def peak_bytes(devices):
    """The peak device memory of the fullest of ``devices``: its buffers'
    peak plus the peak the runtime reserved for programs' temporaries,
    which ``peak_bytes_in_use`` leaves out; None where the backend keeps
    no statistics."""
    peaks = [s["peak_bytes_in_use"] + s.get("peak_bytes_reserved", 0)
             for s in (d.memory_stats() or {} for d in devices)
             if "peak_bytes_in_use" in s]
    return max(peaks) if peaks else None


def percentile(values, q) -> float:
    """The ``q``th percentile, linear between order statistics; a missing
    value (a failed request) is +inf."""
    if not values:
        return float("inf")
    return float(np.percentile(np.asarray(values, float), q))


def prepare(m: dict, mix: dict, seed: int, clock=time.perf_counter):
    """Set-up: the engine over the seed's weights, every executable the
    mix can reach compiled, and the host-side operations warmed.  Returns
    the engine, the weights and the ``clock`` reading at each phase's end."""
    at = {"imported": clock()}
    eng, params = build(m, m["serve"], seed)
    at["built"] = clock()
    eng.prewarm(mix["prompt_len"]["hi"])
    at["prewarmed"] = clock()
    warm_up(eng, m["vocab"], seed)
    at["warmed"] = clock()
    return eng, params, at


def window(eng, reqs, seconds: float, trace_dir=None, on_open=None) -> dict:
    """Serve ``reqs`` (due within ``seconds``) through one
    ``SlotPoolEngine.run`` and measure it from the delivery times."""
    import jax
    from repro.serve.scheduler import Request

    before = dict(eng.stats)
    n_events = len(eng.obs.tracer.events)
    deliv = Delivery(eng)
    if on_open is not None:
        on_open()
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    t_open = time.perf_counter()
    done = eng.run([Request(rid=r.rid, tokens=r.prompt, max_new=r.max_new,
                            arrival=r.arrival) for r in reqs])
    run_s = time.perf_counter() - t_open
    if trace_dir:
        jax.profiler.stop_trace()
    deliv.unwrap()
    counters = {k: v - before.get(k, 0) for k, v in eng.stats.items()}
    spans = [e for e in eng.obs.tracer.events[n_events:]
             if e.get("ph") == "X"]

    ttft, tbt, failed, in_window, backlog = [], [], 0, 0, 0
    delivered, last = 0, 0.0
    served = {}
    for r in reqs:
        c = done[r.rid]
        if not c.ok or len(c.tokens) != r.max_new:
            failed += 1
            ttft.append(float("inf"))
            tbt.append(float("inf"))
            continue
        t = deliv.times(c.token_times)
        ttft.append(t[0] - r.arrival)
        tbt.extend(np.diff(t).tolist())
        in_window += sum(1 for x in t if x <= seconds)
        backlog += t[-1] > seconds
        delivered += len(t)
        last = max(last, t[-1])
        served[r.rid] = list(c.tokens)
    # the rate of all the window's work: every token of the requests due
    # in it, over the time until the last of them was delivered
    e2e = {"ttft_p95_ms": 1e3 * percentile(ttft, 95),
           "tbt_p95_ms": 1e3 * percentile(tbt, 95),
           "output_tokens_per_s": delivered / last if last else 0.0}
    side = {"ttft_p50_ms": 1e3 * percentile(ttft, 50),
            "tokens_in_window_per_s": in_window / seconds,
            "tbt_p50_ms": 1e3 * percentile(tbt, 50),
            "requests": len(reqs), "ttft_samples": len(ttft),
            "tbt_samples": len(tbt), "run_s": run_s,
            "drain_s": max(0.0, run_s - seconds),
            "unfinished_at_close": backlog,
            "compiles_in_window": sum(1 for e in spans
                                      if e["name"] == "compile"),
            "traced_s": run_s}
    return {"e2e": e2e, "side": side, "failed": failed, "served": served,
            "counters": counters, "spans": spans, "calls": deliv.calls}


def run(cfgfile: dict, mix: dict, seed: int, seconds: float, trace_dir,
        clock):
    """One run of a serving cell.  ``clock`` gives seconds since the
    process started.  Returns the result record."""
    import jax

    m = cfgfile
    eng, params, at = prepare(m, mix, seed, clock)
    reqs = traffic.serve_requests(mix, seed, seconds, m["vocab"])
    setup = {}
    w = window(eng, reqs, seconds, trace_dir,
               on_open=lambda: setup.setdefault("s", clock()))
    w["e2e"]["setup_s"] = setup["s"]
    w["side"]["setup_phases_s"] = at
    peak = peak_bytes(jax.devices()[:1])
    served = w["served"]
    work = serve_work(m, m["serve"], reqs, served)
    del eng
    gc.collect()
    check = check_served(m, m["serve"], params, reqs, served, seed)
    return {"kind": "serve", "e2e": w["e2e"], "side": w["side"],
            "check": check, "attempted": len(reqs), "failed": w["failed"],
            "peak_bytes": peak, "counters": w["counters"],
            "spans": w["spans"], "work": work, "calls": w["calls"]}


def serve_work(m: dict, serve: dict, reqs, served) -> dict:
    """The useful work of the requests served, from their shapes
    (``costs``): prefill FLOPs of the real prompt tokens, and attention
    FLOPs and bytes of the prefill chunks and of the decode steps."""
    L, H, KV, D = m["n_layers"], m["n_heads"], m["n_kv_heads"], m["d_head"]
    act, kvd, w = serve["compute_dtype"], serve["cache_dtype"], \
        serve["prefill_chunk"]
    pre_f = pre_b = dec_f = dec_b = 0.0
    lens, rows, dec_pos = [], 0, []
    for r in reqs:
        if r.rid not in served:
            continue
        n = len(r.prompt)
        lens.append(n)
        rows += 1
        for s in range(0, n, w):
            q = min(w, n - s)
            pre_f += L * costs.attention_flops(H, D, costs.chunk_pairs(s, q))
            pre_b += L * costs.attention_bytes(H, KV, D, q, s + q, act, kvd)
        for p in range(n, n + len(served[r.rid]) - 1):
            dec_pos.append(p)
            dec_f += L * costs.attention_flops(H, D, p + 1)
            dec_b += L * costs.attention_bytes(H, KV, D, 1, p + 1, act, kvd)
    return {"prefill_model_flops": costs.prefill_flops(m, lens, rows),
            "decode_model_flops": costs.decode_flops(m, dec_pos),
            "attn_prefill_flops": pre_f, "attn_prefill_bytes": pre_b,
            "attn_decode_flops": dec_f, "attn_decode_bytes": dec_b}


# --- correctness ---------------------------------------------------------------

def sample_served(reqs, served, seed: int, min_tokens: int) -> list:
    """Requests to compare, drawn from the seed: the longest served
    sequence first, then others until ``min_tokens`` served tokens."""
    ok = [r for r in reqs if r.rid in served]
    if not ok:
        return []
    first = max(ok, key=lambda r: (len(r.prompt) + len(served[r.rid]), r.rid))
    rest = [r for r in ok if r is not first]
    order = traffic.rng(seed, 3).permutation(len(rest))
    out, n = [first], len(served[first.rid])
    for i in order:
        if n >= min_tokens:
            break
        out.append(rest[i])
        n += len(served[rest[i].rid])
    return out


def gaps(m: dict, params, seqs, max_len: int, quant=None) -> list:
    """Per sampled sequence, the gap (in logits) at each served position
    between the reference's best and the served token (or, with ``quant``,
    the control's own choice).  ``seqs``: (prompt, served tokens)."""
    import jax.numpy as jnp
    from bench import reference
    mk = reference.sizes_key(m)
    out = []
    for prompt, toks in seqs:
        seq = np.concatenate([prompt, np.asarray(toks, np.int32)])
        n, first = len(seq), len(prompt)
        pad = np.zeros(max_len, np.int32)
        pad[:n] = seq
        valid = np.zeros(max_len, bool)
        valid[first - 1:n - 1] = True
        g = reference.served_gaps(mk, params, jnp.asarray(pad),
                                  jnp.asarray(valid), quant=quant,
                                  kv=m["serve"]["cache_dtype"],
                                  split=m["serve"]["page_size"])
        out.append(np.asarray(g)[first - 1:n - 1])
    return out


def check_served(m, serve, params, reqs, served, seed) -> dict:
    """The widest logit gap of a sample of served tokens below the float32
    reference's best, against the configuration's limit."""
    lim = serve["check"]
    pick = sample_served(reqs, served, seed, lim["min_tokens"])
    seqs = [(r.prompt, served[r.rid]) for r in pick]
    g = gaps(m, params, seqs, serve["max_len"])
    widest = max(float(x.max()) for x in g) if g else float("inf")
    missing = len(reqs) - len(served)
    return {"numbers": {"served_logit_gap": [widest, lim["served_logit_gap"]],
                        "requests_unanswered": [missing, 0]},
            "compared_tokens": sum(len(t) for _, t in seqs),
            "compared_requests": len(seqs)}
