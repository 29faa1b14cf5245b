"""Observability layer (DESIGN.md §15): tracer, metrics, numerics, and the
scheduler integration.

Under test:
  * tracer — event shapes (X/i/C), disabled no-op, Perfetto-loadable
    output (NaN args stringified), ``compile_watch`` counting + logger
    restore, every span a profiler annotation ``<cat>.<name>`` (enabled
    or not), backdated ``complete`` events, ``run_train``'s phase spans;
  * metrics — histogram sketch percentiles within the documented ~2.5%
    relative error, exact count/sum, one-kind-per-name binding, JSONL
    snapshot export;
  * numerics — device-side ``logit_stats``/``format_stats`` values on
    known inputs, monitor folding (non-finite kept as ``last`` but not
    folded), quarantine annotation;
  * scheduler — the legacy ``stats`` dict is a faithful view over the
    registry, metric totals reconcile with the Completion records, a
    traced serve covers the span taxonomy, ``Completion.ttft`` is None
    when nothing was emitted;
  * StragglerMonitor — warm-up folding, EMA convergence, outlier
    flagged-not-folded, and the warm-estimate handoff to the device-side
    deadline TTL (``_observe_burst`` -> ``_ttl_vector``);
  * lint — ``obs.untimed-hot-path`` fires on unspanned hot loops and
    respects span scopes and waivers.
"""
import glob
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ServeConfig

# --------------------------------------------------------------------------
# tracer
# --------------------------------------------------------------------------


def test_tracer_event_shapes(tmp_path):
    from repro.obs.trace import Tracer
    clock = iter(np.arange(0.0, 10.0, 0.5))
    tr = Tracer(enabled=True, clock=lambda: next(clock))
    with tr.span("admit", n=3):
        tr.instant("preempt", rid=7)
    tr.counter("queue", depth=2)
    assert [e["ph"] for e in tr.events] == ["i", "X", "C"]
    span = next(e for e in tr.events if e["ph"] == "X")
    assert span["name"] == "admit" and span["args"] == {"n": 3}
    assert span["dur"] == pytest.approx(1.0 * 1e6)  # two clock ticks
    assert tr.span_kinds() == {"admit", "preempt", "queue"}
    p = tmp_path / "t.json"
    tr.write(str(p))
    d = json.loads(p.read_text())
    assert set(d) == {"traceEvents", "displayTimeUnit"}
    assert len(d["traceEvents"]) == 3


def test_tracer_disabled_is_noop():
    from repro.obs.trace import NULL_TRACER
    with NULL_TRACER.span("x", a=1):
        NULL_TRACER.instant("y")
    NULL_TRACER.counter("z", v=1)
    NULL_TRACER.compile_span("f", 0.1, "xla")
    assert NULL_TRACER.events == []


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: records the names
    opened and closed, in order."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        log = self.log

        class _Ann:
            def __enter__(self):
                log.append(("open", name))

            def __exit__(self, *exc):
                log.append(("close", name))
        return _Ann()


@pytest.mark.parametrize("enabled", [True, False])
def test_span_opens_a_profiler_annotation(monkeypatch, enabled):
    """Every span is a profiler annotation ``<cat>.<name>`` whether or not
    the tracer records; only an enabled tracer appends the Chrome event."""
    from repro.obs import trace
    ann = _Annotations()
    monkeypatch.setattr(trace, "TraceAnnotation", ann)
    tr = trace.Tracer(enabled=enabled)
    with tr.span("decode_burst", active=2):
        with tr.span("step", cat="train"):
            pass
    assert ann.log == [("open", "serve.decode_burst"), ("open", "train.step"),
                       ("close", "train.step"),
                       ("close", "serve.decode_burst")]
    if enabled:
        assert [(e["name"], e["cat"]) for e in tr.events] == [
            ("step", "train"), ("decode_burst", "serve")]
        assert tr.events[1]["args"] == {"active": 2}
    else:
        assert tr.events == []


def test_span_annotation_is_on_the_profiler_host_plane(tmp_path):
    """Under a real profiler capture the span lands on the host plane."""
    from jax.profiler import ProfileData

    from repro.obs.trace import Tracer
    jax.profiler.start_trace(str(tmp_path))
    with Tracer(enabled=False).span("prefill_pack"):
        jnp.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    (f,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for p in ProfileData.from_file(f).planes
             if p.name == "/host:CPU" for ln in p.lines for e in ln.events}
    assert "serve.prefill_pack" in names


def test_complete_writes_a_backdated_event(tmp_path):
    from repro.obs.trace import NULL_TRACER, Tracer
    tr = Tracer(enabled=True, clock=lambda: 100.0)
    tr.complete("request_queued", 3.25, 4.5, rid=7, resume=False)
    (e,) = tr.events
    assert e["ph"] == "X" and e["name"] == "request_queued"
    assert e["ts"] == pytest.approx(3.25e6) and e["dur"] == pytest.approx(
        1.25e6)
    assert e["cat"] == "serve" and e["tid"] == 0
    assert e["args"] == {"rid": 7, "resume": False}
    # a compile span is a complete event ending now, as before
    tr.compile_span("jit_burst", 0.5, "xla")
    c = tr.events[-1]
    assert (c["name"], c["cat"], c["tid"]) == ("compile", "compile", 1)
    assert c["ts"] + c["dur"] == pytest.approx(100.0e6)
    assert c["args"] == {"fn": "jit_burst", "kind": "xla"}
    p = tmp_path / "t.json"
    tr.write(str(p))
    assert len(json.loads(p.read_text())["traceEvents"]) == 2
    NULL_TRACER.complete("request_queued", 1.0, 2.0, rid=1)
    assert NULL_TRACER.events == []


def test_run_train_phases_are_spans(monkeypatch):
    """``run_train`` puts each phase of a step under a ``train`` span: the
    batch, the step's dispatch, the metrics sync on log steps, the
    straggler check; annotations open with the default disabled tracer."""
    from repro.configs.base import TrainConfig
    from repro.obs import trace
    from repro.obs.trace import Tracer
    from repro.train.loop import run_train
    ann = _Annotations()
    monkeypatch.setattr(trace, "TraceAnnotation", ann)

    def step(state, batch):
        return state + batch, {"loss": jnp.float32(state)}

    tcfg = TrainConfig(total_steps=3)
    tr = Tracer(enabled=True)
    state, hist = run_train(0, step, lambda s: s, tcfg, log_every=2,
                            log_fn=lambda *_: None, tracer=tr)
    assert state == 3 and [h["step"] for h in hist] == [0, 2]
    names = [(e["name"], e["args"]["step"]) for e in tr.events]
    assert names == [("batch", 0), ("step", 0), ("log", 0), ("straggler", 0),
                     ("batch", 1), ("step", 1), ("straggler", 1),
                     ("batch", 2), ("step", 2), ("log", 2), ("straggler", 2)]
    assert {e["cat"] for e in tr.events} == {"train"}
    ann.log.clear()
    run_train(0, step, lambda s: s, tcfg, log_fn=lambda *_: None)
    opened = [n for k, n in ann.log if k == "open"]
    assert opened.count("train.step") == 3 and "train.batch" in opened


def test_tracer_write_sanitizes_nonfinite(tmp_path):
    """Quarantine instants carry poisoned stats; NaN/Inf are not valid
    JSON and must be stringified so Perfetto still loads the file."""
    from repro.obs.trace import Tracer
    tr = Tracer(enabled=True)
    tr.instant("quarantine", z_max=float("nan"), z_min=float("-inf"),
               nested={"a": [float("inf"), 1.0]})
    p = tmp_path / "t.json"
    tr.write(str(p))
    raw = p.read_text()
    assert "NaN" not in raw and "Infinity" not in raw
    args = json.loads(raw)["traceEvents"][0]["args"]
    assert args["z_max"] == "nan" and args["z_min"] == "-inf"
    assert args["nested"]["a"] == ["inf", 1.0]


def test_compile_watch_counts_and_restores():
    import logging
    from repro.obs.trace import Tracer, compile_watch
    logger = logging.getLogger("jax")
    before = (logger.level, logger.propagate, list(logger.handlers))
    tr = Tracer(enabled=True)
    with compile_watch(tr) as w:
        jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
    assert any("<lambda>" in c for c in w.listener.compiles)
    assert "compile" in tr.span_kinds()
    after = (logger.level, logger.propagate, list(logger.handlers))
    assert before == after
    # enabled=False is a no-op shell
    with compile_watch(enabled=False) as w2:
        jax.jit(lambda x: x - 5)(jnp.ones(4)).block_until_ready()
    assert w2.listener.compiles == []


def test_retrace_guard_still_guards():
    """The PR 8 RetraceGuard API survives its rebase onto compile_watch."""
    from repro.analysis.retrace import RetraceError, RetraceGuard
    with pytest.raises(RetraceError):
        with RetraceGuard():
            jax.jit(lambda x: x * 7)(jnp.ones(5)).block_until_ready()
    with RetraceGuard(max_compiles=16) as g:
        jax.jit(lambda x: x * 11)(jnp.ones(6)).block_until_ready()
    assert g.compiles  # inspectable after exit


# --------------------------------------------------------------------------
# metrics
# --------------------------------------------------------------------------


def test_histogram_percentiles_within_sketch_error():
    from repro.obs.metrics import Histogram
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=-3.0, sigma=1.5, size=5000)
    h = Histogram()
    for v in vals:
        h.observe(float(v))
    assert h.count == len(vals)
    assert h.total == pytest.approx(vals.sum())
    assert h.vmin == vals.min() and h.vmax == vals.max()
    for q in (50, 90, 99):
        exact = np.percentile(vals, q)
        assert h.percentile(q) == pytest.approx(exact, rel=0.05), q
    s = h.summary()
    assert s["count"] == len(vals) and s["mean"] == pytest.approx(vals.mean())


def test_histogram_empty_and_underflow():
    from repro.obs.metrics import Histogram
    h = Histogram()
    # empty histogram: percentiles are None (0.0 would read as a real —
    # excellent — latency downstream), count/sum stay numeric
    assert h.percentile(50) is None
    s = h.summary()
    assert s["count"] == 0 and s["min"] == 0.0 and s["p99"] is None
    h.observe(0.0)  # underflow bucket
    h.observe(5.0)
    assert h.count == 2 and h.percentile(0) == 0.0


def test_registry_kind_binding_and_find():
    from repro.obs.metrics import Registry
    r = Registry()
    c = r.counter("serve.tokens", scheduler="continuous")
    c.inc(5)
    assert r.counter("serve.tokens", scheduler="continuous") is c
    assert r.counter("serve.tokens", scheduler="spec").value == 0
    with pytest.raises(TypeError):
        r.gauge("serve.tokens")
    assert r.find("serve.tokens", scheduler="continuous").value == 5
    assert r.find("serve.tokens", scheduler="lockstep") is None


def test_registry_snapshot_jsonl(tmp_path):
    from repro.obs.metrics import Registry
    r = Registry()
    r.counter("a").inc(3)
    r.gauge("b").set(1.5)
    r.histogram("c").observe(0.25)
    p = tmp_path / "m.jsonl"
    r.write_jsonl(str(p))
    r.counter("a").inc(1)
    r.write_jsonl(str(p))
    lines = [json.loads(line) for line in p.read_text().splitlines()]
    assert len(lines) == 2
    byname = {m["name"]: m for m in lines[-1]["metrics"]}
    assert byname["a"]["value"] == 4 and byname["a"]["kind"] == "counter"
    assert byname["b"]["value"] == 1.5
    assert byname["c"]["count"] == 1 and byname["c"]["sum"] == 0.25
    assert "a 4" in r.report()


# --------------------------------------------------------------------------
# numerics
# --------------------------------------------------------------------------


def test_logit_stats_known_values():
    from repro.obs import numerics as obs_numerics
    logits = jnp.asarray([[2.0, -6.0, 4.0], [100.0, 0.0, -100.0]], jnp.float32)
    active = jnp.asarray([True, False])
    z = np.asarray(obs_numerics.logit_stats(logits, active))
    # only the active row counts: max 4, min -6, post-sub min -10
    assert z.tolist() == [4.0, -6.0, -10.0]
    r = obs_numerics.reduce_logit_stats(jnp.stack([z, z * 2]))
    assert r["z_max"] == 8.0 and r["z_min"] == -12.0
    assert r["zsub_min"] == -20.0


def test_format_stats_fp2fx8_cache():
    from repro.obs import numerics as obs_numerics
    raws = jnp.asarray([[127, -127, 3], [0, 1, 2]], jnp.int8)
    cache = {"k": raws, "k_scale": jnp.asarray([0.5 * 2**-7, 0.25 * 2**-7],
                                               jnp.float32),
             "written": jnp.asarray([1.0, 1.0], jnp.float32)}
    s = {k: np.asarray(v) for k, v in obs_numerics.format_stats(cache).items()}
    assert int(s["kv_saturated"]) == 2
    assert obs_numerics.format_stats({"k": jnp.zeros((2, 2), jnp.float32)}) \
        == {}


def test_numerics_monitor_folding_and_quarantine():
    from repro.obs.numerics import NumericsMonitor
    m = NumericsMonitor()
    m.update({"z_max": jnp.float32(3.0), "z_min": jnp.float32(-2.0),
              "zsub_min": jnp.float32(-5.0)})
    m.update({"z_max": jnp.float32(float("nan")),
              "z_min": jnp.float32(float("nan")),
              "zsub_min": jnp.float32(float("nan"))})
    s = m.summary()
    # NaN burst is kept as `last` (for quarantine annotation) but the
    # running range stays finite
    assert s["z_max"] == 3.0 and s["zsub_min"] == -5.0
    ev = m.record_quarantine(9, "burst")
    assert ev["rid"] == 9 and ev["where"] == "burst"
    assert np.isnan(ev["z_max"])
    assert m.summary()["quarantine_events"] == [ev]


# --------------------------------------------------------------------------
# Completion.ttft
# --------------------------------------------------------------------------


def test_ttft_none_when_no_tokens_emitted():
    from repro.serve.scheduler import Completion
    c = Completion(rid=0, tokens=[], prompt_len=4, finished_at=2.0,
                   arrival=1.0, cancelled=True)
    assert c.ttft is None
    assert c.latency == 1.0
    # TTFT is to the token's delivery, not its call's dispatch stamp
    c2 = Completion(rid=1, tokens=[5], prompt_len=4, finished_at=2.0,
                    arrival=1.0, token_times=[1.0], delivered_times=[1.25])
    assert c2.ttft == pytest.approx(0.25)


# --------------------------------------------------------------------------
# StragglerMonitor + deadline-TTL handoff
# --------------------------------------------------------------------------


def test_straggler_warmup_folds_without_flagging():
    from repro.distributed.fault_tolerance import StragglerMonitor
    m = StragglerMonitor()
    assert not any(m.observe(10.0) for _ in range(m.warm))
    assert m.flagged == 0 and m.ema > 0


def test_straggler_ema_converges():
    from repro.distributed.fault_tolerance import StragglerMonitor
    m = StragglerMonitor()
    for _ in range(100):
        m.observe(0.5)
    assert m.ema == pytest.approx(0.5, rel=1e-3)
    assert m.flagged == 0


def test_straggler_outlier_flagged_not_folded():
    from repro.distributed.fault_tolerance import StragglerMonitor
    m = StragglerMonitor()
    for _ in range(20):
        m.observe(0.1)
    ema_before = m.ema
    assert m.observe(1.0)  # 10x the EMA, threshold is 3x
    assert m.flagged == 1
    assert m.ema == ema_before  # outliers don't pollute the estimate
    assert not m.observe(0.1)   # normal observations keep folding


def test_straggler_warm_handoff_to_deadline_ttl():
    """``_observe_burst`` feeds the EMA into ``_step_ema``; once warm,
    ``_ttl_vector`` converts wall-clock deadlines into per-slot device
    step budgets (clipped to >= 1), and no-deadline slots stay TTL_NONE."""
    from repro.distributed.fault_tolerance import StragglerMonitor
    from repro.obs.metrics import Histogram
    from repro.serve.scheduler import Request, SlotPoolEngine, TTL_NONE

    eng = SlotPoolEngine.__new__(SlotPoolEngine)  # no model build needed
    eng.straggler = StragglerMonitor()
    eng._step_ema = 0.0
    eng.scfg = ServeConfig(n_slots=3)
    eng._hists = {"burst_wall_s": Histogram()}
    eng._count = lambda *a, **k: None

    # cold: no estimate yet -> every slot TTL_NONE
    eng.slot_rid = [0, 1, None]
    eng.active = np.array([True, True, False])
    eng.requests = {0: Request(rid=0, tokens=np.zeros(2, np.int32),
                               max_new=4, deadline=10.0),
                    1: Request(rid=1, tokens=np.zeros(2, np.int32),
                               max_new=4)}
    assert (eng._ttl_vector(now=0.0) == TTL_NONE).all()

    for _ in range(10):  # warm the estimate: 0.4 s bursts of 4 steps
        eng._observe_burst(0.4, steps=4)
    assert eng._step_ema == pytest.approx(0.1, rel=1e-3)

    ttl = eng._ttl_vector(now=9.5)
    assert ttl[0] == 5          # 0.5 s left / 0.1 s per step
    assert ttl[1] == TTL_NONE   # no deadline
    assert ttl[2] == TTL_NONE   # empty slot
    assert eng._ttl_vector(now=99.0)[0] == 1  # already late: clipped, >= 1


# --------------------------------------------------------------------------
# scheduler integration
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


@pytest.mark.slow
def test_traced_serve_stats_view_and_reconciliation(tmp_path):
    """One traced serve: the legacy stats dict mirrors the registry, the
    token counter and TTFT/TBT histograms reconcile exactly with the
    Completion records, the trace file covers the core span kinds, and
    the metrics JSONL export wrote parseable snapshots."""
    from repro.obs import Obs
    from repro.serve.scheduler import SlotPoolEngine
    cfg, model, params = _setup()
    reqs = _requests(cfg, 5, np.random.default_rng(0))
    scfg = ServeConfig(max_len=32, cache_dtype="float32",
                       scheduler="continuous", n_slots=3, decode_burst=4)
    mpath = tmp_path / "m.jsonl"
    obs = Obs.enabled(metrics_path=str(mpath))
    eng = SlotPoolEngine(model, params, scfg, obs=obs)
    eng.prewarm(max(len(r.tokens) for r in reqs))
    done = eng.run(reqs)

    st = eng.stats
    lab = dict(scheduler="continuous", family=cfg.family)
    assert st["tokens_emitted"] == \
        obs.metrics.find("serve.tokens_emitted", **lab).value
    assert st["peak_active"] == \
        obs.metrics.find("serve.peak_active", **lab).value
    assert st["tokens_emitted"] == sum(len(c.tokens) for c in done.values())

    ttfts = [c.ttft for c in done.values() if c.ttft is not None]
    h = obs.metrics.find("serve.ttft_s", **lab)
    assert h.count == len(ttfts)
    assert h.total == pytest.approx(sum(ttfts))
    gaps = [g for c in done.values() for g in np.diff(c.delivered_times)]
    hb = obs.metrics.find("serve.tbt_s", **lab)
    assert hb.count == len(gaps)
    assert hb.total == pytest.approx(sum(gaps))

    kinds = obs.tracer.span_kinds()
    assert {"prewarm", "admit", "prefill_chunk", "decode_burst",
            "compile", "schedule", "prefill_pack", "prefill_finish",
            "burst_prep", "burst_emit", "request_queued",
            "request_prefill"} <= kinds, kinds
    tpath = tmp_path / "t.json"
    obs.tracer.write(str(tpath))
    evs = json.loads(tpath.read_text())["traceEvents"]
    assert all(e["ph"] in ("X", "i", "C") for e in evs)
    lines = [json.loads(line) for line in mpath.read_text().splitlines()]
    assert lines and all("metrics" in d for d in lines)


@pytest.mark.slow
def test_stats_view_default_obs_matches_legacy_shape():
    """Without an injected Obs the engine still exposes the full legacy
    stats dict (the PR 3-8 keys, zero-initialized, ints)."""
    from repro.serve.scheduler import SlotPoolEngine
    cfg, model, params = _setup()
    scfg = ServeConfig(max_len=32, cache_dtype="float32",
                       scheduler="continuous", n_slots=2, decode_burst=4)
    eng = SlotPoolEngine(model, params, scfg)
    st = eng.stats
    for k in ("admitted", "bursts", "prefills", "tokens_emitted",
              "quarantines", "fp32_retries", "stragglers", "audits",
              "peak_active", "pages_peak"):
        assert st[k] == 0, k
    done = eng.run(_requests(cfg, 3, np.random.default_rng(1)))
    assert eng.stats["tokens_emitted"] == \
        sum(len(c.tokens) for c in done.values())


@pytest.mark.slow
def test_telemetry_quarantine_annotated_under_nan_poison():
    """fp2fx8 + telemetry + NaN-poison chaos: the numeric-health ladder
    fires and every quarantine event carries the device-side stats that
    triggered it (the §13 'explainable quarantine' acceptance)."""
    from repro.obs import Obs
    from repro.serve.chaos import ChaosMonkey, FaultPlan
    from repro.serve.scheduler import SlotPoolEngine
    cfg, model, params = _setup()
    reqs = _requests(cfg, 4, np.random.default_rng(2), plen=(4, 8),
                     max_new=(6, 10))
    scfg = ServeConfig(max_len=24, cache_dtype="fp2fx8",
                       scheduler="continuous", n_slots=2, decode_burst=4,
                       telemetry=True)
    monkey = ChaosMonkey(FaultPlan(seed=5, nan_kv_rate=0.5, max_faults=3))
    eng = SlotPoolEngine(model, params, scfg, chaos=monkey, obs=Obs())
    eng.prewarm(max(len(r.tokens) for r in reqs))
    done = eng.run(reqs)
    assert set(done) == {r.rid for r in reqs}

    s = eng.obs.numerics.summary()
    assert s["bursts"] > 0 and np.isfinite(s["z_max"])
    assert s["kv_int8_total"] > 0 and s["kv_scale_hist"]
    assert s["converts"] > 0
    assert eng.stats["quarantines"] > 0
    for ev in s["quarantine_events"]:
        assert {"rid", "where", "z_max", "z_min", "zsub_min",
                "kv_saturated"} <= set(ev)
    # the poison that fired the quarantine is visible in the annotation
    assert any(not np.isfinite(ev["z_max"]) or not np.isfinite(ev["zsub_min"])
               for ev in s["quarantine_events"])


@pytest.mark.slow
def test_telemetry_does_not_change_outputs():
    """telemetry=True only APPENDS stats to the burst outputs — greedy
    tokens are unchanged."""
    from repro.serve.scheduler import SlotPoolEngine
    cfg, model, params = _setup()
    reqs = _requests(cfg, 4, np.random.default_rng(3))
    outs = {}
    for tel in (False, True):
        scfg = ServeConfig(max_len=32, cache_dtype="float32",
                           scheduler="continuous", n_slots=2,
                           decode_burst=4, telemetry=tel)
        eng = SlotPoolEngine(model, params, scfg)
        done = eng.run([r for r in reqs])
        outs[tel] = {rid: c.tokens for rid, c in done.items()}
    assert outs[False] == outs[True]


# --------------------------------------------------------------------------
# lint: obs.untimed-hot-path
# --------------------------------------------------------------------------

_LOOP = """
import jax
step = jax.jit(lambda x: x + 1)
for i in range(10):
    y = step(i)
"""

_LOOP_SPANNED = """
import jax
step = jax.jit(lambda x: x + 1)
with tracer.span("decode"):
    for i in range(10):
        y = step(i)
"""

_LOOP_INNER_SPAN = """
import jax
step = jax.jit(lambda x: x + 1)
for i in range(10):
    with tracer.span("step"):
        y = step(i)
"""

_LOOP_WAIVED = """
import jax
step = jax.jit(lambda x: x + 1)
for i in range(10):
    y = step(i)  # lint: allow(obs.untimed-hot-path)
"""

_BUILDER_ATTR = """
class Eng:
    def __init__(self):
        self._burst = build_burst(1) if True else build_spec(2)
    def run(self):
        while True:
            out = self._burst()
"""

_DENYLISTED = """
for name in names:
    model = build_model(cfg)
"""


def _rules(src):
    from repro.analysis.lint import lint_source
    return [f.rule for f in lint_source(src)]


def test_hot_path_lint_flags_unspanned_loop():
    assert "obs.untimed-hot-path" in _rules(_LOOP)


def test_hot_path_lint_respects_span_scopes():
    assert _rules(_LOOP_SPANNED) == []
    assert _rules(_LOOP_INNER_SPAN) == []


def test_hot_path_lint_waiver():
    assert _rules(_LOOP_WAIVED) == []


def test_hot_path_lint_builder_attribute_and_ifexp():
    assert "obs.untimed-hot-path" in _rules(_BUILDER_ATTR)


def test_hot_path_lint_denylists_model_factories():
    assert _rules(_DENYLISTED) == []


def test_repo_is_hot_path_clean():
    """The repo's own hot loops are all spanned (or waived with a cited
    reason) — the same gate scripts/check.py --lint enforces in CI."""
    from repro.analysis import lint
    bad = [f for f in lint.run() if f.rule == "obs.untimed-hot-path"]
    assert bad == [], bad
