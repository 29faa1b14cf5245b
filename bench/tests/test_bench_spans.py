"""Device idle time put down to the program's host phases
(``bench/idle_spans.py``), the three scheduler-layer readers, and the
program's request-life events against the harness's delivery re-timing."""
import copy
import importlib.util
import os

import numpy as np
import pytest

from bench import idle_spans, trace_reduce
from bench.tests import smoke

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6                                    # ns


def _ev(ops, marks):
    return {"/device:TPU:0": {"XLA Ops": [(f"%op{i} = x()", s, d)
                                          for i, (s, d) in enumerate(ops)],
                              "XLA Modules": []},
            "/host:CPU": {"python": marks}}


def _reader(name):
    path = os.path.join(os.path.dirname(HERE), "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_a_gap_goes_to_the_innermost_annotation():
    """Along a gap, each stretch goes to the innermost annotation over it:
    the end of ``serve.decode_burst`` and ``serve.burst_emit`` inside the
    harness's ``bench.burst``, the harness span between and after them,
    and the rest of the gap to ``host: between calls``."""
    ev = _ev([(0, 1 * MS), (4 * MS, 1 * MS)],
             [("bench.burst", 0.5 * MS, 3 * MS),
              ("serve.decode_burst", 0.5 * MS, 0.9 * MS),
              ("serve.burst_emit", 1.5 * MS, 1.5 * MS)])
    busy, gaps = idle_spans.device_gaps(ev)
    assert gaps == [(1 * MS, 4 * MS)] and busy == pytest.approx(2e-3)
    spans = idle_spans.idle_by_span(ev, gaps, 1)
    assert spans == {"serve.decode_burst": pytest.approx(0.4e-3),
                     "bench.burst": pytest.approx(0.6e-3),
                     "serve.burst_emit": pytest.approx(1.5e-3),
                     "host: between calls": pytest.approx(0.5e-3)}
    assert sum(spans.values()) == pytest.approx(3e-3)


def test_a_gap_under_no_annotation_is_between_calls():
    ev = _ev([(0, 1 * MS), (3 * MS, 1 * MS)],
             [("serve.schedule", 5 * MS, 1 * MS), ("other", 1 * MS, 2 * MS)])
    _, gaps = idle_spans.device_gaps(ev)
    assert idle_spans.idle_by_span(ev, gaps, 1) == {
        "host: between calls": pytest.approx(2e-3)}


def test_a_long_wait_is_split_over_the_loop_phases():
    """One idle stretch over many scheduling checkpoints and sleeps goes
    to each in proportion, not all to the phase at its middle."""
    marks = []
    for i in range(10):
        t = (1 + i) * MS
        marks += [("serve.schedule", t, 0.1 * MS),
                  ("serve.wait_arrival", t + 0.1 * MS, 0.9 * MS)]
    ev = _ev([(0, 1 * MS), (11 * MS, 1 * MS)], marks)
    _, gaps = idle_spans.device_gaps(ev)
    spans = idle_spans.idle_by_span(ev, gaps, 1)
    assert spans["serve.wait_arrival"] == pytest.approx(9e-3)
    assert spans["serve.schedule"] == pytest.approx(1e-3)


def test_the_v5e_slice_reduction_is_unchanged():
    """The reduction ``trace_reduce`` makes of the v5e slice keeps every
    key and value, and its idle gaps add up to the split's; the slice holds
    no annotation of the program's, so nothing is attached."""
    ev = smoke.fixture_events()
    before = trace_reduce.reduce_events(copy.deepcopy(ev), 0.02217962)
    busy, gaps = idle_spans.device_gaps(ev)
    spans = idle_spans.idle_by_span(ev, gaps, 1)
    after = trace_reduce.reduce_events(ev, 0.02217962)
    assert after == before
    assert busy == before["busy_s"]
    assert set(before) == {"window_s", "busy_s", "chips", "modules",
                           "kernels", "breakdown"}
    assert spans == {"host: between calls": pytest.approx(
        sum(v for _, v in before["breakdown"]["idle_gaps"]))}


def test_attach_finds_the_run_trace_by_its_busy_time(monkeypatch, tmp_path):
    ev = _ev([(0, 1 * MS), (4 * MS, 1 * MS)],
             [("serve.prefill_finish", 1 * MS, 2 * MS)])
    other = _ev([(0, 3 * MS)], [])
    dirs = {str(tmp_path / "a"): other, str(tmp_path / "b"): ev}
    monkeypatch.setattr(idle_spans, "_candidates", lambda: list(dirs))
    monkeypatch.setattr(trace_reduce, "load", lambda d: dirs[d])
    rec = {"kind": "serve", "side": {},
           "trace": trace_reduce.reduce_events(ev, 6e-3)}
    spans = idle_spans.attach(rec)
    assert spans == {"serve.prefill_finish": pytest.approx(2e-3),
                     "host: between calls": pytest.approx(1e-3)}
    assert rec["side"]["idle_spans"][0] == ["serve.prefill_finish",
                                            pytest.approx(2e-3)]
    assert "idle_spans" not in rec["trace"]["breakdown"]
    # a program whose tracer opens no annotation: nothing to read
    dirs[str(tmp_path / "b")] = _ev([(0, 1 * MS), (4 * MS, 1 * MS)],
                                    [("bench.burst", 0, 5 * MS)])
    bare = {"kind": "serve", "side": {},
            "trace": trace_reduce.reduce_events(ev, 6e-3)}
    assert idle_spans.attach(bare) is None and "idle_spans" not in bare["side"]


def _span(name, dur_ms, resume=False, rid=0):
    return {"name": name, "ph": "X", "ts": 0.0, "dur": dur_ms * 1e3,
            "args": {"rid": rid, "resume": resume}}


@pytest.mark.parametrize("metric,event", [
    ("queue_wait_p95_ms.serve", "request_queued"),
    ("prefill_p95_ms.serve", "request_prefill")])
def test_request_life_readers(metric, event):
    read = _reader(metric)
    durs = [10.0, 20.0, 30.0, 40.0, 1000.0]
    spans = [_span(event, d, rid=i) for i, d in enumerate(durs)]
    spans += [_span(event, 5000.0, resume=True),
              _span("prefill_chunk", 7000.0)]
    assert read({"spans": spans}) == pytest.approx(np.percentile(durs, 95))
    # the parent program records no such event
    assert read({"spans": [_span("prefill_chunk", 7000.0)]}) is None


def test_host_stall_share_reader(monkeypatch):
    read = _reader("host_stall_share.serve")
    rec = {"kind": "serve", "side": {},
           "trace": {"window_s": 10.0, "busy_s": 9.0, "chips": 1,
                     "idle_spans": {"serve.wait_arrival": 0.7,
                                    "serve.burst_emit": 0.2,
                                    "host: between calls": 0.1}}}
    assert read(rec) == pytest.approx(3.0)
    monkeypatch.setattr(idle_spans, "_candidates", lambda: [])
    none = {"kind": "serve", "side": {},
            "trace": {"window_s": 10.0, "busy_s": 9.0, "chips": 1}}
    assert read(none) is None


def test_request_events_and_delivery_times_on_a_served_run():
    """A served run at smoke size: every request has one queue wait, from
    its arrival to its admission, and one prefill, from there to its first
    token's delivery; each token's delivery time lies at most 5 ms before
    the end of the call that produced it, as the harness re-times it."""
    import jax
    from repro.configs import get_config, smoke_config
    from repro.configs.base import ServeConfig
    from repro.models import build_model
    from repro.models.layers import unbox
    from repro.obs import Obs
    from repro.obs.trace import Tracer
    from repro.serve.scheduler import Request, SlotPoolEngine

    from bench.serve_cell import Delivery
    cfg = smoke_config(get_config("olmo-1b")).with_(vocab=64)
    model = build_model(cfg)
    params = unbox(model.init(jax.random.PRNGKey(0)))
    scfg = ServeConfig(max_len=48, cache_dtype="float32", n_slots=2,
                       kv_layout="paged", page_size=8, prefill_chunk=8,
                       decode_burst=4, attn_mode="unfused")
    eng = SlotPoolEngine(model, params, scfg, obs=Obs(tracer=Tracer()))
    rng = np.random.default_rng(4)
    reqs = [Request(rid=i, tokens=rng.integers(0, 64, n).astype(np.int32),
                    max_new=m, arrival=a)
            for i, (n, m, a) in enumerate([(20, 6, 0.0), (9, 5, 0.0),
                                           (14, 7, 0.05), (5, 3, 0.4)])]
    eng.prewarm(20)
    n0 = len(eng.obs.tracer.events)
    deliv = Delivery(eng)
    done = eng.run(reqs)
    deliv.unwrap()
    evs = eng.obs.tracer.events[n0:]
    t0 = eng._t0_tr
    for r in reqs:
        c = done[r.rid]
        assert c.ok and len(c.delivered_times) == len(c.tokens) == r.max_new
        (q,) = [e for e in evs if e["name"] == "request_queued"
                and e["args"] == {"rid": r.rid, "resume": False}]
        (p,) = [e for e in evs if e["name"] == "request_prefill"
                and e["args"] == {"rid": r.rid, "resume": False}]
        assert q["ts"] / 1e6 == pytest.approx(t0 + r.arrival, abs=1e-6)
        assert q["ts"] + q["dur"] == pytest.approx(p["ts"], abs=1e-3)
        assert (p["ts"] + p["dur"]) / 1e6 - t0 == pytest.approx(
            c.delivered_times[0], abs=1e-3)
        assert c.ttft == pytest.approx(c.delivered_times[0] - r.arrival)
        for stamp, held in zip(c.token_times, c.delivered_times):
            assert 0 <= deliv.end_of[stamp] - held < 5e-3
