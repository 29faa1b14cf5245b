"""The trace reduction on a slice of a profiler trace recorded on a TPU v5e
(olmo-1b serving: the end of a prefill chunk, the host's gap, and a decode
burst up to its first paged-decode kernel)."""
import json
import os

import pytest

from bench import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def events():
    with open(os.path.join(HERE, "serve_trace_slice.json")) as f:
        raw = json.load(f)
    return {p: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()}
            for p, lines in raw.items()}


def _ops(events):
    return events["/device:TPU:0"]["XLA Ops"]


def test_busy_is_the_union_of_op_intervals(events):
    ops = _ops(events)
    # brute force at 1 us resolution over the slice
    lo = min(s for _, s, _ in ops)
    hi = max(s + d for _, s, d in ops)
    grid = bytearray(int((hi - lo) / 1e3) + 2)
    for _, s, d in ops:
        for i in range(int((s - lo) / 1e3), int((s + d - lo) / 1e3)):
            grid[i] = 1
    r = tr.reduce_events(events, 0.02217962)
    assert r["busy_s"] == pytest.approx(sum(grid) * 1e-6, abs=2e-5)
    assert r["busy_s"] < r["window_s"]


def test_idle_share_and_host_gap(events):
    r = tr.reduce_events(events, 0.02217962)
    idle = 1 - r["busy_s"] / r["window_s"]
    assert 0.2 < idle < 0.4
    # no harness span in this recording: the gap is between calls
    (name, secs), = r["breakdown"]["idle_gaps"]
    assert name == "host: between calls" and secs > 5e-3


def test_kernel_time_is_attributed_to_its_executable(events):
    r = tr.reduce_events(events, 0.02217962)
    assert set(r["kernels"]) == {"jit_burst"}
    k = r["kernels"]["jit_burst"]
    assert k["calls"] == 1 and k["device_s"] == pytest.approx(8.4886e-3,
                                                              rel=1e-3)
    assert r["breakdown"]["device_ops"][0][0] == "flash_hyft_decode_paged"
    assert r["modules"]["jit_chunk"]["calls"] == 1


def test_leaves_drop_containers():
    ops = [("%while.1 = while()", 0.0, 10.0), ("%a.1 = x()", 1.0, 2.0),
           ("%b.2 = y()", 4.0, 2.0), ("%c = z()", 20.0, 1.0)]
    assert [n for n, _, _ in tr.leaves(ops)] == ["%a.1 = x()", "%b.2 = y()",
                                                  "%c = z()"]
    assert tr.op_name("%fusion.12 = f32[] fusion()") == "fusion"


def test_idle_gaps_are_named_by_the_harness_span():
    ev = {"/device:TPU:0": {"XLA Ops": [("%a = x()", 0.0, 1e6),
                                        ("%b = y()", 3e6, 1e6)],
                            "XLA Modules": []},
          "/host:CPU": {"python": [("bench.burst", 0.5e6, 3e6)]}}
    r = tr.reduce_events(ev, 4e-3)
    assert r["breakdown"]["idle_gaps"] == [["bench.burst",
                                            pytest.approx(2e-3)]]
