"""A serving cell at smoke size on the CPU: delivery re-timing, the result
line, and the check's response to a token altered where it is produced.
The cell runs once, in a process of its own (``bench/tests/smoke.py``)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import run as bench_run
from bench import traffic
from bench.tests import smoke

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def res():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]))
    p = subprocess.run([sys.executable, "-m", "bench.tests.smoke", "serve"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_every_delivery_is_a_call_end_at_or_after_its_stamp(res):
    w = res["window"]
    end_of = {s: e for _, s, e in w["calls"]}
    ends = set(end_of.values())
    n = 0
    for r in w["requests"]:
        assert r["ok"] and r["tokens"] == r["max_new"]
        for stamp in r["stamps"]:
            assert end_of[stamp] >= stamp and end_of[stamp] in ends
            n += 1
    assert n == sum(r["max_new"] for r in w["requests"])
    assert w["side"]["compiles_in_window"] == 0


def test_latencies_come_from_delivery_times(res):
    w = res["window"]
    end_of = {s: e for _, s, e in w["calls"]}
    first = [end_of[r["stamps"][0]] - r["arrival"] for r in w["requests"]]
    stamped = [r["stamps"][0] - r["arrival"] for r in w["requests"]]
    assert w["e2e"]["ttft_p95_ms"] == pytest.approx(
        1e3 * np.percentile(first, 95))
    assert min(np.subtract(first, stamped)) > 0   # stamps are call starts
    last = max(end_of[r["stamps"][-1]] for r in w["requests"])
    tokens = sum(r["tokens"] for r in w["requests"])
    assert w["e2e"]["output_tokens_per_s"] == pytest.approx(tokens / last)


def test_result_line_schema(res):
    bm = bench_run.benchmark()
    untraced, traced = res["untraced"], res["traced"]
    for out in (untraced, traced):
        assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                                 "device"]
        assert list(out)[-1] == "check"
        assert out["correct"] is True and out["failed"] == 0, out["check"]
        assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
            out["device"])
        for v in out["metrics"].values():
            assert set(v) == {"value", "unit"}
        for v in out["check"].values():
            assert set(v) == {"value", "limit"}
    assert "breakdown" not in untraced
    assert set(untraced["metrics"]) == {"ttft_p95_ms", "tbt_p95_ms",
                                        "output_tokens_per_s", "setup_s"}
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}
    assert traced["device"]["busy_s"] > 0 and traced["device"]["window_s"] > 0
    assert set(traced["metrics"]) <= {x["name"] for x in bm["per_layer"]}
    assert {"padded_lane_share.serve", "idle_share.serve"} <= set(
        traced["metrics"])
    assert not set(traced["metrics"]) & {"mfu.train", "idle_share.train"}


def test_the_control_is_not_correct(res):
    """The float8 reference in the program's place: its own first choices
    lie further below the reference's best than the limit allows."""
    m, _ = smoke.serve_cell()
    assert res["control_gap"] > m["serve"]["check"]["served_logit_gap"]


def test_an_altered_token_is_not_correct(res):
    gap, limit = res["altered"]["served_logit_gap"]
    assert gap > limit


def test_same_seed_same_requests_and_other_seed_same_sizes():
    m, mix = smoke.serve_cell()
    a = traffic.serve_requests(mix, smoke.SEED, 10.0, m["vocab"])
    b = traffic.serve_requests(mix, smoke.SEED, 10.0, m["vocab"])
    c = traffic.serve_requests(mix, smoke.SEED + 1, 10.0, m["vocab"])
    assert all((x.prompt == y.prompt).all() and x.arrival == y.arrival
               for x, y in zip(a, b))
    assert [len(x.prompt) for x in a] == [len(x.prompt) for x in c]
    assert [x.max_new for x in a] == [x.max_new for x in c]
    assert [x.arrival for x in a] == [x.arrival for x in c]
    assert [x.prompt.tolist() for x in a] != [x.prompt.tolist() for x in c]
    assert max(x.arrival for x in a) < 10.0


def test_arrivals_are_a_poisson_path_of_the_expected_count():
    """Exponential gaps (mean 1/rate, coefficient of variation 1), the
    first arrival at the opening, none past the close, and lengths drawn
    inside the mix's ranges."""
    _, mix = smoke.serve_cell()
    reqs = traffic.serve_requests(mix, smoke.SEED, 2000.0, 512, rate=1.5)
    arr = np.array([r.arrival for r in reqs])
    gaps = np.diff(arr)
    assert len(reqs) == 3000 and arr[0] == 0.0 and arr[-1] < 2000.0
    assert (gaps >= 0).all()
    assert gaps.mean() == pytest.approx(1 / 1.5, rel=0.05)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.08)
    plen = np.array([len(r.prompt) for r in reqs])
    olen = np.array([r.max_new for r in reqs])
    for v, d in ((plen, mix["prompt_len"]), (olen, mix["output_len"])):
        assert v.min() >= d["lo"] and v.max() < d["hi"]
        # log-uniform: the median sits at the geometric mean, less the
        # floor to a whole token
        assert np.median(v) == pytest.approx(np.sqrt(d["lo"] * d["hi"]),
                                             rel=0.08, abs=1)
