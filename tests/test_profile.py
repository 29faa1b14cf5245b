"""Cost accounting + regression ledger (DESIGN.md §16).

Covers: the cost-analysis join (known-matmul FLOPs match the analytic
count), CostBook record/observe gating and metric emission, the kernel
microbench rows, ledger append/compare round-trips, the tolerance policy
(seeded slowdown flagged, improvement never flagged, cross-host walls
skipped, exact mismatches always flagged), and the ``regress`` gate over a
fabricated artifact+ledger directory.  Everything runs against tmp dirs —
no dependence on the repo's committed BENCH files.
"""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from repro.obs import Obs, ledger, profile
from repro.roofline import hw

M, K, N = 64, 128, 32


def _matmul():
    f = jax.jit(lambda a, b: a @ b)
    a = jnp.zeros((M, K), jnp.float32)
    b = jnp.zeros((K, N), jnp.float32)
    return f, a, b


# ---------------------------------------------------------------------------
# cost-analysis join
# ---------------------------------------------------------------------------


def test_exec_cost_matmul_flops_match_analytic():
    f, a, b = _matmul()
    c = profile.exec_cost(f, a, b)
    assert c is not None
    assert c["flops"] == pytest.approx(2 * M * K * N)
    # operands + result all touched at least once
    assert c["bytes"] >= 4 * (M * K + K * N + M * N)


def test_join_cost_fields_and_roofline_fraction():
    cost = {"flops": 2e9, "bytes": 8e9, "transcendentals": 0.0}
    j = profile.join_cost(cost, wall_s=1.0, device_kind=hw.V5E)
    assert j["achieved_gflops"] == pytest.approx(2.0)
    assert j["achieved_gbps"] == pytest.approx(8.0)
    # 8 GB at 819 GB/s dominates 2 GFLOP at 197 TFLOP/s
    assert j["bound_dominant"] == "memory"
    assert j["roofline_fraction"] == pytest.approx(
        j["bound_us"] * 1e-6 / 1.0)
    assert 0 < j["roofline_fraction"] < 1


@pytest.mark.parametrize("kind", ["cpu", "TPU v99"])
def test_join_cost_unknown_device_raises(kind):
    """A device with no published peaks has no roofline share."""
    with pytest.raises(ValueError, match="no published peaks"):
        profile.join_cost({"flops": 1.0, "bytes": 1.0}, 1.0, device_kind=kind)


def test_costbook_off_tpu_reports_no_share():
    """On the CPU backend a dispatch's wall is recorded, never joined."""
    obs = Obs.enabled()
    f, a, b = _matmul()
    obs.profile.record("mm", f, a, b)
    assert obs.profile.observe("mm", 1e-3) is None
    assert obs.metrics.find("perf.roofline_fraction", executable="mm") is None
    assert obs.metrics.find("perf.wall_s", executable="mm").count == 1
    assert "roofline_fraction" not in obs.profile.summary()["mm"]


def test_costbook_record_observe_emits_metrics(monkeypatch):
    monkeypatch.setattr(profile, "roofline_kind", lambda: hw.V5E)
    obs = Obs.enabled()
    f, a, b = _matmul()
    c = obs.profile.record("mm", f, a, b)
    assert "mm" in obs.profile and c["trip_factor"] == 1.0
    j = obs.profile.observe("mm", 1e-3)
    assert j is not None
    g = obs.metrics.find("perf.roofline_fraction", executable="mm")
    assert g is not None and g.value == pytest.approx(j["roofline_fraction"])
    assert obs.metrics.find("perf.wall_s", executable="mm").count == 1
    s = obs.profile.summary()
    assert s["mm"]["calls"] == 1
    assert s["mm"]["wall_mean_us"] == pytest.approx(1000.0)


def test_costbook_disabled_is_noop_and_unknown_observe_none():
    book = profile.CostBook(enabled=False)
    f, a, b = _matmul()
    assert book.record("mm", f, a, b) is None
    assert "mm" not in book
    assert book.observe("mm", 1e-3) is None


def test_costbook_trip_factor_scales_cost():
    b1 = profile.CostBook(enabled=True)
    b4 = profile.CostBook(enabled=True)
    f, a, b = _matmul()
    c1 = b1.record("mm", f, a, b)
    c4 = b4.record("mm", f, a, b, trip_factor=4.0)
    assert c4["flops"] == pytest.approx(4 * c1["flops"])
    assert c4["bytes"] == pytest.approx(4 * c1["bytes"])


def test_microbench_smoke_one_kernel():
    entries = [e for e in profile.default_registry()
               if e.name == "softmax_fwd"]
    rows = profile.microbench(entries=entries, iters=1)
    (row,) = rows
    assert row["kernel"] == "softmax_fwd" and row["format"] == "float32"
    assert row["us_per_call"] > 0
    assert row["flops"] > 0  # CPU backend provides cost analysis
    assert "roofline_fraction" not in row  # but a CPU wall has no share


def test_xla_profile_capture_window(tmp_path):
    out = str(tmp_path / "prof")
    with profile.xla_profile(out):
        jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    files = [os.path.join(d, f) for d, _, fs in os.walk(out) for f in fs]
    assert files, "capture window wrote nothing"
    with profile.xla_profile(None):
        pass  # falsy outdir: no-op


# ---------------------------------------------------------------------------
# ledger
# ---------------------------------------------------------------------------


def _prov(ts, host="hostA", mode="full", sha="aaaa111"):
    return {"backend": "cpu", "device_kind": "cpu", "interpret": True,
            "jax_version": "0.0", "git_sha": sha, "host": host, "ts": ts,
            "mode": mode}


KERNEL_RESULTS = {"kernels": [
    {"kernel": "softmax_fwd", "us_per_call": 100.0},
    {"kernel": "flash_fwd", "us_per_call": 50.0}]}


def test_provenance_has_all_keys():
    p = ledger.provenance("smoke")
    assert set(ledger.PROVENANCE_KEYS) <= set(p)
    assert p["mode"] == "smoke" and p["backend"] == jax.default_backend()


def test_ledger_append_load_roundtrip(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    row = ledger.append(path, "kernels", KERNEL_RESULTS, prov=_prov(1.0))
    rows = ledger.load(path)
    assert rows == [row]
    assert rows[0]["metrics"]["kernels.count"] == 2.0
    assert rows[0]["metrics"]["kernels.softmax_fwd.us_per_call"] == 100.0
    ledger.append(path, "kernels", KERNEL_RESULTS, prov=_prov(2.0))
    assert len(ledger.load(path)) == 2  # append-only


def test_baseline_prefers_strictly_older_then_self():
    rows = [{"bench": "kernels", "provenance": _prov(1.0, sha="old1"),
             "metrics": {}},
            {"bench": "kernels", "provenance": _prov(2.0, sha="old2"),
             "metrics": {}},
            {"bench": "kernels", "provenance": _prov(3.0, sha="self"),
             "metrics": {}}]
    b = ledger.baseline_for(rows, "kernels", _prov(3.0, sha="self"))
    assert b["provenance"]["git_sha"] == "old2"  # newest strictly older
    b = ledger.baseline_for(rows[2:], "kernels", _prov(3.0, sha="self"))
    assert b["provenance"]["git_sha"] == "self"  # self-row fallback
    # a smoke-mode run never matches full-mode baselines
    assert ledger.baseline_for(rows, "kernels",
                               _prov(9.0, mode="smoke")) is None


def test_compare_flags_seeded_slowdown_not_improvement():
    base = {"provenance": _prov(1.0),
            "metrics": {"kernels.softmax_fwd.us_per_call": 100.0,
                        "kernels.flash_fwd.us_per_call": 50.0,
                        "kernels.count": 2.0}}
    slow = ledger.extract("kernels", {"kernels": [
        {"kernel": "softmax_fwd", "us_per_call": 400.0},   # 3x worse
        {"kernel": "flash_fwd", "us_per_call": 10.0}]})    # improvement
    fs = ledger.compare(base, slow, _prov(2.0), bench="kernels")
    assert len(fs) == 1 and "softmax_fwd" in fs[0].where
    assert fs[0].rule == "regress.wall"


def test_compare_skips_wall_across_hosts_but_not_exact():
    base = {"provenance": _prov(1.0, host="hostA"),
            "metrics": {"kernels.softmax_fwd.us_per_call": 100.0,
                        "kernels.count": 2.0}}
    cur = ledger.extract("kernels", {"kernels": [
        {"kernel": "softmax_fwd", "us_per_call": 9999.0}]})
    fs = ledger.compare(base, cur, _prov(2.0, host="hostB"))
    # the wall slowdown is skipped (different host) but the kernel-count
    # change is exact and always compared
    assert [f.rule for f in fs] == ["regress.exact"]
    assert "kernels.count" in fs[0].where


def test_compare_ratio_within_tolerance_passes():
    base = {"provenance": _prov(1.0),
            "metrics": {"spec.acceptance_rate": 0.8}}
    m = [ledger.Metric("spec.acceptance_rate", 0.6, "ratio", "higher", 0.3)]
    assert ledger.compare(base, m, _prov(2.0)) == []   # -25% < 30% tol
    m = [ledger.Metric("spec.acceptance_rate", 0.4, "ratio", "higher", 0.3)]
    assert len(ledger.compare(base, m, _prov(2.0))) == 1


def _write_artifact(root, results, prov):
    results = dict(results)
    results["provenance"] = prov
    with open(os.path.join(root, "BENCH_kernels.json"), "w") as f:
        json.dump(results, f)


def test_regress_clean_and_seeded_slowdown(tmp_path):
    root = str(tmp_path)
    lpath = os.path.join(root, ledger.LEDGER)
    prov = _prov(2000.0)
    _write_artifact(root, KERNEL_RESULTS, prov)
    ledger.append(lpath, "kernels", KERNEL_RESULTS, prov=prov)
    lines = []
    assert ledger.regress(root, report=lines.append) == []  # self-row clean
    assert any("kernels" in ln for ln in lines)
    # seed a FASTER older baseline: the committed artifact now reads as a
    # slowdown the gate must flag
    fast = {"kernels": [{"kernel": "softmax_fwd", "us_per_call": 10.0},
                        {"kernel": "flash_fwd", "us_per_call": 5.0}]}
    ledger.append(lpath, "kernels", fast, prov=_prov(1000.0, sha="fastold"))
    fs = ledger.regress(root, report=lambda *_: None)
    assert fs and all(f.rule == "regress.wall" for f in fs)
    assert {f.where for f in fs} == {
        "kernels:kernels.softmax_fwd.us_per_call",
        "kernels:kernels.flash_fwd.us_per_call"}


def test_regress_missing_provenance_is_a_finding(tmp_path):
    root = str(tmp_path)
    with open(os.path.join(root, "BENCH_kernels.json"), "w") as f:
        json.dump(KERNEL_RESULTS, f)  # no provenance stamp
    fs = ledger.regress(root, report=lambda *_: None)
    assert len(fs) == 1 and fs[0].rule == "regress.no-provenance"


def test_finalize_stamps_provenance_and_appends(tmp_path):
    path = str(tmp_path / "BENCH_kernels.json")
    res = ledger.finalize(path, "kernels", KERNEL_RESULTS, mode="smoke")
    assert set(ledger.PROVENANCE_KEYS) <= set(res["provenance"])
    assert res["provenance"]["mode"] == "smoke"
    with open(path) as f:
        assert json.load(f)["provenance"] == res["provenance"]
    rows = ledger.load(str(tmp_path / ledger.LEDGER))
    assert len(rows) == 1 and rows[0]["bench"] == "kernels"
    # and the freshly finalized state passes its own regress gate
    assert ledger.regress(str(tmp_path), report=lambda *_: None) == []


# ---------------------------------------------------------------------------
# metrics satellites: atomic snapshot export
# ---------------------------------------------------------------------------


def test_write_jsonl_atomic_and_linewise(tmp_path):
    from repro.obs.metrics import Registry
    reg = Registry()
    reg.counter("c").inc()
    path = str(tmp_path / "m.jsonl")
    reg.write_jsonl(path)
    reg.counter("c").inc()
    reg.write_jsonl(path)
    with open(path) as f:
        lines = [json.loads(ln) for ln in f if ln.strip()]
    assert len(lines) == 2  # one line per snapshot, all parseable
    assert lines[1]["metrics"][0]["value"] == 2
    assert not [f for f in os.listdir(tmp_path) if ".tmp" in f]
