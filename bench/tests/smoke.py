"""Smoke-size versions of the benchmark's cells for the CPU tests: the
same configuration files and mixes with tiny widths, short prompts and a
window of a few seconds.  The kernels run in the Pallas interpreter.

    python -m bench.tests.smoke serve    # prints one JSON line
    python -m bench.tests.smoke toy      # the toy architecture's cell

The serving cell runs in a process of its own with JAX's asynchronous CPU
dispatch off: on the CPU backend the served tokens of the program's slot
pool depend on the timing of its calls while dispatch is asynchronous
(PERF.md, Open questions), and the tests check the harness, not that.
"""
from __future__ import annotations

import json
import os
import sys
import time

SMOKE_SIZES = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                   d_head=16, d_ff=128, vocab=512, max_seq=256)
SEED = 2**31 + 11       # larger than 32 signed bits hold
TOY_WINDOWS = [None, 8]  # the layers of bench/tests/toy_arch.py
HERE = os.path.dirname(os.path.abspath(__file__))


def serve_cell():
    from bench import traffic
    from bench.run import load_config
    m = load_config("olmo-1b")
    m.update(SMOKE_SIZES)
    m["serve"].update(max_len=128, prefill_chunk=32, n_slots=2)
    # the smoke model's own limit: its sound runs read 0.05, the float8
    # control 0.2 (CPU)
    m["serve"]["check"] = dict(m["serve"]["check"], min_tokens=20,
                               served_logit_gap=0.12)
    mix = traffic.load("serve-long")
    mix.update(rate_per_s=2.0,
               prompt_len={"dist": "loguniform", "lo": 20, "hi": 60},
               output_len={"dist": "loguniform", "lo": 4, "hi": 12})
    return m, mix


def train_cell():
    from bench import traffic
    from bench.run import load_config
    m = load_config("bert-base")
    m.update(SMOKE_SIZES)
    job = traffic.load("train-s512")
    job.update(seq_len=32, global_batch=4, reference_rows=2,
               steps_per_call=2, distinct_batches=4)
    return m, job


def clock():
    t0 = time.perf_counter()
    return lambda: time.perf_counter() - t0


def fixture_events():
    """The slice of a serving trace recorded on a TPU v5e."""
    with open(os.path.join(HERE, "serve_trace_slice.json")) as f:
        raw = json.load(f)
    return {p: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()}
            for p, lines in raw.items()}


def serve_results() -> dict:
    """Everything the serving tests assert on, from one process."""
    from bench import costs, serve_cell as sc, trace_reduce, traffic
    from bench import run as bench_run
    from repro.serve.scheduler import SlotPoolEngine
    m, mix = serve_cell()
    out = {}

    eng, params, _ = sc.prepare(m, mix, SEED)
    reqs = traffic.serve_requests(mix, SEED, 3.0, m["vocab"])
    w = sc.window(eng, reqs, 3.0)
    pick = sc.sample_served(reqs, w["served"], SEED,
                            m["serve"]["check"]["min_tokens"])
    seqs = [(r.prompt, w["served"][r.rid]) for r in pick]
    out["control_gap"] = max(float(g.max()) for g in sc.gaps(
        m, params, seqs, m["serve"]["max_len"], quant="fp8"))
    out["window"] = {"calls": w["calls"], "e2e": w["e2e"], "side": w["side"],
                     "requests": [
                         {"arrival": r.arrival, "max_new": r.max_new,
                          "ok": eng.completions[r.rid].ok,
                          "tokens": len(eng.completions[r.rid].tokens),
                          "stamps": eng.completions[r.rid].token_times}
                         for r in reqs]}

    bm = bench_run.benchmark()
    cell = bench_run.cell_of(bm, "olmo-1b.serve-long")
    bench_run.load_config = lambda name: m
    traffic.load = lambda name: mix
    out["untraced"] = bench_run.run_cell(bm, cell, SEED, 2.0,
                                         False)["result"]
    trace_reduce.load = lambda d: fixture_events()
    v5e = costs.peaks("TPU v5 lite")
    costs.peaks = lambda kind: v5e
    out["traced"] = bench_run.run_cell(bm, cell, SEED + 1, 2.0,
                                       True)["result"]

    first = SlotPoolEngine._first_token
    SlotPoolEngine._first_token = lambda self, last: (
        first(self, last) + 1) % m["vocab"]
    out["altered"] = sc.run(m, mix, SEED + 2, 2.0, None,
                            clock())["check"]["numbers"]
    SlotPoolEngine._first_token = first
    return out


def toy_results() -> dict:
    """The serving cell with its configuration naming the toy architecture
    of ``bench/tests/toy_arch.py``, registered for this process alone: one
    run through ``bench/run.py``'s ``run_cell``, and the check of a run
    whose weights leave the windowed layer's attention in play."""
    from bench import run as bench_run, serve_cell as sc, traffic
    from bench.tests import toy_arch
    sys.modules["bench.arch.toy"] = toy_arch
    m, mix = serve_cell()
    m.update(arch="toy", windows=TOY_WINDOWS)
    bm = bench_run.benchmark()
    cell = bench_run.cell_of(bm, "olmo-1b.serve-long")
    bench_run.load_config = lambda name: m
    traffic.load = lambda name: mix
    rec = bench_run.run_cell(bm, cell, SEED, 2.0, False)
    reqs = traffic.serve_requests(mix, SEED, 2.0, m["vocab"])
    toy_arch.make_weights = toy_arch.make_weights_unmuted
    unmuted = sc.run(m, mix, SEED, 2.0, None, clock())["check"]["numbers"]
    return {"result": rec["result"], "work": rec["work"],
            "requests": [[len(r.prompt), r.max_new] for r in reqs],
            "unmuted": unmuted}


def main(argv) -> int:
    import jax
    jax.config.update("jax_cpu_enable_async_dispatch", False)
    runs = {"serve": serve_results, "toy": toy_results}
    if len(argv) != 2 or argv[1] not in runs:
        raise SystemExit("usage: python -m bench.tests.smoke serve|toy")
    print(json.dumps(runs[argv[1]]()))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
