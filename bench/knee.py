"""Find a serving cell's knee: step the offered rate and print, per rate,
what was completed within the window and what was still unfinished at its
close.  Run once when a cell is defined, on the chip:

    python bench/knee.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 0.1,0.2,0.3

The engine is set up once and serves one window per rate, lowest first.
The knee is the highest rate at which the backlog does not grow over the
window: requests unfinished at the close stay about as many as the slots
in decode, and the drain after the close stays near one request's
lifetime.  Prints one JSON line per rate and writes them all to
``chiprun_out/knee-<cell>.json`` when that directory exists.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from bench import run as bench_run
    from bench import serve_cell, traffic
    bm = bench_run.benchmark()
    cell = bench_run.cell_of(bm, args.workload)
    if jax.devices()[0].platform != "tpu":
        print("knee.py: needs a TPU", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    m = bench_run.load_config(cell["config"])
    mix = traffic.load(cell["traffic"])
    eng, _, _ = serve_cell.prepare(m, mix, args.seed)
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        reqs = traffic.serve_requests(mix, args.seed + i, args.seconds,
                                      m["vocab"], rate=rate)
        reqs = [dataclasses.replace(r, rid=r.rid + 100_000 * (i + 1))
                for r in reqs]
        w = serve_cell.window(eng, reqs, args.seconds)
        done = len(w["served"]) - w["side"]["unfinished_at_close"]
        row = {"rate_per_s": rate, "offered": len(reqs),
               "completed_in_window": done,
               "unfinished_at_close": w["side"]["unfinished_at_close"],
               "drain_s": w["side"]["drain_s"], "failed": w["failed"],
               **w["e2e"], "ttft_p50_ms": w["side"]["ttft_p50_ms"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    if os.path.isdir(out):
        with open(os.path.join(out, f"knee-{args.workload}.json"), "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
