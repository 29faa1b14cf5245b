"""Read the two ends of each correctness limit on the chip, at a cell's own
size: the program over many seeds (the lower reading) and the control, the
plain reference computed one precision lower (float8 e4m3 matrix products,
below the bfloat16 the configurations compute in), put in its place (the
upper reading).  A training cell also reads the fault of half of the batch
left out.  Everything runs in one process, set up once:

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 20

Serving: per seed, the seed's weights are swapped into the one engine,
which serves a window of ``seconds`` at the cell's load; the sampled served
requests are scored against the float32 reference (the program's reading)
and, at the same positions, the control's own first choices (the
control's reading).  Training: per seed, the state is reset to the seed's
weights, driven through the first steps, and compared; the control's
reference steps and the half-batch fault are compared the same way.
Prints one JSON line per seed and writes them all to
``chiprun_out/calibrate-<cell>.json`` when that directory exists.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def serve_seeds(m, mix, seeds, seconds):
    import numpy as np
    from bench import arch, serve_cell, traffic
    eng, _, _ = serve_cell.prepare(m, mix, seeds[0])
    for i, seed in enumerate(seeds):
        params = arch.load(m).make_weights(m, m["serve"]["param_dtype"], seed)
        eng.params = params
        reqs = traffic.serve_requests(mix, seed, seconds, m["vocab"])
        for r in reqs:
            r.rid += 100_000 * (i + 1)
        w = serve_cell.window(eng, reqs, seconds)
        served = w["served"]
        pick = serve_cell.sample_served(reqs, served, seed,
                                        m["serve"]["check"]["min_tokens"])
        seqs = [(r.prompt, served[r.rid]) for r in pick]
        prog = serve_cell.gaps(m, params, seqs, m["serve"]["max_len"])
        ctrl = serve_cell.gaps(m, params, seqs, m["serve"]["max_len"],
                               quant="fp8")
        row = {"seed": seed, "requests": len(reqs), "failed": w["failed"],
               "compared_tokens": sum(len(t) for _, t in seqs)}
        for name, g in (("program", prog), ("control", ctrl)):
            allg = np.concatenate(g)
            row[name] = {"widest_gap": float(allg.max()),
                         "mean_gap": float(allg.mean()),
                         "flip_share": float((allg > 0).mean())}
        yield row


def train_seeds(m, job, seeds):
    import jax
    import jax.numpy as jnp

    from bench import arch, train_cell
    mesh, tcfg, state, step, state_sh, _, _ = train_cell.build(m, job,
                                                               seeds[0])
    fresh = jax.jit(lambda p: {
        "params": jax.tree.map(lambda x: x.copy(), p),
        "opt": {"step": jnp.zeros((), jnp.int32),
                "master": jax.tree.map(lambda x: x.copy(), p),
                "m": jax.tree.map(jnp.zeros_like, p),
                "v": jax.tree.map(jnp.zeros_like, p)},
        "step": jnp.zeros((), jnp.int32), "rng": jax.random.PRNGKey(0)},
        out_shardings=state_sh)
    names = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_flatten_with_path(state["params"])[0]]
    del state
    for seed in seeds:
        params0 = arch.load(m).make_weights(m, m["train"]["param_dtype"], seed)
        feed = train_cell.make_feed(m, job, seed)
        batches = [feed(i) for i in range(job["check_steps"])]
        half = [dict(b, mask=b["mask"].at[job["global_batch"] // 2:].set(0))
                for b in batches]
        ref = train_cell.reference_steps(m, job, params0, batches)
        row = {"seed": seed}
        for name, feed_b in (("program", batches), ("half_batch", half)):
            call = train_cell.Driver(mesh, tcfg, step, state_sh, feed_b)
            _, losses, g1, delta = train_cell.first_steps(
                call, fresh(params0), params0, job)
            row[name] = train_cell.compare((losses, g1, delta), ref, names)
        ctrl = train_cell.reference_steps(m, job, params0, batches,
                                          quant="fp8")
        row["control"] = train_cell.compare(ctrl, ref, names)
        row["reference_losses"] = ref[0]
        yield row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from bench import run as bench_run
    from bench import traffic
    cell = bench_run.cell_of(bench_run.benchmark(), args.workload)
    if jax.devices()[0].platform != "tpu":
        print("calibrate.py: needs a TPU", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    m = bench_run.load_config(cell["config"])
    mix = traffic.load(cell["traffic"])
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = []
    gen = (serve_seeds(m, mix, seeds, args.seconds) if mix["kind"] == "serve"
           else train_seeds(m, mix, seeds))
    for row in gen:
        rows.append(row)
        print(json.dumps(row), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    if os.path.isdir(out):
        with open(os.path.join(out, f"calibrate-{args.workload}.json"),
                  "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
