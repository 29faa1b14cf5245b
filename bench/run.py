"""Run one benchmark cell on the accelerator this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration and traffic are looked up by name in
``BENCHMARK.json`` and read from ``bench/configs`` and ``bench/traffic``.
With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the run is traced by the JAX profiler and the result carries
its per-layer metrics, each read by ``bench/metrics/<metric>.py``.  The
last line of standard output is one JSON object; the numbers the check
compared are the last lines of standard error.  Without a TPU, or with
fewer chips than the cell asks for, it exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_of(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")


def applies(metric: dict, cell: str, reported: set) -> bool:
    """Whether ``metric`` is reported in ``cell``: the cells it lists, or
    else every cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in reported


def load_config(name: str) -> dict:
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def reader(metric: str):
    """``read(record)`` of ``bench/metrics/<metric>.py``."""
    path = os.path.join(ROOT, "bench", "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CacheCount:
    """Persistent compilation cache hits and misses, from JAX's events."""

    def __init__(self):
        import jax.monitoring
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._event)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/compile_requests_use_cache":
            self.misses += 1

    def line(self) -> str:
        return (f"compile cache: {self.hits} hits, "
                f"{self.misses - self.hits} misses")


def run_cell(bm: dict, cell: dict, seed: int, seconds: float,
             trace: bool) -> dict:
    """Everything of a run after the look for a chip: set-up, window,
    check, and the reduction of the trace.  Returns the result object."""
    import jax

    from bench import costs, serve_cell, trace_reduce, train_cell, traffic
    cfg = load_config(cell["config"])
    mix = traffic.load(cell["traffic"])
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    clock = lambda: time.perf_counter() - T_START  # noqa: E731
    try:
        if mix["kind"] == "serve":
            rec = serve_cell.run(cfg, mix, seed, seconds, tmp, clock)
        else:
            rec = train_cell.run(cfg, mix, seed, seconds, tmp, clock)
        devs = jax.devices()
        d = devs[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": len(devs), "memory_peak_bytes": rec["peak_bytes"]}
        out = {"correct": None, "attempted": rec["attempted"],
               "failed": rec["failed"], "metrics": {}, "device": device}
        e2e = [x for x in bm["end_to_end"] if x["name"] in rec["e2e"]
               and applies(x, cell["name"], set())]
        reported = {x["name"] for x in e2e}
        if trace:
            rec["model"] = cfg
            rec["peak"] = costs.peaks(d.device_kind)
            rec["trace"] = trace_reduce.reduce(tmp, rec)
            device["busy_s"] = rec["trace"]["busy_s"]
            device["window_s"] = rec["trace"]["window_s"]
            for x in bm["per_layer"]:
                if not applies(x, cell["name"], reported):
                    continue
                v = reader(x["name"])(rec)
                if v is not None:
                    out["metrics"][x["name"]] = {"value": v, "unit": x["unit"]}
            out["breakdown"] = rec["trace"]["breakdown"]
        else:
            for x in e2e:
                out["metrics"][x["name"]] = {"value": rec["e2e"][x["name"]],
                                             "unit": x["unit"]}
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)
    nums = rec["check"]["numbers"]
    out["correct"] = all(v <= lim for v, lim in nums.values())
    out["check"] = {k: {"value": v, "limit": lim} for k, (v, lim) in
                    nums.items()}
    rec["result"] = out
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        log(f"run.py: no program under {SRC}: run it from a checkout of "
            "the repository")
        return 2
    sys.path[:0] = [ROOT, SRC]
    bm = benchmark()
    cell = cell_of(bm, args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        log(f"run.py: {args.workload} needs {cell['chips']} TPU chip(s); "
            f"found {len(devs)} x {devs[0].platform}")
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    # every program goes to the cache, however quick its compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    count = CacheCount()
    log(f"compile cache at {cache_dir}")
    rec = run_cell(bm, cell, args.seed, args.seconds, bool(args.trace))
    out = rec["result"]
    print(count.line(), flush=True)
    for k, v in rec.get("side", {}).items():
        log(f"side {k} = {v}")
    for k, v in out["check"].items():
        log(f"check {k} = {v['value']!r} (limit {v['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
