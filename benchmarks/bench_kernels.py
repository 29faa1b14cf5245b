"""Registry-driven Pallas kernel microbench (DESIGN.md §16).

Times every kernel in ``obs.profile.default_registry()`` and records each
with its XLA HLO cost: us/call per (kernel, shape, format), plus achieved
GFLOP/s / GB/s / roofline fraction when the run is on a chip with published
peaks (``roofline/hw.py``).  Results land in BENCH_kernels.json with a full
provenance stamp and a ledger row.

Off a TPU the kernels run in the Pallas interpreter: those times are
control-flow checks, not speed results, and no roofline share is reported.
"""
from __future__ import annotations

from repro.obs import profile


def run(report, iters: int = 20, quick: bool = False):
    """All 10 registry kernels even in --quick (coverage is the contract);
    quick only drops the iteration count."""
    rows = profile.microbench(iters=3 if quick else iters, report=report)
    return {"kernels": rows}


if __name__ == "__main__":
    import argparse

    from repro.obs import ledger

    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="BENCH_kernels.json")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: fewer timing iters (same 10 kernels)")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--xla-profile", default=None, metavar="DIR",
                    help="jax.profiler capture window around the bench "
                         "(xplane + trace.json.gz under DIR)")
    ap.add_argument("--ledger", default="auto",
                    help="ledger path ('auto' = next to --json, 'none' to "
                         "skip the append)")
    args = ap.parse_args()
    with profile.xla_profile(args.xla_profile):
        res = run(print, iters=args.iters, quick=args.quick)
    ledger.finalize(args.json, "kernels", res,
                    mode="smoke" if args.quick else "full",
                    ledger_path=None if args.ledger == "none"
                    else args.ledger)
    print(f"# wrote {args.json}")
