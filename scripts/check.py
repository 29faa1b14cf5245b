"""Static analysis driver (DESIGN.md #14): run the three invariant passes
and exit non-zero on any finding.

    PYTHONPATH=src python scripts/check.py --all [--verbose]
    PYTHONPATH=src python scripts/check.py --lint --retrace

Passes:
  --jaxpr    format-flow audit of the real serving/training executables
  --retrace  steady-state serving (warm buckets, 8 admissions) compiles
             nothing new, for the continuous and spec schedulers
  --lint     AST rules over src/repro and scripts/ (traced-bool, host-call,
             prng.constant-seed, cache.not-donated, obs.untimed-hot-path)
  --bench-regress
             compare the repo's BENCH_*.json artifacts against their
             BENCH_ledger.jsonl baseline rows with per-metric tolerances
             (opt-in: not part of --all — it needs bench artifacts, which
             only bench runs produce)

``--verbose`` also prints the scalar weak-convert churn tally from the
jaxpr pass (notes, not findings: XLA folds rank-0 weak casts).
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--jaxpr", action="store_true")
    ap.add_argument("--retrace", action="store_true")
    ap.add_argument("--lint", action="store_true")
    ap.add_argument("--bench-regress", action="store_true",
                    help="BENCH_*.json vs ledger baseline (not in --all)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)
    explicit = args.jaxpr or args.retrace or args.lint or args.bench_regress
    if args.all or not explicit:
        args.jaxpr = args.retrace = args.lint = True

    # lint is pure AST -- run it first so syntax-level breakage is reported
    # even when tracing-based passes cannot build the executables
    passes = []
    if args.lint:
        from repro.analysis import lint
        passes.append(("lint", lambda: lint.run()))
    if args.jaxpr:
        from repro.analysis import jaxpr_audit
        stats: dict = {}
        passes.append(("jaxpr", lambda: jaxpr_audit.run(stats=stats)))
    else:
        stats = {}
    if args.retrace:
        from repro.analysis import retrace
        passes.append(("retrace", lambda: retrace.run()))
    if args.bench_regress:
        from repro.obs import ledger
        root = os.path.join(os.path.dirname(__file__), "..")
        passes.append(("bench", lambda: ledger.regress(root)))

    total = 0
    for name, fn in passes:
        t0 = time.time()
        findings = fn()
        dt = time.time() - t0
        status = "clean" if not findings else f"{len(findings)} finding(s)"
        print(f"[check] {name:8s} {status} ({dt:.1f}s)")
        for f in findings:
            print(f"  {f}")
        total += len(findings)
    if args.verbose and stats:
        print(f"[check] notes: {stats.get('scalar_weak_converts', 0)} scalar "
              f"weak-typed converts (rank-0, folded by XLA; churn only)")
    if total:
        print(f"[check] FAILED: {total} finding(s)")
        return 1
    print("[check] all passes clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
