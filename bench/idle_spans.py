"""Device idle time put down to the program's own host phases.

The program's tracer opens a ``jax.profiler.TraceAnnotation`` named
``<cat>.<name>`` for every span (``serve.decode_burst``, ``train.step``),
so the host plane of a profiler trace carries the serving and training
loops' phases on the device ops' clock, beside the harness's own
``bench.<call>`` annotations.  ``idle_by_span`` splits each device idle
gap over the innermost of those annotations in progress along it; the
stretches under none go to ``host: between calls``.  A gap is split, not
put down whole to the annotation at its middle: one idle stretch of
seconds while the engine waits for an arrival spans hundreds of short
``serve.schedule`` / ``serve.wait_arrival`` pairs.

``attach(rec)`` is how a per-layer reader gets at the trace.
``bench/run.py`` hands a reader the run's record alone, after
``trace_reduce.reduce`` has read the trace and before the trace's
directory is removed; the directory is the newest ``bench-trace-*`` in
the temporary directory whose device ops give the record's ``busy_s``.
"""
from __future__ import annotations

import bisect
import glob
import math
import os
import tempfile

from bench import trace_reduce

MARKS = ("serve.", "train.", "bench.")
PROGRAM = ("serve.", "train.")
BETWEEN = "host: between calls"


def device_gaps(ev: dict) -> tuple:
    """(busy seconds averaged over the chips, [(start_ns, end_ns)] of every
    gap between device ops on every chip), as ``trace_reduce`` counts
    them."""
    chips = [p for p in ev if p.startswith("/device:TPU")]
    busy, gaps = 0.0, []
    for plane in chips:
        spans = trace_reduce.union(
            (s, s + d) for _, s, d in ev[plane].get("XLA Ops", []))
        busy += sum(b - a for a, b in spans) * 1e-9
        gaps += [(a[1], b[0]) for a, b in zip(spans, spans[1:])]
    return busy / max(len(chips), 1), gaps


def host_marks(ev: dict, prefixes=MARKS) -> list:
    """(start_ns, end_ns, name) of the host annotations named with one of
    ``prefixes``, on any host thread."""
    host = ev.get("/host:CPU", {})
    return [(s, s + d, n) for line in host.values() for n, s, d in line
            if n.startswith(prefixes)]


def innermost(marks) -> list:
    """Non-overlapping (start, end, name) stretches: over each, the
    annotation of ``marks`` that began last among those in progress (the
    innermost, as annotations of one thread nest)."""
    segs, stack, t = [], [], -math.inf

    def close(x):
        nonlocal t
        while stack and stack[-1][0] <= x:
            end, name = stack.pop()
            if end > t:
                segs.append((t, end, name))
                t = end

    for s, e, n in sorted(marks, key=lambda m: (m[0], -m[1])):
        close(s)
        if stack and s > t:
            segs.append((t, s, stack[-1][1]))
        t = max(t, s)
        stack.append((e, n))
    close(math.inf)
    return segs


def idle_by_span(ev: dict, gaps, chips: int) -> dict:
    """Idle seconds per host annotation (averaged over ``chips``): each
    gap split over the innermost ``serve.*``, ``train.*`` or ``bench.*``
    annotation along it, the rest under ``host: between calls``."""
    segs = innermost(host_marks(ev))
    starts = [a for a, _, _ in segs]
    total: dict = {}
    for a, b in gaps:
        covered = 0.0
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(segs) and segs[i][0] < b:
            lo, hi = max(a, segs[i][0]), min(b, segs[i][1])
            if hi > lo:
                name = segs[i][2]
                total[name] = total.get(name, 0.0) + (hi - lo) * 1e-9
                covered += hi - lo
            i += 1
        if b - a > covered:
            total[BETWEEN] = total.get(BETWEEN, 0.0) + (b - a - covered) * 1e-9
    return {k: v / chips for k, v in total.items()}


def _candidates() -> list:
    dirs = glob.glob(os.path.join(tempfile.gettempdir(), "bench-trace-*"))
    return sorted(dirs, key=os.path.getmtime, reverse=True)


def _events_of(rec: dict):
    """The loaded trace of ``rec``'s traced run, or None."""
    want = rec["trace"]["busy_s"]
    for d in _candidates():
        try:
            ev = trace_reduce.load(d)
        except (RuntimeError, OSError):
            continue
        busy, gaps = device_gaps(ev)
        if math.isclose(busy, want, rel_tol=1e-9, abs_tol=1e-12):
            return ev, gaps
    return None


def attach(rec: dict):
    """``rec["trace"]["idle_spans"]``: idle seconds under every host
    annotation of the traced run (and the ten largest in
    ``rec["side"]["idle_spans"]``, which ``bench/run.py`` logs).  None
    where the trace cannot be found or holds no annotation of the
    program's own, as a program whose tracer opens none."""
    t = rec.get("trace")
    if t is None:
        return None
    if "idle_spans" not in t:
        t["idle_spans"] = None
        found = _events_of(rec)
        if found is not None and host_marks(found[0], PROGRAM):
            spans = idle_by_span(found[0], found[1], t["chips"])
            t["idle_spans"] = spans
            rec.setdefault("side", {})["idle_spans"] = [
                [k, v] for k, v in sorted(spans.items(),
                                          key=lambda kv: -kv[1])[:10]]
    return t["idle_spans"]
