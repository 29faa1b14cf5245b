"""From a JAX profiler trace to the numbers the per-layer metrics read.

The trace (``<dir>/plugins/profile/<time>/*.xplane.pb``) is read with
``jax.profiler.ProfileData``.  On a TPU each chip is a plane
``/device:TPU:<n>`` with a line ``XLA Modules`` (one event per executable
run, named ``jit_<function>(<fingerprint>)``) and a line ``XLA Ops`` (one
event per operation, named by its HLO text; loops and calls contain the
operations they run).  Host threads are lines of the plane ``/host:CPU``;
the harness marks its calls into the program there with
``jax.profiler.TraceAnnotation`` spans named ``bench.<call>``.

What comes out, per run:

- ``window_s`` and ``busy_s``: the traced window and, averaged over the
  chips, the union of the intervals in which an operation ran;
- ``modules``: per executable (``jit_<function>``), its runs and device
  seconds, summed over chips;
- ``kernels``: per executable, the device seconds and count of the Pallas
  kernels (custom calls to ``tpu_custom_call``) that ran inside it;
- ``breakdown``: the ten leaf operations that took most device time, and
  the idle time between device work grouped by the harness call the host
  was in.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?(?: =|$)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def load(trace_dir: str) -> dict:
    """{plane: {line: [(name, start_ns, dur_ns), ...]}} of every device and
    host plane in the one trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {files}")
    pd = ProfileData.from_file(files[0])
    out = {}
    for plane in pd.planes:
        if not (plane.name.startswith("/device:TPU")
                or plane.name == "/host:CPU"):
            continue
        out[plane.name] = {
            line.name: [(e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events]
            for line in plane.lines}
    return out


def op_name(hlo: str) -> str:
    """``%flash_hyft_decode_paged.14 = ...`` -> ``flash_hyft_decode_paged``."""
    m = _NAME.match(hlo)
    return m.group(1) if m else hlo[:64]


def is_kernel(hlo: str) -> bool:
    m = _TARGET.search(hlo)
    return bool(m) and m.group(1) == "tpu_custom_call"


def module_name(ev: str) -> str:
    """``jit_burst(7279840340544913726)`` -> ``jit_burst``."""
    return ev.split("(", 1)[0]


def union(intervals) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def leaves(ops) -> list:
    """The operations that contain no other operation (a loop's body ops,
    not the loop): ``ops`` as (name, start, dur)."""
    evs = sorted(ops, key=lambda e: (e[1], -e[2]))
    keep = []
    for i, (name, s, d) in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is None or nxt[1] >= s + d:
            keep.append((name, s, d))
    return keep


def _containing(starts, items, t):
    """Index of the interval of ``items`` (sorted (start, end, name);
    ``starts`` their starts) that holds ``t``, or None."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and items[i][0] <= t <= items[i][1]:
        return i
    return None


def reduce_events(ev: dict, window_s: float) -> dict:
    """The reduction of :func:`load`'s events (see the module doc)."""
    chips = [p for p in ev if p.startswith("/device:TPU")]
    if not chips:
        raise RuntimeError("the trace holds no TPU plane")
    modules, kernels, busy = {}, {}, 0.0
    op_time, gaps = {}, []
    for plane in chips:
        lines = ev[plane]
        mods = sorted((s, s + d, module_name(n))
                      for n, s, d in lines.get("XLA Modules", []))
        starts = [a for a, _, _ in mods]
        for s, e, n in mods:
            m = modules.setdefault(n, {"calls": 0, "device_s": 0.0})
            m["calls"] += 1
            m["device_s"] += (e - s) * 1e-9
        ops = lines.get("XLA Ops", [])
        spans = union((s, s + d) for _, s, d in ops)
        busy += sum(b - a for a, b in spans) * 1e-9
        gaps += [(a[1], b[0]) for a, b in zip(spans, spans[1:])]
        for name, s, d in ops:
            if is_kernel(name):
                i = _containing(starts, mods, s)
                owner = mods[i][2] if i is not None else "none"
                k = kernels.setdefault(owner, {"calls": 0, "device_s": 0.0})
                k["calls"] += 1
                k["device_s"] += d * 1e-9
        for name, s, d in leaves(ops):
            key = op_name(name)
            op_time[key] = op_time.get(key, 0.0) + d * 1e-9
    n = len(chips)
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    return {"window_s": window_s, "busy_s": busy / n, "chips": n,
            "modules": modules, "kernels": kernels,
            "breakdown": {"device_ops": [[k, v / n] for k, v in top_ops],
                          "idle_gaps": idle_by_host(ev, gaps, n)}}


def idle_by_host(ev: dict, gaps, chips: int) -> list:
    """Device idle time (between device work) grouped by the harness's
    host annotation (``bench.<call>``) in progress at the middle of each
    gap; ``host: between calls`` where none was."""
    host = ev.get("/host:CPU", {})
    marks = sorted((s, s + d, n) for line in host.values()
                   for n, s, d in line if n.startswith("bench."))
    starts = [a for a, _, _ in marks]
    total = {}
    for a, b in gaps:
        i = _containing(starts, marks, (a + b) / 2)
        key = marks[i][2] if i is not None else "host: between calls"
        total[key] = total.get(key, 0.0) + (b - a) * 1e-9
    return [[k, v / chips] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:10]]


def reduce(trace_dir: str, rec: dict) -> dict:
    return reduce_events(load(trace_dir), rec["side"]["traced_s"])
