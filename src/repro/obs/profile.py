"""Device-side performance accounting (DESIGN.md §16).

Three layers on top of the §15 spans/metrics substrate:

  ``exec_cost``  — lower a jitted callable at concrete args and read XLA's
                   HLO cost analysis (FLOPs, bytes accessed,
                   transcendentals).  Lowering only re-traces — it never
                   triggers a second backend compile — so capture at
                   prewarm/build time costs a fraction of the compile the
                   executable is paying anyway.
  ``CostBook``   — a per-executable cost ledger: costs recorded at
                   compile time, wall times observed per dispatch by its
                   caller.  On a chip listed in ``roofline/hw.py`` the
                   join emits achieved GFLOP/s, GB/s, and the roofline
                   fraction against that chip's peaks into a bound metrics
                   registry (``perf.*{executable=...}``) and as trace
                   counter events.  Nothing on the serving path feeds it:
                   a host wall over ``cost_analysis()`` cannot see inside
                   a Pallas ``tpu_custom_call``, so device time and
                   roofline shares come from the profiler trace instead.
  ``microbench`` — kernel timing over ``default_registry()``: us/call per
                   (kernel, shape, format), plus achieved-vs-peak on a
                   listed chip — the BENCH_kernels.json rows.

XLA's HLO cost analysis counts a ``while``/``scan`` body ONCE regardless of
trip count (the dry-run path corrects the same way), so ``record`` takes a
``trip_factor`` — callers pass the statically-known scan trip product
(burst steps x layer scan), reusing ``analysis.scan_trip_factor`` policy.

The roofline fraction here is *measured-vs-bound*: bound_s =
max(flops/peak_flops, bytes/hbm_bw) at the peaks of the chip the run is on
(keyed by ``device_kind``), over the measured wall.  A run off a TPU (the
CPU backend, Pallas interpret mode) has no peaks to divide by and reports
walls and costs only: a CPU wall is never published as a device share.

``xla_profile`` is the programmatic ``jax.profiler`` capture window
(``--xla-profile``): xplane + trace.json.gz artifacts per bench run.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp

from repro.roofline import analysis, hw


def exec_cost(fn, *args) -> Optional[dict]:
    """FLOPs / bytes / transcendentals of ``fn`` at ``args`` from XLA's HLO
    cost analysis, via ``jit(fn).lower(*args).cost_analysis()``.  Returns
    None when the backend offers no analysis (never raises) — callers must
    treat cost rows as best-effort."""
    try:
        cost = fn.lower(*args).cost_analysis()
    except Exception:
        return None
    if isinstance(cost, (list, tuple)):  # some jax versions: per-device list
        cost = cost[0] if cost else None
    if not isinstance(cost, dict):
        return None
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "transcendentals": float(cost.get("transcendentals", 0.0))}


def roofline_kind() -> Optional[str]:
    """``device_kind`` of the first device when it is a TPU, else None —
    the kind whose peaks a roofline join divides by."""
    d = jax.devices()[0]
    return d.device_kind if d.platform == "tpu" else None


def join_cost(cost: dict, wall_s: float, device_kind: str) -> dict:
    """Join a cost row with a wall time measured on ``device_kind``:
    achieved GFLOP/s and GB/s, the roofline bound at that chip's published
    peaks (via ``analysis.analyze`` so the compute/memory terms and the
    dominant-term logic are the dry-run's), and the fraction of that bound
    the measured time achieves.  Raises for a device with no peaks."""
    roof = analysis.analyze(
        {"flops": cost["flops"], "bytes accessed": cost["bytes"]},
        hlo_text="", chips=1, device_kind=device_kind)
    pk = hw.peaks(device_kind)
    bound_s = roof.step_time_s
    return {
        "achieved_gflops": cost["flops"] / wall_s / 1e9,
        "achieved_gbps": cost["bytes"] / wall_s / 1e9,
        "peak_gflops": pk.flops_bf16 / 1e9,
        "peak_gbps": pk.hbm_bw / 1e9,
        "bound_us": bound_s * 1e6,
        "roofline_fraction": bound_s / wall_s if wall_s > 0 else 0.0,
        "bound_dominant": roof.dominant,
    }


class CostBook:
    """Per-executable cost ledger + wall-time join (DESIGN.md §16).

    ``record`` runs at compile time (prewarm / executable build) and is
    gated on ``enabled`` so engines built by tests and production paths
    never pay the extra re-trace; ``observe`` runs on the hot path and is
    one dict probe when nothing was recorded.  ``bind`` attaches the Obs
    bundle's registry + tracer so joins land as ``perf.*`` gauges and
    ``roofline.*`` counter tracks.
    """

    def __init__(self, enabled: bool = False):
        self.enabled = enabled
        self.costs: Dict[str, dict] = {}
        self._agg: Dict[str, dict] = {}
        self._metrics = None
        self._tracer = None

    def bind(self, metrics, tracer) -> None:
        self._metrics = metrics
        self._tracer = tracer

    def __contains__(self, name: str) -> bool:
        return name in self.costs

    def record(self, name: str, fn, *args, trip_factor: float = 1.0
               ) -> Optional[dict]:
        """Capture ``fn``'s cost at ``args`` under ``name``.  Idempotent
        per name (an executable's cost is static), no-op unless enabled."""
        if not self.enabled:
            return None
        if name in self.costs:
            return self.costs[name]
        c = exec_cost(fn, *args)
        if c is None:
            return None
        c = {"flops": c["flops"] * trip_factor,
             "bytes": c["bytes"] * trip_factor,
             "transcendentals": c["transcendentals"] * trip_factor,
             "trip_factor": trip_factor}
        self.costs[name] = c
        return c

    def observe(self, name: str, wall_s: float) -> Optional[dict]:
        """Record one measured dispatch of ``name`` and, on a chip with
        published peaks, join it against its recorded cost: emits
        gauges/counter-track and returns the join (None when no cost is on
        record — the disabled-path cost is this one dict probe — or when
        the run is off a TPU)."""
        cost = self.costs.get(name)
        if cost is None or wall_s <= 0:
            return None
        agg = self._agg.setdefault(name, {"calls": 0, "wall_s": 0.0})
        agg["calls"] += 1
        agg["wall_s"] += wall_s
        lab = dict(executable=name)
        if self._metrics is not None:
            self._metrics.histogram("perf.wall_s", **lab).observe(wall_s)
        kind = roofline_kind()
        if kind is None:
            return None
        j = join_cost(cost, wall_s, kind)
        if self._metrics is not None:
            self._metrics.gauge("perf.achieved_gflops", **lab).set(
                j["achieved_gflops"])
            self._metrics.gauge("perf.achieved_gbps", **lab).set(
                j["achieved_gbps"])
            self._metrics.gauge("perf.roofline_fraction", **lab).set(
                j["roofline_fraction"])
        if self._tracer is not None and self._tracer.enabled:
            self._tracer.counter(
                f"roofline.{name}", cat="perf",
                gflops=j["achieved_gflops"], gbps=j["achieved_gbps"],
                frac=j["roofline_fraction"])
        return j

    def summary(self) -> Dict[str, dict]:
        """Per-executable rows: static cost, the mean observed wall time,
        and on a listed chip the join at that mean (executables recorded
        but never dispatched carry the cost alone)."""
        kind = roofline_kind()
        rows: Dict[str, dict] = {}
        for name, cost in sorted(self.costs.items()):
            row = dict(cost)
            agg = self._agg.get(name)
            if agg and agg["calls"]:
                mean = agg["wall_s"] / agg["calls"]
                row.update(calls=agg["calls"], wall_mean_us=mean * 1e6)
                if kind is not None:
                    row.update(join_cost(cost, mean, kind))
            rows[name] = row
        return rows


@contextlib.contextmanager
def xla_profile(outdir: Optional[str]) -> Iterator[None]:
    """Programmatic ``jax.profiler`` capture window: xplane + trace
    artifacts land under ``outdir`` (no-op when ``outdir`` is falsy, so
    call sites thread the ``--xla-profile`` flag through unconditionally).
    """
    if not outdir:
        yield
        return
    jax.profiler.start_trace(outdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@dataclasses.dataclass
class KernelEntry:
    """One kernel to time: ``make`` returns ``(fn, args)`` — a traceable
    callable (statics closed over) and smoke-size operands."""
    name: str
    make: Callable[[], tuple[Callable, tuple]]


def default_registry() -> List[KernelEntry]:
    """The main-path kernels at smoke shapes: standalone softmax fwd/bwd,
    the fused flash fwd and its backward, split-K decode (dense and
    fp2fx8), paged decode (dense and fp2fx8), and the chunk/verify kernel
    (dense and paged fp2fx8).  ``interpret`` comes from ``ops``: compiled
    on a TPU, interpreted elsewhere."""
    from repro.core.hyft import HYFT16
    from repro.kernels.flash_attention import (
        flash_hyft_attention, flash_hyft_decode, flash_hyft_decode_paged,
        flash_hyft_verify)
    from repro.kernels.hyft_softmax import (
        hyft_softmax_bwd_kernel, hyft_softmax_fwd_kernel)
    from repro.kernels.ops import _auto_interpret

    F32, I32, I8 = jnp.float32, jnp.int32, jnp.int8
    cfg, it = HYFT16, _auto_interpret()
    key = jax.random.PRNGKey(0)
    B, Hq, Hkv, D = 2, 4, 2, 16

    def rnd(shape, dtype=F32, k=0):
        if dtype == I8:
            return jax.random.randint(jax.random.fold_in(key, k), shape,
                                      -127, 128, I32).astype(I8)
        return jax.random.normal(jax.random.fold_in(key, k), shape, dtype)

    def qkv(sq, sk, kv_dtype=F32):
        return (rnd((B, Hq, sq, D)), rnd((B, Hkv, sk, D), kv_dtype, 1),
                rnd((B, Hkv, sk, D), kv_dtype, 2))

    entries = [
        KernelEntry("softmax_fwd", lambda: (
            lambda z: hyft_softmax_fwd_kernel(z, cfg, interpret=it),
            (rnd((24, 64)),))),
        KernelEntry("softmax_bwd", lambda: (
            lambda s, dy: hyft_softmax_bwd_kernel(s, dy, cfg, interpret=it),
            (jax.nn.softmax(rnd((24, 64))), rnd((24, 64), k=1)))),
        KernelEntry("flash_fwd", lambda: (
            lambda q, k, v: flash_hyft_attention(
                q, k, v, cfg, block_q=16, block_k=16, interpret=it),
            qkv(32, 32))),
        KernelEntry("flash_bwd", lambda: (
            jax.grad(lambda q, k, v: flash_hyft_attention(
                q, k, v, cfg, block_q=16, block_k=16, interpret=it).sum(),
                argnums=(0, 1, 2)),
            qkv(32, 32))),
    ]

    Sk = 48  # deliberately not lane-aligned: exercises the pad path
    entries.append(KernelEntry("splitk_decode[float32]", lambda: (
        lambda q, k, v: flash_hyft_decode(q, k, v, cfg, block_k=128,
                                          interpret=it),
        qkv(1, Sk))))
    entries.append(KernelEntry("splitk_decode[fp2fx8]", lambda: (
        lambda q, k, v, ks, vs: flash_hyft_decode(
            q, k, v, cfg, block_k=128, interpret=it, k_scale=ks, v_scale=vs),
        qkv(1, Sk, I8) + (rnd((B, Hkv, Sk), k=3), rnd((B, Hkv, Sk), k=4)))))

    n_pages, ps, nb = 6, 8, 3
    bt = jnp.arange(B * nb, dtype=I32).reshape(B, nb) % n_pages

    def paged(qz: bool):
        def make():
            kv_dt = I8 if qz else F32
            kp = rnd((n_pages, Hkv, ps, D), kv_dt, 1)
            vp = rnd((n_pages, Hkv, ps, D), kv_dt, 2)
            sc = ((rnd((n_pages, Hkv, ps), k=3), rnd((n_pages, Hkv, ps), k=4))
                  if qz else (None, None))
            fn = lambda q, kp, vp, bt: flash_hyft_decode_paged(
                q, kp, vp, bt, cfg, interpret=it, k_scale=sc[0],
                v_scale=sc[1])
            return fn, (rnd((B, Hq, 1, D)), kp, vp, bt)
        return make
    entries.append(KernelEntry("paged_decode[float32]", paged(False)))
    entries.append(KernelEntry("paged_decode[fp2fx8]", paged(True)))

    Sq = 4
    entries.append(KernelEntry("verify[dense]", lambda: (
        lambda q, k, v, m: flash_hyft_verify(q, k, v, m, cfg, block_k=128,
                                             interpret=it),
        qkv(Sq, Sk) + (jnp.ones((B, Sq, Sk), F32),))))

    def verify_paged():
        kp = rnd((n_pages, Hkv, ps, D), I8, 1)
        vp = rnd((n_pages, Hkv, ps, D), I8, 2)
        ks, vs = rnd((n_pages, Hkv, ps), k=3), rnd((n_pages, Hkv, ps), k=4)
        fn = lambda q, kp, vp, bt, m: flash_hyft_verify(
            q, kp, vp, m, cfg, interpret=it, block_tables=bt, k_scale=ks,
            v_scale=vs)
        return fn, (rnd((B, Hq, Sq, D)), kp, vp, bt,
                    jnp.ones((B, Sq, nb * ps), F32))
    entries.append(KernelEntry("verify[paged,fp2fx8]", verify_paged))
    return entries


def _block(x) -> None:
    jax.block_until_ready(x)


def microbench(entries=None, iters: int = 5, report=None) -> List[dict]:
    """Time every kernel in the registry (jitted, steady-state) with its
    HLO cost: one row per (kernel, shape, format) with us/call and, on a
    chip with published peaks, GFLOP/s, GB/s, and the roofline fraction.
    ``entries`` defaults to the 10-kernel ``default_registry()``."""
    kind = roofline_kind()
    rows: List[dict] = []
    for entry in entries if entries is not None else default_registry():
        fn, args = entry.make()
        jfn = jax.jit(fn)
        cost = exec_cost(jfn, *args)
        _block(jfn(*args))  # compile  # lint: allow(obs.untimed-hot-path)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = jfn(*args)  # lint: allow(obs.untimed-hot-path)
        _block(out)
        us = (time.perf_counter() - t0) / iters * 1e6
        fmt = entry.name.partition("[")[2].rstrip("]") or "float32"
        row = {"kernel": entry.name, "format": fmt,
               "shapes": ["x".join(map(str, a.shape)) for a in args],
               "dtypes": [str(a.dtype) for a in args],
               "iters": iters, "us_per_call": us}
        if cost is not None:
            row.update(cost)
            if kind is not None:
                row.update(join_cost(cost, us * 1e-6, kind))
        rows.append(row)
        if report is not None:
            frac = row.get("roofline_fraction")
            report(f"bench_kernels,{entry.name},us_per_call={us:.1f},"
                   f"gflops={row.get('achieved_gflops', 0):.3f},"
                   f"gbps={row.get('achieved_gbps', 0):.3f},"
                   f"bound_us={row.get('bound_us', 0):.3f},"
                   f"frac={frac if frac is None else format(frac, '.2e')}")
    return rows
