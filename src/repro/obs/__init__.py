"""Observability bundle: span tracing + metrics registry + numeric telemetry.

One ``Obs`` object threads all three pillars (DESIGN.md §15) through an
engine:

  tracer   — host-side span tracer emitting Chrome trace-event JSON
             (``repro.obs.trace``; load the file in Perfetto /
             chrome://tracing); every span is also a ``jax.profiler``
             annotation, so a profiler capture (``--xla-profile``) shows
             the host phases on the device ops' clock
  metrics  — typed counters / gauges / streaming-percentile histograms
             (``repro.obs.metrics``); the scheduler's legacy ``stats`` dict
             is a read-only view over this registry
  numerics — hybrid-format telemetry accumulator (``repro.obs.numerics``):
             softmax-input exponent range, fp2fx8 scale histograms, int8
             saturation, convert volume — fed per burst when
             ``ServeConfig.telemetry`` is on

Device time, and with it every roofline share, comes from a profiler
capture (``repro.obs.profile.xla_profile``), never from host walls.

Every ``SlotPoolEngine`` owns an Obs (a fresh disabled-tracer one by
default, so two engines never share counters unless the caller passes a
shared bundle on purpose).  ``metrics_path`` + ``snapshot_every_s`` turn on
periodic JSONL snapshot export from inside the serving loop.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

from repro.obs.metrics import Registry
from repro.obs.numerics import NumericsMonitor
from repro.obs.trace import NULL_TRACER, Tracer, compile_watch  # noqa: F401


@dataclasses.dataclass
class Obs:
    tracer: Tracer = dataclasses.field(
        default_factory=lambda: Tracer(enabled=False))
    metrics: Registry = dataclasses.field(default_factory=Registry)
    numerics: NumericsMonitor = dataclasses.field(
        default_factory=NumericsMonitor)
    # periodic metrics JSONL export (None = no export); snapshots are
    # appended from the serving loop every ``snapshot_every_s`` seconds and
    # once more at the end of every run
    metrics_path: Optional[str] = None
    snapshot_every_s: float = 1.0
    _last_snapshot: float = dataclasses.field(default=0.0, repr=False)

    @classmethod
    def enabled(cls, metrics_path: Optional[str] = None,
                snapshot_every_s: float = 1.0) -> "Obs":
        """An Obs with the tracer ON (the ``--trace`` bundle)."""
        return cls(tracer=Tracer(enabled=True),
                   metrics_path=metrics_path,
                   snapshot_every_s=snapshot_every_s)

    def maybe_snapshot(self, force: bool = False) -> None:
        """Append a metrics snapshot line to ``metrics_path`` if the export
        cadence (or ``force``) says so.  No-op without a path."""
        if self.metrics_path is None:
            return
        now = time.monotonic()
        if force or now - self._last_snapshot >= self.snapshot_every_s:
            self._last_snapshot = now
            self.metrics.write_jsonl(self.metrics_path)
