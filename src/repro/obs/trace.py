"""Host-side span tracer emitting Chrome trace-event JSON (DESIGN.md §15).

The output file loads directly in Perfetto (https://ui.perfetto.dev) or
chrome://tracing.  Three event kinds are used:

  "X"  complete event  — a span with ts+dur (microseconds), from
                         ``Tracer.span(...)`` used as a context manager, or
                         backdated with explicit ends by ``Tracer.complete``
  "i"  instant event   — a point-in-time marker from ``Tracer.instant(...)``
  "C"  counter event   — a sampled value track from ``Tracer.counter(...)``

Every ``span`` is also a ``jax.profiler.TraceAnnotation`` named
``<cat>.<name>`` (``serve.decode_burst``), so under a ``jax.profiler``
capture it lands on the host plane of the device trace, on the device
ops' clock.  The annotation is opened whether or not the tracer is
enabled (the profiler decides whether it records); ``enabled`` decides
only whether the Chrome event is appended.

Overhead, measured on a CPU host with no profiler running: a span costs
about 1.7 us with the tracer disabled (the annotation alone) and 5.5 us
enabled (the Chrome event appended too) — against device calls of
milliseconds to seconds.  No locks, no I/O until ``write()``.

``compile_watch`` turns XLA compile log lines into "compile" spans at
runtime: it is the same ``jax_log_compiles`` listener that
``analysis/retrace.py``'s RetraceGuard is built on (the regexes and the
logging plumbing live here; retrace.py layers its budget/steady-state
policy on top).
"""
from __future__ import annotations

import json
import logging
import math
import os
import re
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

# "Finished tracing + transforming <name> for ..." / "... in N sec" — the
# exact phrasing varies across jax versions, hence the permissive tails.
TRACE_RE = re.compile(r"Finished tracing \+ transforming (.+?) (?:for|in)\b")
COMPILE_RE = re.compile(r"Finished XLA compilation of (.+?) in\b")
_DUR_RE = re.compile(r"in ([0-9.eE+-]+) sec")


def _jsonable(o: Any):
    if hasattr(o, "tolist"):
        return o.tolist()
    return str(o)


def _finite(o: Any):
    """NaN/Inf are not valid JSON — stringify them (e.g. quarantine args
    carrying poisoned numeric stats) so the file stays Perfetto-parseable."""
    if isinstance(o, float) and not math.isfinite(o):
        return repr(o)
    if isinstance(o, dict):
        return {k: _finite(v) for k, v in o.items()}
    if isinstance(o, (list, tuple)):
        return [_finite(v) for v in o]
    return o


class _Span:
    """A profiler annotation, plus a Chrome "X" event when ``record``."""
    __slots__ = ("_tracer", "_name", "_cat", "_args", "_record", "_ann",
                 "_t0")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args: dict,
                 record: bool):
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._args = args
        self._record = record
        self._ann = TraceAnnotation(f"{cat}.{name}")

    def __enter__(self):
        self._ann.__enter__()
        if self._record:
            self._t0 = self._tracer._clock()
        return self

    def __exit__(self, *exc):
        if self._record:
            self._tracer._x(self._name, self._t0, self._tracer._clock(),
                            self._cat, 0, self._args)
        self._ann.__exit__(*exc)
        return False


class Tracer:
    """Appends Chrome trace events to an in-memory list; ``write()`` dumps
    a Perfetto-loadable ``{"traceEvents": [...]}`` JSON file."""

    __slots__ = ("enabled", "events", "_pid", "_clock")

    def __init__(self, enabled: bool = True, clock=time.perf_counter):
        self.enabled = enabled
        self.events: List[Dict[str, Any]] = []
        self._pid = os.getpid()
        self._clock = clock

    def now(self) -> float:
        """The tracer's clock, in seconds."""
        return self._clock()

    def span(self, name: str, cat: str = "serve", **args):
        """Context manager: a profiler annotation ``<cat>.<name>``, and an
        "X" complete event recorded on exit when the tracer is enabled."""
        return _Span(self, name, cat, args, self.enabled)

    def complete(self, name: str, t0: float, t1: float, cat: str = "serve",
                 tid: int = 0, **args) -> None:
        """An "X" event from ``t0`` to ``t1`` (seconds on the tracer's
        clock), recorded after the fact — a request's queue wait, a
        compile whose duration arrives from the log stream."""
        if self.enabled:
            self._x(name, t0, t1, cat, tid, args)

    def _x(self, name, t0, t1, cat, tid, args) -> None:
        self.events.append({
            "name": name, "ph": "X", "ts": t0 * 1e6, "dur": (t1 - t0) * 1e6,
            "pid": self._pid, "tid": tid, "cat": cat, "args": args,
        })

    def instant(self, name: str, cat: str = "serve", **args) -> None:
        if not self.enabled:
            return
        self.events.append({
            "name": name, "ph": "i", "ts": self._clock() * 1e6,
            "pid": self._pid, "tid": 0, "cat": cat, "s": "t", "args": args,
        })

    def counter(self, name: str, cat: str = "serve", **values) -> None:
        if not self.enabled:
            return
        self.events.append({
            "name": name, "ph": "C", "ts": self._clock() * 1e6,
            "pid": self._pid, "tid": 0, "cat": cat, "args": values,
        })

    def compile_span(self, name: str, dur_s: float, kind: str) -> None:
        """Backdated span ending now — compile durations arrive after the
        fact from the jax log stream."""
        if not self.enabled:
            return
        t1 = self._clock()
        self.complete("compile", t1 - dur_s, t1, cat="compile", tid=1,
                      fn=name, kind=kind)

    def span_kinds(self) -> set:
        return {e["name"] for e in self.events}

    def write(self, path: str) -> None:
        events = [dict(e, args=_finite(e.get("args", {})))
                  for e in self.events]
        with open(path, "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms"},
                      f, default=_jsonable)


NULL_TRACER = Tracer(enabled=False)


class CompileListener(logging.Handler):
    """Collects jax trace/compile log lines; optionally stamps "compile"
    spans into a tracer as they happen."""

    def __init__(self, tracer: Optional[Tracer] = None):
        super().__init__(level=logging.DEBUG)
        self.traces: List[str] = []
        self.compiles: List[str] = []
        self.tracer = tracer

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        m = TRACE_RE.search(msg)
        if m:
            self.traces.append(m.group(1))
            if self.tracer is not None:
                dm = _DUR_RE.search(msg)
                self.tracer.compile_span(
                    m.group(1), float(dm.group(1)) if dm else 0.0, "trace")
            return
        m = COMPILE_RE.search(msg)
        if m:
            self.compiles.append(m.group(1))
            if self.tracer is not None:
                dm = _DUR_RE.search(msg)
                self.tracer.compile_span(
                    m.group(1), float(dm.group(1)) if dm else 0.0, "xla")


class compile_watch:
    """Context manager routing jax compile logs into a CompileListener.

    Flips ``jax_log_compiles`` on and pins the "jax" logger (level INFO,
    propagation off) for the duration, restoring everything on exit.
    ``compile_watch(tracer)`` with a disabled/None tracer still counts
    compiles (``.listener``); pass ``enabled=False`` to make it a no-op.
    Nesting is safe — each watch attaches its own handler.
    """

    def __init__(self, tracer: Optional[Tracer] = None,
                 enabled: bool = True):
        self.listener = CompileListener(
            tracer if tracer is not None and tracer.enabled else None)
        self._enabled = enabled
        self._logger = logging.getLogger("jax")

    def __enter__(self) -> "compile_watch":
        if not self._enabled:
            return self
        import jax
        self._flag = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        self._level = self._logger.level
        self._propagate = self._logger.propagate
        self._logger.setLevel(logging.INFO)
        self._logger.propagate = False
        # park the logger's own handlers (jax installs a stderr
        # StreamHandler) so compile records feed the listener, not stderr;
        # other CompileListeners stay attached so nested watches both count
        self._parked = [h for h in self._logger.handlers
                        if not isinstance(h, CompileListener)]
        for h in self._parked:
            self._logger.removeHandler(h)
        self._logger.addHandler(self.listener)
        return self

    def __exit__(self, *exc) -> bool:
        if not self._enabled:
            return False
        import jax
        self._logger.removeHandler(self.listener)
        for h in self._parked:
            self._logger.addHandler(h)
        self._logger.setLevel(self._level)
        self._logger.propagate = self._propagate
        jax.config.update("jax_log_compiles", self._flag)
        return False
