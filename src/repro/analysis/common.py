"""Shared plumbing for the static-analysis passes.

A ``Finding`` is one violated invariant, printable as
``[pass.rule] where -- detail``.  The jaxpr helpers here are the only place
that touches JAX internals for eqn walking, so an upstream API move breaks
one module, not four.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator

from jax._src import core as jcore
from jax._src import source_info_util


@dataclasses.dataclass(frozen=True)
class Finding:
    """One violated invariant surfaced by an analysis pass."""
    pass_name: str   # "jaxpr" | "pallas" | "retrace" | "lint"
    rule: str        # e.g. "format.weak-promotion"
    where: str       # "file:line" or the executable/kernel name
    detail: str

    def __str__(self) -> str:
        return f"[{self.pass_name}.{self.rule}] {self.where} -- {self.detail}"


def subjaxprs(eqn: jcore.JaxprEqn) -> list[jcore.Jaxpr]:
    """Sub-jaxprs carried in an eqn's params (scan/while/cond/jit bodies,
    custom-vjp branches, Pallas index maps are NOT included -- those live in
    grid_mapping and are handled by the tile checker)."""
    out: list[jcore.Jaxpr] = []
    for v in eqn.params.values():
        items = v if isinstance(v, (list, tuple)) else [v]
        for x in items:
            if isinstance(x, jcore.ClosedJaxpr):
                out.append(x.jaxpr)
            elif isinstance(x, jcore.Jaxpr):
                out.append(x)
    return out


_LOOP_PRIMS = frozenset({"scan", "while"})


def walk_eqns(jaxpr: jcore.Jaxpr,
              in_loop: bool = False) -> Iterator[tuple[jcore.JaxprEqn, bool]]:
    """Yield every eqn in the jaxpr tree with a flag marking whether it sits
    inside a ``lax.scan`` / ``lax.while_loop`` body."""
    for eqn in jaxpr.eqns:
        yield eqn, in_loop
        inner = in_loop or eqn.primitive.name in _LOOP_PRIMS
        for sub in subjaxprs(eqn):
            yield from walk_eqns(sub, inner)


def eqn_location(eqn: jcore.JaxprEqn) -> str:
    """Best-effort ``file:line`` for an eqn, preferring repo frames over the
    caller's trace harness."""
    frames = list(source_info_util.user_frames(eqn.source_info.traceback))
    for fr in frames:
        if "/src/repro/" in fr.file_name.replace("\\", "/"):
            return f"{fr.file_name}:{fr.start_line}"
    if frames:
        return f"{frames[0].file_name}:{frames[0].start_line}"
    return "<unknown>"
