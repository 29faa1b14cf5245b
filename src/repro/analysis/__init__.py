"""Static analysis & invariant checks (DESIGN.md #14).

Three passes, each returning ``list[Finding]`` from its ``run()``:

- ``jaxpr_audit``  -- format-flow auditor over the real executables
- ``retrace``      -- steady-state serving compiles nothing new
- ``lint``         -- AST rules over src/ and scripts/

``scripts/check.py`` drives all three; CI fails on any finding.  Whether a
Pallas kernel tiles is the TPU compiler's to say: ``tests/test_tpu_compile.py``
compiles every main-path kernel for a described v5e.
"""
from repro.analysis.common import Finding
from repro.analysis.retrace import RetraceError, RetraceGuard

__all__ = ["Finding", "RetraceError", "RetraceGuard",
           "jaxpr_audit", "retrace", "lint"]
