"""jaxpr format-flow auditor.

Hyft's contract is that every intermediate lives in the format the next op
wants (DESIGN.md #14): conversions happen at the declared FP2FX / FX2FP /
quantize / mask boundaries and nowhere else.  This pass traces the *real*
executables (chunked prefill, decode burst, spec verify step, host serve
step, scanned decode loop, train step) to ClosedJaxprs and walks every eqn:

``format.f64``            any float64 value or convert target (an x64 leak
                          would silently double HBM traffic on every path).
``format.weak-promotion`` a ``convert_element_type`` whose input is a
                          *weak-typed* array of rank >= 1: a Python scalar
                          was broadcast against a tensor and the promotion
                          materialized in the hot path instead of folding.
``format.undeclared-convert``  a rank >= 1 dtype change whose (src, dst)
                          pair is not a declared format boundary.
``host.op-in-loop``       callbacks / ``device_put`` inside a scan or while
                          body -- a host round-trip per decode step.
``donation.cache-not-donated``  an executable that threads a KV cache whose
                          lowered HLO does not alias every cache leaf to an
                          output (each step then copies the whole cache).

Scalar (rank-0) weak converts are NOT findings: XLA constant-folds them.
They are tallied and reported by ``scripts/check.py --verbose`` as churn.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.analysis.common import Finding, eqn_location, walk_eqns

# Declared format boundaries (DESIGN.md #14): (src, dst) dtype-name pairs a
# rank >= 1 convert_element_type may legitimately cross.  Everything else in
# a traced executable is a finding.
DECLARED_BOUNDARIES: frozenset[tuple[str, str]] = frozenset({
    # FP2FX / FX2FP and float-field assembly (numerics.py)
    ("float32", "int32"), ("int32", "float32"),
    # fp2fx8 KV-cache quantize (store) and fused dequant (load)
    ("int32", "int8"), ("float32", "int8"),
    ("int8", "int32"), ("int8", "float32"),
    # masks / gates / validity lanes
    ("bool", "int32"), ("bool", "float32"),
    ("int32", "bool"), ("float32", "bool"),
    # parameter / activation precision (mixed-precision configs)
    ("float32", "bfloat16"), ("bfloat16", "float32"),
    ("float32", "float16"), ("float16", "float32"),
})

_HOST_PRIMS = frozenset({
    "io_callback", "pure_callback", "debug_callback", "callback",
    "device_put", "infeed", "outfeed",
})


@dataclasses.dataclass
class AuditTarget:
    """One executable to audit.

    ``make`` returns ``(fn, args)`` at smoke size; ``cache_argnum`` names the
    positional arg holding the KV cache/pool (``None`` = no cache threaded,
    donation not checked).  ``fn`` must be the *jitted* callable so the
    donation check can lower it.
    """
    name: str
    make: Callable[[], tuple[Callable, tuple]]
    cache_argnum: int | None = None


def audit_jaxpr(closed, name: str,
                stats: dict[str, int] | None = None) -> list[Finding]:
    """Walk one ClosedJaxpr applying the format-flow rules."""
    findings: list[Finding] = []
    for eqn, in_loop in walk_eqns(closed.jaxpr):
        prim = eqn.primitive.name
        if prim in _HOST_PRIMS and in_loop:
            findings.append(Finding(
                "jaxpr", "host.op-in-loop", eqn_location(eqn),
                f"{name}: `{prim}` inside a scan/while body -- host "
                f"round-trip per loop step"))
        for var in eqn.outvars:
            aval = var.aval
            if getattr(aval, "dtype", None) is not None \
                    and str(aval.dtype) == "float64":
                findings.append(Finding(
                    "jaxpr", "format.f64", eqn_location(eqn),
                    f"{name}: float64 value produced by `{prim}`"))
        if prim != "convert_element_type":
            continue
        src = eqn.invars[0].aval
        src_dt, dst_dt = str(src.dtype), str(eqn.params["new_dtype"])
        if dst_dt == "float64":
            findings.append(Finding(
                "jaxpr", "format.f64", eqn_location(eqn),
                f"{name}: convert {src_dt} -> float64"))
            continue
        weak = bool(getattr(src, "weak_type", False))
        if len(src.shape) == 0:
            if stats is not None and weak:
                stats["scalar_weak_converts"] = \
                    stats.get("scalar_weak_converts", 0) + 1
            continue
        if weak:
            findings.append(Finding(
                "jaxpr", "format.weak-promotion", eqn_location(eqn),
                f"{name}: weak-typed {src_dt}{list(src.shape)} converted to "
                f"{dst_dt} -- a Python scalar was broadcast against a "
                f"tensor before the cast"))
        elif src_dt != dst_dt and (src_dt, dst_dt) not in DECLARED_BOUNDARIES:
            findings.append(Finding(
                "jaxpr", "format.undeclared-convert", eqn_location(eqn),
                f"{name}: {src_dt} -> {dst_dt} on shape {list(src.shape)} is "
                f"not a declared format boundary (DESIGN.md #14)"))
    return findings


# -- donation ---------------------------------------------------------------

_ARG_RE = re.compile(r"%arg(\d+):")


def _aliased_arg_indices(hlo_text: str) -> set[int]:
    """Flat arg indices of ``@main`` carrying ``tf.aliasing_output`` (the
    StableHLO marker for a donated buffer that the compiler accepted)."""
    m = re.search(r"func\.func public @main\(", hlo_text)
    if m is None:
        return set()
    end = hlo_text.find(") -> ", m.end())
    sig = hlo_text[m.end():end if end != -1 else m.end()]
    out: set[int] = set()
    spans = list(_ARG_RE.finditer(sig))
    for i, am in enumerate(spans):
        end = spans[i + 1].start() if i + 1 < len(spans) else len(sig)
        if "tf.aliasing_output" in sig[am.end():end]:
            out.add(int(am.group(1)))
    return out


def audit_donation(fn, args: tuple, cache_argnum: int, name: str) -> list[Finding]:
    """Check every leaf of ``args[cache_argnum]`` is donated (aliased to an
    output) in the lowered HLO of the jitted ``fn``."""
    try:
        text = fn.lower(*args).as_text()
    except Exception as e:  # not a jit-wrapped callable, or lowering failed
        return [Finding("jaxpr", "donation.unlowerable", name,
                        f"could not lower for the donation check: {e!r}")]
    aliased = _aliased_arg_indices(text)
    offset = sum(len(jax.tree_util.tree_leaves(a))
                 for a in args[:cache_argnum])
    keys = [jax.tree_util.keystr(kp) for kp, _ in
            jax.tree_util.tree_flatten_with_path(args[cache_argnum])[0]]
    findings = []
    for i, key in enumerate(keys):
        if offset + i not in aliased:
            findings.append(Finding(
                "jaxpr", "donation.cache-not-donated", name,
                f"cache leaf {key or '<root>'} (flat arg {offset + i}) is "
                f"not aliased to an output -- every call copies it"))
    return findings


# -- the real-executable registry -------------------------------------------


def default_targets() -> list[AuditTarget]:
    """The serving/training executables, built at smoke size (the shapes CI
    can afford; the rules are shape-independent)."""
    from repro.configs import get_config, smoke_config
    from repro.configs.base import ServeConfig, TrainConfig
    from repro.models import build_model, resolve_attn_mode
    from repro.models.layers import unbox
    from repro.serve import engine, scheduler, spec
    from repro.train.step import make_step_fn
    from repro import optim

    I32 = jnp.int32
    cfg = smoke_config(get_config("qwen2-1.5b")).with_(
        softmax_impl="hyft16", vocab=64)
    model = build_model(cfg)
    params = unbox(model.init(jax.random.PRNGKey(0)))
    n, L, W, K = 3, 32, 8, 3

    def serve_parts(cache_dtype):
        scfg = ServeConfig(max_len=L, cache_dtype=cache_dtype, n_slots=n,
                           decode_burst=4, attn_mode="kernel", draft_k=K)
        m = resolve_attn_mode(model, scfg.attn_mode)
        bkey = scheduler.exec_key_cfg(scfg)
        cache = m.init_cache(params, n, L, cache_dtype)
        return scfg, bkey, m, cache

    def mk_prefill_chunk(cache_dtype):
        def make():
            scfg, bkey, m, cache = serve_parts(cache_dtype)
            fn = engine.build_prefill_chunk(m, bkey, W)
            args = (params, cache, jnp.zeros((n, W), I32), jnp.zeros(n, I32),
                    jnp.ones(n, I32), jnp.zeros(n, bool))
            return fn, args
        return make

    def mk_burst(cache_dtype):
        def make():
            scfg, bkey, m, cache = serve_parts(cache_dtype)
            fn = scheduler.build_burst(m, bkey, scfg.decode_burst)
            args = (params, cache, jnp.zeros((n, 1), I32), jnp.ones(n, I32),
                    jnp.zeros(n, bool), jnp.ones(n, I32),
                    jnp.full(n, scheduler.TTL_NONE, I32),
                    jax.random.PRNGKey(0))
            return fn, args
        return make

    def mk_spec_step(cache_dtype):
        def make():
            scfg, bkey, m, cache = serve_parts(cache_dtype)
            fn = spec.build_spec_step(m, bkey, K)
            args = (params, cache, jnp.zeros((n, 1), I32),
                    jnp.zeros((n, K), I32), jnp.zeros(n, I32),
                    jnp.ones(n, I32), jnp.zeros(n, bool), jnp.ones(n, I32))
            return fn, args
        return make

    def mk_serve_step():
        scfg, bkey, m, cache = serve_parts("float32")
        fn = engine.build_serve_step(m, scfg)
        return fn, (params, cache, jnp.zeros((n, 1), I32), 4,
                    jax.random.PRNGKey(0))

    def mk_decode_loop():
        scfg, bkey, m, cache = serve_parts("float32")
        fn = engine.build_decode_loop(m, scfg, 4)
        return fn, (params, cache, jnp.zeros((n, 1), I32), 4,
                    jax.random.PRNGKey(0))

    def mk_train_step():
        step = jax.jit(make_step_fn(model, TrainConfig(), optim.OptConfig()),
                       donate_argnums=(0,))
        state = {"params": params,
                 "opt": optim.init(optim.OptConfig(), params),
                 "step": jnp.zeros((), I32), "rng": jax.random.PRNGKey(0)}
        batch = {"tokens": jnp.zeros((2, 16), I32),
                 "targets": jnp.zeros((2, 16), I32)}
        return step, (state, batch)

    targets = []
    for cd in ("float32", "fp2fx8"):
        targets.append(AuditTarget(f"prefill_chunk[{cd}]",
                                   mk_prefill_chunk(cd), cache_argnum=1))
        targets.append(AuditTarget(f"decode_burst[{cd}]", mk_burst(cd),
                                   cache_argnum=1))
        targets.append(AuditTarget(f"spec_step[{cd}]", mk_spec_step(cd),
                                   cache_argnum=1))
    targets.append(AuditTarget("serve_step[float32]", mk_serve_step,
                               cache_argnum=1))
    targets.append(AuditTarget("decode_loop[float32]", mk_decode_loop,
                               cache_argnum=1))
    targets.append(AuditTarget("train_step", mk_train_step, cache_argnum=None))
    return targets


def run(targets: list[AuditTarget] | None = None,
        stats: dict[str, int] | None = None) -> list[Finding]:
    """Audit every target; returns all findings (empty = clean)."""
    findings: list[Finding] = []
    for t in targets if targets is not None else default_targets():
        fn, args = t.make()
        closed = jax.make_jaxpr(fn)(*args)
        findings += audit_jaxpr(closed, t.name, stats=stats)
        if t.cache_argnum is not None:
            findings += audit_donation(fn, args, t.cache_argnum, t.name)
    return findings
