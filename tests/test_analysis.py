"""Each analysis pass must catch its seeded violation and pass the repo.

The seeded fixtures are traced/parsed only — never executed — so a broken
index map or a smuggled convert costs a trace, not a crash.
"""
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import lint
from repro.analysis import jaxpr_audit
from repro.analysis.common import Finding
from repro.analysis.retrace import RetraceError, RetraceGuard, serve_steady_state

F32 = jnp.float32


@pytest.fixture(autouse=True, scope="module")
def _isolate_executable_caches():
    """The audit/retrace passes build real serving executables; restore the
    process-global FIFO caches afterwards so this module doesn't push later
    tests' entries toward the eviction cap."""
    from repro.serve import engine, scheduler, spec
    stores = [engine._PREFILL_CACHE, engine._STEP_CACHE, engine._LOOP_CACHE,
              engine._CHUNK_CACHE, scheduler._BURST_CACHE,
              scheduler._SCATTER_CACHE, scheduler._AXES_CACHE,
              scheduler._ENCODE_CACHE, spec._DRAFT_LOOP_CACHE,
              spec._SPEC_CACHE]
    snaps = [dict(s) for s in stores]
    yield
    for store, snap in zip(stores, snaps):
        store.clear()
        store.update(snap)


# -- jaxpr format-flow auditor ----------------------------------------------


def test_jaxpr_catches_weak_promotion():
    # jnp.where(x < 0, -1, 0) builds a weak-typed rank-1 i32 that the add
    # then promotes to f32 — the exact bug fixed in numerics.log_div
    def bad(x):
        return x + jnp.where(x < 0, -1, 0)

    closed = jax.make_jaxpr(bad)(jnp.zeros(8, F32))
    rules = {f.rule for f in jaxpr_audit.audit_jaxpr(closed, "seeded")}
    assert "format.weak-promotion" in rules


def test_jaxpr_catches_undeclared_convert():
    # int8 -> float16 is not a declared boundary (DESIGN.md #14)
    def bad(x):
        return x.astype(jnp.float16) * jnp.float16(2)

    closed = jax.make_jaxpr(bad)(jnp.zeros((4, 4), jnp.int8))
    rules = {f.rule for f in jaxpr_audit.audit_jaxpr(closed, "seeded")}
    assert "format.undeclared-convert" in rules


def test_jaxpr_scalar_weak_convert_is_note_not_finding():
    def ok(x):
        # rank-0 weak i32 -> f32 convert: churn, folded by XLA
        return jnp.where(x.sum() > 0, 1, 0) * x

    closed = jax.make_jaxpr(ok)(jnp.zeros(8, F32))
    stats = {}
    assert jaxpr_audit.audit_jaxpr(closed, "ok", stats=stats) == []
    assert stats.get("scalar_weak_converts", 0) >= 1


def test_jaxpr_donation_check():
    def step(params, cache):
        return {"k": cache["k"] + params}

    args = (jnp.ones(4), {"k": jnp.zeros(4)})
    bad = jax.jit(step)
    good = jax.jit(step, donate_argnums=(1,))
    assert any(f.rule == "donation.cache-not-donated"
               for f in jaxpr_audit.audit_donation(bad, args, 1, "bad"))
    assert jaxpr_audit.audit_donation(good, args, 1, "good") == []


@pytest.mark.slow
def test_jaxpr_repo_clean():
    assert jaxpr_audit.run() == []


# -- retrace guard -----------------------------------------------------------


def test_retrace_guard_catches_fresh_compile():
    f = jax.jit(lambda x: x * 2 + 1)
    with pytest.raises(RetraceError, match="compilation"):
        with RetraceGuard():
            f(jnp.zeros(7))  # never-seen shape: must compile


def test_retrace_guard_warm_call_is_clean():
    f = jax.jit(lambda x: x * 3 - 1)
    x = jnp.zeros(5)
    f(x)  # cold call outside the guard
    with RetraceGuard() as g:
        f(x)
    assert g.compiles == []


def test_retrace_guard_budget_and_restore():
    prev = jax.config.jax_log_compiles
    f = jax.jit(lambda x: x - 4)
    x = jnp.zeros(11)  # built outside: jnp.zeros itself compiles
    with RetraceGuard(max_compiles=1) as g:
        f(x)
    assert len(g.compiles) == 1
    assert jax.config.jax_log_compiles == prev


@pytest.mark.slow
def test_retrace_steady_state_serving():
    # 8 admissions through warm buckets + decode bursts compile nothing new
    guard = serve_steady_state("continuous", n_requests=8)
    assert guard.compiles == []


# -- repo lint ---------------------------------------------------------------


_SEEDED = textwrap.dedent("""
    import functools
    import numpy as np
    import jax
    import jax.numpy as jnp

    @jax.jit
    def bad_branch(x):
        if x > 0:              # traced-bool
            return x
        return -x

    @jax.jit
    def bad_host(x):
        y = float(x)           # host-call
        return np.tanh(x) + y  # host-call (np. on traced)

    @jax.jit
    def bad_seed(x):
        k = jax.random.PRNGKey(0)  # prng.constant-seed
        return x + jax.random.normal(k, x.shape)

    @functools.partial(jax.jit, static_argnames=("n",))
    def bad_cache_step(params, cache, n):   # cache.not-donated
        return cache
""").strip()


def _lint_snippet(src: str):
    import tempfile, pathlib
    with tempfile.TemporaryDirectory() as d:
        p = pathlib.Path(d) / "snippet.py"
        p.write_text(src)
        return lint.run(roots=[pathlib.Path(d)])


def test_lint_catches_all_seeded_rules():
    rules = {f.rule for f in _lint_snippet(_SEEDED)}
    assert {"traced-bool", "host-call",
            "prng.constant-seed", "cache.not-donated"} <= rules


def test_lint_static_arg_branch_is_allowed():
    ok = textwrap.dedent("""
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("flag",))
        def f(x, flag):
            if flag:           # static: not traced
                return x
            return -x
    """).strip()
    assert [f for f in _lint_snippet(ok) if f.rule == "traced-bool"] == []


def test_lint_waiver_comment():
    waived = textwrap.dedent("""
        import jax

        @jax.jit
        def f(x):
            if x > 0:  # lint: allow(traced-bool)
                return x
            return -x
    """).strip()
    assert [f for f in _lint_snippet(waived) if f.rule == "traced-bool"] == []


def test_lint_repo_clean():
    assert lint.run() == []


def test_finding_str():
    f = Finding("lint", "traced-bool", "a.py:3", "boom")
    assert str(f) == "[lint.traced-bool] a.py:3 -- boom"
