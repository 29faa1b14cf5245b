"""A training cell at smoke size on the CPU: the check passes the
program as it is, and comes out false under each fault the cell can have
(the step returns its state unchanged; half of the batch left out, the
mean taken over the rest), and so does the control: the reference computed
in float8 in the program's place."""
import jax
import jax.numpy as jnp
import pytest

from bench import arch, train_cell
from bench.tests import smoke


def _run(fault=None, seed=smoke.SEED):
    m, job = smoke.train_cell()
    return train_cell.run(m, job, seed, 1.0, None, smoke.clock(),
                          fault=fault)


def _failed(numbers) -> bool:
    return any(v > lim for v, lim in numbers.values())


def test_sound_run_is_correct():
    rec = _run()
    assert not _failed(rec["check"]["numbers"]), rec["check"]
    assert rec["e2e"]["train_step_ms"] > 0 and rec["side"]["steps"] > 0
    assert rec["check"]["leaves_left_out"] == 0


def unchanged(step, feed):
    def bad(state, batch):
        _, metrics = step(jax.tree.map(jnp.copy, state), batch)
        return state, metrics
    return bad, feed


def half_batch(step, feed):
    def fed(i):
        b = feed(i)
        return dict(b, mask=b["mask"].at[b["mask"].shape[0] // 2:].set(0))
    return step, fed


@pytest.mark.parametrize("fault", [unchanged, half_batch])
def test_fault_is_not_correct(fault):
    rec = _run(fault)
    assert _failed(rec["check"]["numbers"]), rec["check"]


def test_the_control_is_not_correct():
    m, job = smoke.train_cell()
    params0 = arch.load(m).make_weights(m, m["train"]["param_dtype"],
                                        smoke.SEED)
    feed = train_cell.make_feed(m, job, smoke.SEED)
    batches = [feed(i) for i in range(job["check_steps"])]
    ref = train_cell.reference_steps(m, job, params0, batches)
    ctrl = train_cell.reference_steps(m, job, params0, batches, quant="fp8")
    got = train_cell.compare(ctrl, ref)
    assert _failed({k: (got[k], job["check"][k]) for k in train_cell.NUMBERS})
