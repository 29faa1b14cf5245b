"""Training loop: checkpoint/restart, straggler monitoring, metrics."""
from __future__ import annotations

import time
from typing import Callable, Optional

import jax

from repro.checkpoint import checkpointer
from repro.configs.base import TrainConfig
from repro.distributed.fault_tolerance import RestartManager, StragglerMonitor
from repro.obs.trace import NULL_TRACER, Tracer


def run_train(state, train_step, batch_fn: Callable[[int], dict],
              tcfg: TrainConfig, ckpt_dir: Optional[str] = None,
              state_sh=None, log_every: int = 10,
              fail_at: Optional[Callable[[int], None]] = None,
              log_fn=print, tracer: Tracer = NULL_TRACER
              ) -> tuple[dict, list]:
    """Run the loop with fault tolerance. ``fail_at`` injects faults (tests).

    Returns (final state, metric history).  If ``ckpt_dir`` is set the loop
    is supervised by RestartManager: any exception reloads the latest atomic
    checkpoint and resumes (deterministic data stream keyed by step).
    Each phase of a step runs under a ``tracer`` span of category
    ``train`` — ``batch``, ``step`` (the dispatch), ``log`` (the metrics
    sync), ``straggler``, ``checkpoint`` — and so under a profiler
    annotation ``train.<phase>`` even with the default disabled tracer.
    """
    history: list = []
    monitor = StragglerMonitor()
    state_box = {"state": state}

    def body(start_step: int) -> int:
        if ckpt_dir and checkpointer.latest_step(ckpt_dir) is not None:
            st, step0 = checkpointer.restore(
                ckpt_dir, checkpointer.latest_step(ckpt_dir),
                jax.eval_shape(lambda: state_box["state"]), shardings=state_sh)
            state_box["state"] = st
            start_step = step0
        for step in range(start_step, tcfg.total_steps):
            if fail_at is not None:
                fail_at(step)  # may raise (fault injection)
            t0 = time.monotonic()
            with tracer.span("batch", cat="train", step=step):
                batch = batch_fn(step)
            with tracer.span("step", cat="train", step=step):
                state_box["state"], metrics = train_step(state_box["state"],
                                                         batch)
            if step % log_every == 0 or step == tcfg.total_steps - 1:
                with tracer.span("log", cat="train", step=step):
                    m = {k: float(v) for k, v in metrics.items()}
                    history.append({"step": step, **m})
                    log_fn(f"step {step:5d} " +
                           " ".join(f"{k}={v:.4f}" for k, v in m.items()))
            with tracer.span("straggler", cat="train", step=step):
                dt = time.monotonic() - t0
                if monitor.observe(dt):
                    log_fn(f"[straggler] step {step} took {dt:.3f}s "
                           f"(ema {monitor.ema:.3f}s)")
            if ckpt_dir and (step + 1) % tcfg.checkpoint_every == 0:
                with tracer.span("checkpoint", cat="train", step=step):
                    checkpointer.save(ckpt_dir, step + 1, state_box["state"],
                                      keep=tcfg.keep_checkpoints)
        return tcfg.total_steps

    if ckpt_dir:
        RestartManager(ckpt_dir).run(body)
    else:
        body(0)
    return state_box["state"], history
