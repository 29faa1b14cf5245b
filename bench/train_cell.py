"""A training cell: the program's trainer (``launch.train.build_trainer``)
driven by ``train.loop.run_train``.

Set-up builds one object, the compiled step with its state, from weights
the benchmark makes from the seed, and drives it through the first
``check_steps`` steps by the window's own call and feed.  It keeps what the
check needs from those steps: each loss, the gradient the optimizer got in
step 1 (Adam's first moment after one step is (1 - b1) times it) and each
leaf's change over the first steps.  The window then goes on with the same
object, in calls of ``steps_per_call`` steps that each end on
``block_until_ready``, until ``seconds`` have passed.  After the window the
plain reference follows the same first steps from the same weights.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import time

import numpy as np

from bench import costs
from bench.serve_cell import peak_bytes


def _mesh(shape):
    from repro.launch.mesh import make_host_mesh
    return make_host_mesh(tuple(shape))


def build(m: dict, job: dict, seed: int):
    """Trainer, state, jitted step, shardings and the feed, as the program
    builds them, with the state's parameters replaced by the benchmark's."""
    import jax
    from repro import optim
    from repro.configs import get_config
    from repro.configs.base import TrainConfig
    from repro.launch.train import build_trainer

    from bench import weights
    from bench.serve_cell import model_config
    tr = m["train"]
    cfg = model_config(get_config, m, dict(tr, attn_mode=job["attn_mode"]))
    tcfg = TrainConfig(global_batch=job["global_batch"],
                       seq_len=job["seq_len"], total_steps=job["total_steps"],
                       warmup_steps=job["warmup_steps"], lr=job["lr"],
                       grad_clip=job["grad_clip"], attn_mode=job["attn_mode"])
    ocfg = optim.OptConfig(name="adamw", lr=job["lr"], b1=job["b1"],
                           b2=job["b2"], eps=job["eps"],
                           weight_decay=job["weight_decay"])
    mesh = _mesh(job["mesh"])
    _, state, step, state_sh = build_trainer(cfg, tcfg, ocfg, mesh, seed=seed)
    params0 = weights.make(m, tr["param_dtype"], seed)
    weights.check_layout(params0, state["params"])
    with mesh:
        # fresh buffers: the step donates its state, and params0 must
        # outlive it for the reference
        put = jax.jit(lambda p: (jax.tree.map(lambda x: x.copy(), p),
                                 jax.tree.map(lambda x: x.copy(), p)),
                      out_shardings=(state_sh["params"],
                                     state_sh["opt"]["master"]))
        state["params"], state["opt"]["master"] = put(params0)
    return mesh, tcfg, state, step, state_sh, params0, make_feed(m, job, seed)


def make_feed(m: dict, job: dict, seed: int):
    """``feed(i)``: the program's ``lm_batch`` for step ``i`` of the seed's
    data.  The seed enters as an argument, not a constant, so every seed
    runs the one compiled program."""
    import jax
    import jax.numpy as jnp
    from repro.data.synthetic import DataConfig, lm_batch
    dcfg = DataConfig(vocab=m["vocab"], seq_len=job["seq_len"],
                      global_batch=job["global_batch"])
    gen = jax.jit(lambda s, i: lm_batch(dataclasses.replace(dcfg, seed=s), i))
    s = jnp.int32(seed % (1 << 27))
    return lambda i: gen(s, i)


def leaf_norms(tree):
    """Per-leaf L2 norms, on the device, as one flat float32 vector."""
    import jax
    import jax.numpy as jnp
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


class Driver:
    """The window's own call: ``n`` steps through ``run_train`` on the next
    distinct batches, ending on ``block_until_ready``."""

    def __init__(self, mesh, tcfg, step, state_sh, batches):
        self.mesh, self.tcfg, self.step = mesh, tcfg, step
        self.state_sh, self.batches, self.next = state_sh, batches, 0

    def __call__(self, state, n: int):
        import jax
        from repro.train.loop import run_train
        base, feed = self.next, self.batches
        self.next += n
        with self.mesh, jax.profiler.TraceAnnotation("bench.train_call"):
            state, hist = run_train(
                state, self.step, lambda s: feed[(base + s) % len(feed)],
                dataclasses.replace(self.tcfg, total_steps=n),
                state_sh=self.state_sh, log_every=n, log_fn=lambda *a: None)
            jax.block_until_ready(state)
        return state, hist


def first_steps(call, state, params0, job: dict):
    """Drive ``state`` through the first ``check_steps`` steps, one call
    each; return the state and what the check compares: the losses, the
    gradient step 1 gave the optimizer (a copy on the device: Adam's first
    moment after one step over 1 - b1), and the per-leaf norms of each
    leaf's change over the steps."""
    import jax
    losses = []
    for i in range(job["check_steps"]):
        state, hist = call(state, 1)
        losses += [h["loss"] for h in hist]
        if i == 0:
            g1 = jax.jit(lambda t: jax.tree.map(
                lambda x: x / (1 - job["b1"]), t))(state["opt"]["m"])
    delta = np.asarray(jax.jit(lambda a, b: leaf_norms(
        jax.tree.map(lambda x, y: x.astype(np.float32) - y, a, b)))(
            state["params"], params0))
    return state, losses, g1, delta


def run(cfgfile: dict, job: dict, seed: int, seconds: float, trace_dir,
        clock, *, fault=None):
    """One run of a training cell; ``fault`` (tests only) wraps the step
    and the feed to plant a fault.  Returns the result record."""
    import jax

    m = cfgfile
    at = {"imported": clock()}
    mesh, tcfg, state, step, state_sh, params0, feed = build(m, job, seed)
    at["built"] = clock()
    batches = [feed(i) for i in range(job["distinct_batches"])]
    fed = batches
    if fault is not None:       # the program's path, not the reference's
        step, bad = fault(step, feed)
        fed = [bad(i) for i in range(job["distinct_batches"])]
    jax.block_until_ready(fed)
    call = Driver(mesh, tcfg, step, state_sh, fed)
    at["fed"] = clock()
    state, losses, g1, delta = first_steps(call, state, params0, job)

    setup_s = clock()
    at["first_steps"] = setup_s
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    k, steps = job["steps_per_call"], 0
    t_open = time.perf_counter()
    while time.perf_counter() - t_open < seconds:
        state, _ = call(state, k)
        steps += k
    window = time.perf_counter() - t_open
    if trace_dir:
        jax.profiler.stop_trace()
    peak = peak_bytes(mesh.devices.flat)
    del state
    gc.collect()

    tokens = job["global_batch"] * job["seq_len"]
    e2e = {"train_step_ms": 1e3 * window / steps, "setup_s": setup_s}
    side = {"steps": steps, "window_s": window, "traced_s": window,
            "tokens_per_s": tokens * steps / window, "setup_phases_s": at}
    check = check_train(m, job, params0, batches, losses, g1, delta)
    work = {"flops_per_step": tokens * costs.train_flops_per_token(
                m, job["seq_len"]),
            "steps": steps, "chips": int(np.prod(job["mesh"])),
            "global_batch": job["global_batch"], "seq_len": job["seq_len"]}
    return {"kind": "train", "e2e": e2e, "side": side, "check": check,
            "attempted": steps + job["check_steps"], "failed": 0,
            "peak_bytes": peak,
            "counters": {}, "spans": [], "work": work, "calls": []}


# --- correctness ---------------------------------------------------------------

def reference_steps(m: dict, job: dict, params0, batches, quant=None):
    """The plain reference over the first ``check_steps`` steps: each loss,
    step 1's clipped gradient, the per-leaf norms of each leaf's change over
    the steps, and the per-leaf norms of step 1's raw gradient."""
    import jax
    import jax.numpy as jnp

    from bench import reference
    mk = reference.sizes_key(m)
    hp = {k: job[k] for k in ("lr", "b1", "b2", "eps", "weight_decay",
                              "grad_clip", "warmup_steps", "total_steps")}
    p = jax.tree.map(lambda x: x.astype(jnp.float32), params0)
    opt = {"m": jax.tree.map(jnp.zeros_like, p),
           "v": jax.tree.map(jnp.zeros_like, p)}
    upd = jax.jit(functools.partial(reference.adamw_step, hp=hp),
                  static_argnames=())
    losses, g1 = [], None
    for i in range(job["check_steps"]):
        val, g = reference.loss_and_grad(mk, p, batches[i], job["z_loss"],
                                         job["reference_rows"], quant=quant,
                                         block=m["train"]["attention_block"])
        p, opt, gc_ = upd(opt, p, g, i)
        losses.append(float(val))
        if i == 0:
            g1, raw = gc_, np.asarray(leaf_norms(g))
    delta = np.asarray(jax.jit(lambda a, b: leaf_norms(
        jax.tree.map(lambda x, y: x - y.astype(jnp.float32), a, b)))(
            p, params0))
    return losses, g1, delta, raw


def leaf_gap(prog, ref, keep) -> float:
    """Worst leaf's gap between the program's norm and the reference's,
    over the reference's norm of that leaf or of the median leaf,
    whichever is larger; only leaves in ``keep`` count."""
    prog, ref = np.asarray(prog)[keep], np.asarray(ref)[keep]
    floor = np.median(ref)
    return float(np.max(np.abs(prog - ref) / np.maximum(ref, floor)))


def compare(prog, ref, names=None) -> dict:
    """The numbers of the check: ``prog`` and ``ref`` are (losses, step 1
    gradient, change leaf norms[, raw gradient leaf norms]).  ``names``
    (the leaves' paths) adds the worst leaf of each number."""
    import jax
    losses, g1, delta = prog[:3]
    r_loss, r_g1, r_delta, raw = ref
    norms = np.asarray(leaf_norms(g1))
    r_norms = np.asarray(leaf_norms(r_g1))
    diff = np.asarray(jax.jit(lambda a, b: leaf_norms(jax.tree.map(
        lambda x, y: x.astype(np.float32) - y, a, b)))(g1, r_g1))
    # a leaf whose gradient is nought to rounding in the reference moves
    # under Adam by round-off alone: the change leaves it out
    keep = raw >= 1e-3 * np.median(raw)
    every = np.ones_like(keep)
    out = {"loss_rel_gap": max(abs(a - b) / abs(b)
                               for a, b in zip(losses, r_loss)),
           "grad_leaf_gap": leaf_gap(norms, r_norms, every),
           "update_leaf_gap": leaf_gap(delta, r_delta, keep),
           # a gap of norms moves with the square of an unbiased error, so
           # float8's rounding hardly shows in it; the norm of the
           # difference moves with the error itself
           "grad_leaf_diff": float(np.max(
               diff / np.maximum(r_norms, np.median(r_norms)))),
           "leaves_left_out": int((~keep).sum())}
    if names is not None:
        def worst(p, r, k):
            v = np.where(k, np.abs(p - r) / np.maximum(r, np.median(r[k])), -1)
            return names[int(np.argmax(v))]
        out["worst"] = {
            "grad_leaf_gap": worst(norms, r_norms, every),
            "update_leaf_gap": worst(delta, r_delta, keep),
            "grad_leaf_diff": names[int(np.argmax(
                diff / np.maximum(r_norms, np.median(r_norms))))]}
    return out


NUMBERS = ("loss_rel_gap", "grad_leaf_gap", "update_leaf_gap",
           "grad_leaf_diff")


def check_train(m, job, params0, batches, losses, g1, delta) -> dict:
    """Each of the first steps' losses, step 1's gradient (its norm and
    the norm of its difference, worst leaf) and the change of the
    parameters against the reference, each by its limit."""
    lim = job["check"]
    ref = reference_steps(m, job, params0, batches)
    got = compare((losses, g1, delta), ref)
    return {"numbers": {k: [got[k], lim[k]] for k in NUMBERS},
            "losses": losses, "reference_losses": ref[0],
            "leaves_left_out": got["leaves_left_out"]}
