"""Chip smoke: the Hyft serving and training paths, once, on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: sharded training only

One chip.  olmo-1b at its published widths (random weights from ``--seed``)
serves 8 requests through ``SlotPoolEngine``: continuous batching, the
fused Pallas kernels, hyft16 softmax, paged fp2fx8 KV and 512-token prefill
chunks.  One prompt's first-step logits are checked against the unfused
emulation path.  Then bert-base, the paper's evaluation model, takes 5
training steps through the fused forward and backward kernels.

Four chips.  olmo-1b trains 3 steps with its state sharded over a (1, 4)
mesh; a 2-layer cut of it trains 3 steps on one device and 3 on the four,
and their losses must agree.

Everything runs in this one process: a chip belongs to one process at a
time.  Without a TPU, or outside a checkout of the repository, the script
exits non-zero before any phase.  On success the last line of standard
output is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

# rms |kernel - unfused| first-step logit, over the spread (std) of the
# unfused logits.  Set between the correct kernel (0.0295) and the weakest
# of four faults planted at olmo-1b size (0.211), both read on a TPU v5e;
# see PERF.md.
LOGIT_TOL = 0.08
LOSS_TOL = 1e-2        # relative loss gap, 1-device vs 4-device 2-layer cut


def _log(*a):
    print(*a, flush=True)


def _peak_bytes(devices) -> list:
    """``peak_bytes_in_use`` per device, None where the backend has none.
    It is the process's peak so far: a phase's own only when it runs
    first."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]


def _first_step_logits(model, params, scfg, prompt):
    """Next-token logits after ``prompt``, fed through a fresh paged slot
    cache in ``prefill_chunk``-wide calls as the engine feeds it (row 0
    gated on, every other slot row gated off)."""
    import jax.numpy as jnp
    import numpy as np
    from repro.serve import engine
    from repro.serve.scheduler import exec_key_cfg

    n, ps, w = scfg.n_slots, scfg.page_size, scfg.prefill_chunk
    nb = -(-scfg.max_len // ps)
    cache = dict(model.init_paged_cache(params, n * nb, ps, scfg.cache_dtype),
                 block_tables=jnp.asarray(
                     np.arange(1, n * nb + 1, dtype=np.int32).reshape(n, nb)))
    chunk = engine.build_prefill_chunk(model, exec_key_cfg(scfg), w)
    gate = np.zeros(n, bool)
    gate[0] = True
    for start in range(0, len(prompt), w):
        piece = prompt[start:start + w]
        toks = np.zeros((n, w), np.int32)
        toks[0, :len(piece)] = piece
        last, cache = chunk(params, cache, jnp.asarray(toks),
                            jnp.full(n, start, jnp.int32),
                            jnp.full(n, len(piece), jnp.int32),
                            jnp.asarray(gate))
    return np.asarray(last[0], np.float32)


def logit_gap(kern, ref) -> dict:
    """How far first-step logits ``kern`` sit from the reference ``ref``.
    The error is read against the spread of ``ref``, not its largest
    value: with tied embeddings one logit stands far above the rest and
    would hide an error in all the others."""
    import numpy as np
    d = kern - ref
    return {"logit_max_abs_diff": float(np.max(np.abs(d))),
            "logit_rms_diff": float(np.sqrt(np.mean(d * d))),
            "logit_ref_std": float(np.std(ref)),
            "greedy_agree": int(np.argmax(kern)) == int(np.argmax(ref)),
            "finite": bool(np.isfinite(kern).all()
                           and np.isfinite(ref).all())}


def serve_phase(cfg, *, seed: int = 0, n_requests: int = 8,
                prompt_range=(256, 1536), max_new: int = 32,
                prefill_chunk: int = 512, n_slots: int = 8,
                page_size: int = 16) -> dict:
    """Serve ``n_requests`` seeded prompts on ``cfg`` through the slot-pool
    engine in kernel mode, and replay request 0's prompt on the kernel and
    the unfused paths for its first-step logits."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.base import ServeConfig
    from repro.models import build_model
    from repro.models.layers import unbox
    from repro.serve.scheduler import (TTL_NONE, Request, SlotPoolEngine,
                                       build_burst, exec_key_cfg)
    from repro.serve import engine

    cfg = cfg.with_(softmax_impl="hyft16")
    model = build_model(cfg)
    params = jax.jit(lambda k: unbox(model.init(k)))(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    lo, hi = prompt_range
    reqs = [Request(rid=i, tokens=rng.integers(
                0, cfg.vocab, int(rng.integers(lo, hi + 1))).astype(np.int32),
                    max_new=max_new)
            for i in range(n_requests)]
    scfg = ServeConfig(max_len=hi + max_new + 1, cache_dtype="fp2fx8",
                       attn_mode="kernel", scheduler="continuous",
                       n_slots=n_slots, kv_layout="paged",
                       page_size=page_size, prefill_chunk=prefill_chunk)
    eng = SlotPoolEngine(model, params, scfg, key=jax.random.PRNGKey(seed))
    t0 = time.perf_counter()
    eng.prewarm(max(len(r.tokens) for r in reqs))
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    done = eng.run(reqs)
    serve_s = time.perf_counter() - t0
    stats = eng.stats

    # the compiled executables the run used must hold the Pallas kernels
    n, I32 = n_slots, jnp.int32
    burst = build_burst(eng.model, scfg, max(1, scfg.decode_burst))
    burst_text = burst.lower(
        params, eng.cache, jnp.zeros((n, 1), I32), jnp.zeros(n, I32),
        jnp.zeros(n, bool), jnp.zeros(n, I32), jnp.full(n, TTL_NONE, I32),
        jax.random.PRNGKey(0)).compile().as_text()
    chunk = engine.build_prefill_chunk(eng.model, exec_key_cfg(scfg),
                                       prefill_chunk)
    chunk_text = chunk.lower(
        params, eng.cache, jnp.zeros((n, prefill_chunk), I32),
        jnp.zeros(n, I32), jnp.ones(n, I32),
        jnp.zeros(n, bool)).compile().as_text()

    prompt = reqs[0].tokens
    kern = _first_step_logits(eng.model, params, scfg, prompt)
    ref = _first_step_logits(build_model(cfg.with_(attn_mode="unfused")),
                             params, scfg, prompt)
    return {
        "compile_s": compile_s, "serve_s": serve_s,
        "requests": len(reqs), "completed": sum(c.ok for c in done.values()),
        "tokens_emitted": stats["tokens_emitted"],
        "tokens_expected": n_requests * max_new,
        "quarantines": stats["quarantines"],
        "fp32_retries": stats["fp32_retries"],
        "kernel_in_burst": "tpu_custom_call" in burst_text,
        "kernel_in_prefill_chunk": "tpu_custom_call" in chunk_text,
        "ref_prompt_len": len(prompt),
        **logit_gap(kern, ref),
        "engine_first_token_matches_replay":
            bool(done[0].tokens) and done[0].tokens[0] == int(np.argmax(kern)),
        "peak_bytes_in_use": _peak_bytes(jax.devices()[:1])[0],
    }


def check_serve(res: dict, on_chip: bool) -> list:
    """What a serving phase result must show; [] = pass."""
    bad = []
    if res["completed"] != res["requests"]:
        bad.append(f"completed {res['completed']} of {res['requests']}")
    if res["tokens_emitted"] != res["tokens_expected"]:
        bad.append(f"tokens {res['tokens_emitted']} != "
                   f"{res['tokens_expected']}")
    for k in ("quarantines", "fp32_retries"):
        if res[k]:
            bad.append(f"{k} = {res[k]}")
    if not res["finite"]:
        bad.append("non-finite first-step logits")
    rel = res["logit_rms_diff"] / max(res["logit_ref_std"], 1e-30)
    if not rel <= LOGIT_TOL:
        bad.append(f"kernel vs unfused logits {rel:.4g} > {LOGIT_TOL}")
    if on_chip:
        for k in ("kernel_in_burst", "kernel_in_prefill_chunk"):
            if not res[k]:
                bad.append(f"no tpu_custom_call ({k})")
    return bad


def train_phase(cfg, *, steps: int = 5, global_batch: int = 8,
                seq: int = 512, seed: int = 0, mesh_shape=(1, 1),
                attn_mode: str = "kernel") -> dict:
    """``steps`` training steps of ``cfg`` as ``launch/train.py`` builds
    them, on a ``mesh_shape`` (data, model) mesh of the first devices."""
    import jax
    from repro import optim
    from repro.configs.base import TrainConfig
    from repro.data.synthetic import DataConfig, lm_batch
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import build_trainer
    from repro.train.loop import run_train

    cfg = cfg.with_(softmax_impl="hyft16")
    tcfg = TrainConfig(global_batch=global_batch, seq_len=seq,
                       total_steps=steps, warmup_steps=1, lr=1e-4,
                       attn_mode=attn_mode)
    ocfg = optim.OptConfig(name="adamw", lr=tcfg.lr)
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=global_batch,
                      seed=seed)
    mesh = make_host_mesh(mesh_shape)
    t0 = time.perf_counter()
    _, state, step, state_sh = build_trainer(cfg, tcfg, ocfg, mesh, seed=seed)
    leaves = jax.tree.leaves(state)
    total = sum(x.nbytes for x in leaves)
    per_dev = {d: 0 for d in mesh.devices.flat}
    for x in leaves:
        for sh in x.addressable_shards:
            per_dev[sh.device] += sh.data.nbytes
    with mesh:
        state, hist = run_train(state, step, lambda s: lm_batch(dcfg, s),
                                tcfg, state_sh=state_sh, log_every=1,
                                log_fn=_log)
        jax.block_until_ready(state)
    return {"losses": [h["loss"] for h in hist],
            "wall_s": time.perf_counter() - t0,
            "state_bytes": total,
            "state_bytes_per_device": list(per_dev.values()),
            "process_peak_bytes_in_use": _peak_bytes(list(mesh.devices.flat))}


def check_train(res: dict, steps: int) -> list:
    import math
    bad = []
    if len(res["losses"]) != steps:
        bad.append(f"{len(res['losses'])} losses for {steps} steps")
    if not all(math.isfinite(x) for x in res["losses"]):
        bad.append(f"non-finite loss in {res['losses']}")
    return bad


def one_chip(seed: int) -> list:
    from repro.configs import get_config
    _log("# serve: olmo-1b, slot pool, kernel mode, paged fp2fx8, chunk 512")
    s = serve_phase(get_config("olmo-1b"), seed=seed)
    for k, v in s.items():
        _log(f"serve.{k} = {v}")
    bad = check_serve(s, on_chip=True)
    _log("# train: bert-base, 5 steps, kernel mode, seq 512, batch 8")
    t = train_phase(get_config("bert-base"), seed=seed)
    for k, v in t.items():
        _log(f"train.{k} = {v}")
    return bad + check_train(t, 5)


def four_chips(seed: int) -> list:
    from repro.configs import get_config
    cfg = get_config("olmo-1b")
    bad = []
    _log("# olmo-1b, 3 steps, state sharded over a (1, 4) mesh")
    full = train_phase(cfg, steps=3, seq=2048, seed=seed, mesh_shape=(1, 4),
                       attn_mode="chunked")
    for k, v in full.items():
        _log(f"four.full.{k} = {v}")
    bad += check_train(full, 3)
    if max(full["state_bytes_per_device"]) > 0.3 * full["state_bytes"]:
        bad.append("olmo-1b state is not sharded across the four devices")
    cut = cfg.with_(n_layers=2)
    runs = {}
    for name, shape in (("one", (1, 1)), ("four", (1, 4))):
        _log(f"# olmo-1b cut to 2 layers, 3 steps, mesh {shape}")
        runs[name] = train_phase(cut, steps=3, seq=2048, seed=seed,
                                 mesh_shape=shape, attn_mode="chunked")
        _log(f"four.cut_{name}.losses = {runs[name]['losses']}")
        bad += check_train(runs[name], 3)
    gap = max(abs(a - b) / abs(a) for a, b in
              zip(runs["one"]["losses"], runs["four"]["losses"]))
    _log(f"four.cut_loss_rel_gap = {gap}")
    if not gap <= LOSS_TOL:
        bad.append(f"1-device vs 4-device losses differ by {gap:.3g}")
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded training check")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax
    devices = jax.devices()
    want = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < want:
        print(f"chip_smoke: needs {want} TPU device(s), found "
              f"{len(devices)} x {devices[0].platform}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    _log(f"# compile cache: {enable_compile_cache()}")
    bad = four_chips(args.seed) if args.four_chips else one_chip(args.seed)
    if bad:
        for b in bad:
            print(f"chip_smoke: FAIL {b}", file=sys.stderr)
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
