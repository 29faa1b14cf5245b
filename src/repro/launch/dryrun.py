import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^^ MUST precede any jax import: jax locks the device count on first init.
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell we build the *real* step (train_step with optimizer update,
prefill, or serve decode step), lower it with ShapeDtypeStruct inputs under
the production mesh, ``.compile()`` it, and record:
  * memory_analysis()  — per-device argument/output/temp bytes (fits check)
  * cost_analysis()    — per-device FLOPs / bytes accessed
  * collective bytes   — parsed from the post-SPMD HLO text
  * the three-term roofline + MODEL_FLOPS ratio (EXPERIMENTS.md §Roofline)

Results are cached as JSON under results/dryrun/ keyed by
(mesh, arch, shape, tag); re-runs skip finished cells unless --force.

Usage:
  python -m repro.launch.dryrun --arch all --shape all --mesh both
  python -m repro.launch.dryrun --arch mistral-nemo-12b --shape train_4k \
      --mesh single --tag chunked --attn-mode chunked
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro import optim
from repro.configs import ASSIGNED, SHAPES, cell_supported, get_config, input_specs
from repro.configs.base import TrainConfig
from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.roofline import analysis, hw
from repro.train import state as train_state
from repro.train.step import make_step_fn

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

# big archs get adafactor + fsdp + microbatching by default: anything else
# cannot fit optimizer state on a 16 GB/chip pod (recorded in EXPERIMENTS.md)
BIG = {"nemotron-4-340b": 16, "grok-1-314b": 32, "zamba2-7b": 64,
       "mistral-nemo-12b": 64, "phi3.5-moe-42b-a6.6b": 64}


@dataclasses.dataclass
class CellOpts:
    tag: str = "baseline"
    attn_mode: str | None = None     # None = arch default
    softmax: str | None = None
    remat: str = "full"
    optimizer: str | None = None
    microbatch: int | None = None
    fsdp: bool | None = None
    seq_shard: bool = False
    parallel_prefill: bool = False
    pad_vocab: int = 0          # pad vocab up to a multiple (shardability)
    donate: bool = True


def cell_path(mesh_kind, arch, shape, tag):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    return os.path.join(RESULTS_DIR, f"{mesh_kind}__{arch}__{shape}__{tag}.json")


def build_cfg(arch, opts: CellOpts):
    cfg = get_config(arch)
    kw = {}
    if opts.attn_mode:
        kw["attn_mode"] = opts.attn_mode
    if opts.softmax:
        kw["softmax_impl"] = opts.softmax
    if opts.parallel_prefill:
        kw["parallel_prefill"] = True
    if opts.pad_vocab:
        kw["vocab"] = -(-cfg.vocab // opts.pad_vocab) * opts.pad_vocab
    return cfg.with_(**kw) if kw else cfg


def lower_cell(arch: str, shape_name: str, mesh, opts: CellOpts):
    """Returns (lowered, chips, meta). Raises on sharding/lowering bugs."""
    shape = SHAPES[shape_name]
    cfg = build_cfg(arch, opts)
    model = build_model(cfg)
    chips = mesh.size
    fsdp = opts.fsdp if opts.fsdp is not None else arch in BIG
    rules = shd.default_rules(mesh, cfg, fsdp=fsdp)
    if opts.seq_shard:
        rules["seq"] = "model"
    specs = input_specs(cfg, shape)
    meta = dict(arch=arch, shape=shape_name, kind=shape.kind, tag=opts.tag,
                chips=chips, mesh=str(dict(mesh.shape)), fsdp=fsdp,
                opts=dataclasses.asdict(opts))

    params_abs = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    psh = shd.param_shardings(mesh, params_abs, rules)
    from repro.models.layers import unbox
    params_flat = unbox(params_abs)

    if shape.kind == "train":
        mb = opts.microbatch if opts.microbatch is not None else BIG.get(arch, 0)
        tcfg = TrainConfig(global_batch=shape.batch, seq_len=shape.seq,
                           microbatch=mb, remat=opts.remat)
        opt_name = opts.optimizer or (
            "adafactor" if arch in ("nemotron-4-340b", "grok-1-314b")
            else "adamw")
        ocfg = optim.OptConfig(name=opt_name)
        state_sh = train_state.state_shardings(mesh, model, ocfg, rules)
        state_abs = jax.eval_shape(
            lambda: train_state.init_state(model, ocfg, jax.random.PRNGKey(0)))
        batch_sh = shd.batch_shardings(mesh, specs, rules)
        step_fn = make_step_fn(model, tcfg, ocfg)
        meta.update(optimizer=opt_name, microbatch=mb,
                    tokens=shape.batch * shape.seq)
        with mesh:
            with shd.activation_rules(mesh, rules):
                jitted = jax.jit(step_fn, in_shardings=(state_sh, batch_sh),
                                 out_shardings=(state_sh, None),
                                 donate_argnums=(0,) if opts.donate else ())
                return jitted.lower(state_abs, specs), chips, meta

    if shape.kind == "prefill":
        cache_abs = jax.eval_shape(
            lambda: model.init_cache(params_flat, shape.batch, shape.seq,
                                     jnp.bfloat16))
        cache_sh = shd.cache_shardings(mesh, cache_abs, rules)
        batch_sh = shd.batch_shardings(mesh, specs, rules)
        meta.update(tokens=shape.batch * shape.seq)

        def prefill_fn(params, cache, batch):
            return model.prefill(params, cache, batch)
        with mesh:
            with shd.activation_rules(mesh, rules):
                jitted = jax.jit(prefill_fn,
                                 in_shardings=(psh, cache_sh, batch_sh),
                                 out_shardings=(None, cache_sh, None),
                                 donate_argnums=(1,) if opts.donate else ())
                return jitted.lower(params_flat, cache_abs, specs), chips, meta

    # decode: one new token against a seq_len cache
    cache_abs = jax.eval_shape(
        lambda: model.init_cache(params_flat, shape.batch, shape.seq,
                                 jnp.bfloat16))
    cache_sh = shd.cache_shardings(mesh, cache_abs, rules)
    tok_sh = shd.batch_shardings(mesh, specs, rules)
    pos_abs = jax.ShapeDtypeStruct((), jnp.int32)
    meta.update(tokens=shape.batch)

    def serve_fn(params, cache, tokens1, pos):
        return model.decode_step(params, cache, tokens1, pos)
    with mesh:
        jitted = jax.jit(serve_fn,
                         in_shardings=(psh, cache_sh, tok_sh["tokens"], None),
                         out_shardings=(None, cache_sh),
                         donate_argnums=(1,) if opts.donate else ())
        return jitted.lower(params_flat, cache_abs, specs["tokens"],
                            pos_abs), chips, meta


def run_cell(arch, shape_name, mesh_kind, opts: CellOpts, force=False):
    path = cell_path(mesh_kind, arch, shape_name, opts.tag)
    if os.path.exists(path) and not force:
        return json.load(open(path))
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    ok, reason = cell_supported(cfg, shape)
    if not ok:
        result = dict(arch=arch, shape=shape_name, mesh=mesh_kind,
                      tag=opts.tag, status="skipped", reason=reason)
        json.dump(result, open(path, "w"), indent=1)
        return result

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
        lowered, chips, meta = lower_cell(arch, shape_name, mesh, opts)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        tf = analysis.scan_trip_factor(
            build_cfg(arch, opts), meta["kind"], shape.seq, shape.batch,
            meta.get("microbatch", 0))
        # an analytic projection onto the production target (a v5e pod),
        # not a measurement: nothing here ran on a chip
        roof = analysis.analyze(cost, hlo, chips, device_kind=hw.V5E,
                                trip_factor=tf)
        mf = analysis.model_flops(build_cfg(arch, opts), meta["tokens"],
                                  "train" if meta["kind"] == "train"
                                  else "infer")
        result = dict(
            meta, status="ok", mesh_kind=mesh_kind, trip_factor=tf,
            raw_cost={k: cost.get(k, 0.0)
                      for k in ("flops", "bytes accessed", "transcendentals")},
            lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
            memory=dict(
                argument_bytes=mem.argument_size_in_bytes,
                output_bytes=mem.output_size_in_bytes,
                temp_bytes=mem.temp_size_in_bytes,
                alias_bytes=mem.alias_size_in_bytes,
                peak_device_bytes=(mem.argument_size_in_bytes
                                   + mem.output_size_in_bytes
                                   + mem.temp_size_in_bytes
                                   - mem.alias_size_in_bytes),
            ),
            roofline=roof.to_dict(),
            model_flops=mf,
            useful_flops_ratio=(mf / roof.hlo_flops_global
                                if roof.hlo_flops_global else 0.0),
        )
    except Exception as e:  # sharding mismatch / OOM-at-compile are bugs
        result = dict(arch=arch, shape=shape_name, mesh=mesh_kind,
                      tag=opts.tag, status="error",
                      error=f"{type(e).__name__}: {e}",
                      tb=traceback.format_exc()[-2000:])
    json.dump(result, open(path, "w"), indent=1, default=str)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--attn-mode", default=None)
    ap.add_argument("--softmax", default=None)
    ap.add_argument("--remat", default="full")
    ap.add_argument("--optimizer", default=None)
    ap.add_argument("--microbatch", type=int, default=None)
    ap.add_argument("--fsdp", type=int, default=None)
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--parallel-prefill", action="store_true")
    ap.add_argument("--pad-vocab", type=int, default=0)
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = ASSIGNED if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    opts = CellOpts(tag=args.tag, attn_mode=args.attn_mode,
                    softmax=args.softmax, remat=args.remat,
                    optimizer=args.optimizer, microbatch=args.microbatch,
                    fsdp=None if args.fsdp is None else bool(args.fsdp),
                    seq_shard=args.seq_shard,
                    parallel_prefill=args.parallel_prefill,
                    pad_vocab=args.pad_vocab)

    n_ok = n_skip = n_err = 0
    for mesh_kind in meshes:
        for arch in archs:
            for shape in shapes:
                r = run_cell(arch, shape, mesh_kind, opts, force=args.force)
                st = r["status"]
                n_ok += st == "ok"
                n_skip += st == "skipped"
                n_err += st == "error"
                if st == "ok":
                    roof = r["roofline"]
                    print(f"[{mesh_kind}] {arch:22s} {shape:12s} OK "
                          f"compile={r['compile_s']:6.1f}s "
                          f"peak={r['memory']['peak_device_bytes']/2**30:7.2f}GiB "
                          f"dom={roof['dominant']:10s} "
                          f"frac={roof['roofline_fraction']:.3f}", flush=True)
                elif st == "skipped":
                    print(f"[{mesh_kind}] {arch:22s} {shape:12s} SKIP "
                          f"({r['reason'][:60]})", flush=True)
                else:
                    print(f"[{mesh_kind}] {arch:22s} {shape:12s} ERROR "
                          f"{r['error'][:140]}", flush=True)
    print(f"done: ok={n_ok} skip={n_skip} err={n_err}")
    return 0 if n_err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
