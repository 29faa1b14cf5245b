"""Training launcher CLI.

Examples:
  # tiny end-to-end run on CPU (see examples/train_tiny_lm.py for the 100M)
  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --smoke \
      --steps 50 --global-batch 8 --seq 64 --ckpt-dir /tmp/ckpt

  # production lowering happens through repro.launch.dryrun; on a real fleet
  # this same entry point runs under the cluster scheduler with
  # jax.distributed.initialize() (multi-host) and the production mesh.
"""
import argparse


def build_trainer(cfg, tcfg, ocfg, mesh, seed: int = 0):
    """The model, train state, jitted step and state shardings, as the CLI
    builds them.  The state is initialised under ``jit`` with the state
    shardings as its outputs, so a sharded model is created in place on
    its devices and never lands whole on device 0 first."""
    import functools

    import jax
    from repro.configs import input_specs
    from repro.configs.shapes import ShapeSpec
    from repro.distributed import sharding as shd
    from repro.models import build_model
    from repro.train.state import init_state, state_shardings
    from repro.train.step import build_train_step

    model = build_model(cfg)
    rules = shd.default_rules(mesh, cfg)
    state_sh = state_shardings(mesh, model, ocfg, rules)
    specs = input_specs(cfg, ShapeSpec("cli", "train", tcfg.seq_len,
                                       tcfg.global_batch))
    batch_sh = shd.batch_shardings(mesh, specs, rules)
    with mesh:
        state = jax.jit(functools.partial(init_state, model, ocfg),
                        out_shardings=state_sh)(jax.random.PRNGKey(seed))
        step = build_train_step(model, tcfg, ocfg, mesh, state_sh, batch_sh)
    return model, state, step, state_sh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--softmax", default="hyft16")
    ap.add_argument("--attn-mode", default=None,
                    choices=["unfused", "chunked", "kernel"],
                    help="attention path; 'kernel' = fused Pallas fwd+bwd")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--data-mesh", type=int, default=1)
    ap.add_argument("--model-mesh", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro import optim
    from repro.configs import get_config, smoke_config
    from repro.configs.base import TrainConfig
    from repro.data.synthetic import DataConfig, lm_batch
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.train.loop import run_train

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    cfg = cfg.with_(softmax_impl=args.softmax)

    tcfg = TrainConfig(global_batch=args.global_batch, seq_len=args.seq,
                       microbatch=args.microbatch, lr=args.lr,
                       total_steps=args.steps, remat=args.remat,
                       optimizer=args.optimizer, attn_mode=args.attn_mode)
    ocfg = optim.OptConfig(name=args.optimizer, lr=args.lr)
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                      global_batch=args.global_batch, seed=args.seed)

    mesh = make_host_mesh((args.data_mesh, args.model_mesh))
    _, state, step, state_sh = build_trainer(cfg, tcfg, ocfg, mesh,
                                             seed=args.seed)
    with mesh:
        state, hist = run_train(state, step, lambda s: lm_batch(dcfg, s),
                                tcfg, ckpt_dir=args.ckpt_dir,
                                state_sh=state_sh)
    print(f"final loss: {hist[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
