"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --steps 100 --batch 8 --seq 64 [--reduced] [--ckpt-dir /path]

On a real TPU fleet this binary runs once per host (jax.distributed
initializes from the TPU environment); ``--mesh data,model`` builds the
mesh with ``jax.make_mesh`` and every step is pjit-sharded by
``repro.distributed.sharding``.  The config trains at its published width
unless ``--reduced`` is given, which trains a small same-family config
end-to-end with the identical code path minus the mesh.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from ..checkpoint import CheckpointManager
from ..configs import get_config, reduced_config
from ..data import SyntheticLM
from ..distributed.sharding import batch_shardings, params_shardings, opt_state_shardings
from ..models import count_params, init_params
from ..train import AdamWConfig, Trainer, TrainerConfig, adamw_init, make_train_step
from .runtime import device_info, enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--reduced", action="store_true",
                    help="train a small same-family config instead of the "
                    "published width")
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--mesh", default=None,
                    help="data,model e.g. 16,16 (default: single device)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    print("[train] devices: {platform} {kind} x{count}".format(**device_info()))
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
        print(f"[train] reduced config for {args.arch}")

    params = init_params(cfg, seed=0)
    print(f"[train] params: {count_params(params):,}")
    step_fn = make_train_step(
        cfg, AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                         total_steps=args.steps),
        accum_steps=args.accum,
    )

    if args.mesh:
        data, model = (int(x) for x in args.mesh.split(","))
        mesh = jax.make_mesh((data, model), ("data", "model"))
        pshard = params_shardings(params, mesh)
        params = jax.device_put(params, pshard)
        opt = adamw_init(params)
        oshard = opt_state_shardings(opt, pshard, mesh)
        train_step = jax.jit(
            step_fn, in_shardings=(pshard, oshard, None),
            out_shardings=(pshard, oshard, None), donate_argnums=(0, 1),
        )
    else:
        train_step = jax.jit(step_fn, donate_argnums=(0, 1))

    ocfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                       total_steps=args.steps)
    tcfg = TrainerConfig(total_steps=args.steps, checkpoint_every=50)
    ckpt = CheckpointManager(args.ckpt_dir, keep=3)

    trainer = Trainer(
        cfg, ocfg, tcfg,
        lambda start: SyntheticLM(cfg, args.seq, args.batch, seed=0).iterate(start),
        ckpt, train_step=train_step,
    )
    params, _, step = trainer.run(params)
    losses = [h["loss"] for h in trainer.history]
    if losses:
        print(f"[train] done at step {step}; loss {losses[0]:.4f} -> {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
