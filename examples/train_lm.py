"""End-to-end training driver: a few hundred steps on a small LM with the
full substrate — synthetic data pipeline, AdamW + cosine schedule, gradient
accumulation, checkpointing with auto-resume, straggler watchdog.

    PYTHONPATH=src python examples/train_lm.py [--steps 200] [--arch qwen1.5-0.5b]
"""
import argparse
import os
import tempfile

import jax

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, reduced_config
from repro.data import SyntheticLM
from repro.models import count_params, init_params
from repro.train import (
    AdamWConfig,
    Trainer,
    TrainerConfig,
    make_train_step,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--accum", type=int, default=2)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    cfg = reduced_config(
        get_config(args.arch), num_layers=4, d_model=128, num_heads=4,
        head_dim=32, d_ff=384, vocab_size=1024,
    )
    params = init_params(cfg, seed=0)
    print(f"arch={cfg.name} (reduced) params={count_params(params):,}")

    ocfg = AdamWConfig(lr=3e-3, warmup_steps=20, total_steps=args.steps,
                       schedule="cosine")
    tcfg = TrainerConfig(total_steps=args.steps, checkpoint_every=50,
                         keep_checkpoints=2)
    ckpt_dir = args.ckpt_dir or os.path.join(tempfile.gettempdir(), "repro_ckpt")
    ckpt = CheckpointManager(ckpt_dir, keep=tcfg.keep_checkpoints)

    def data_factory(start_step):
        return SyntheticLM(cfg, args.seq, args.batch, seed=0).iterate(start_step)

    trainer = Trainer(
        cfg, ocfg, tcfg, data_factory, ckpt,
        train_step=jax.jit(
            make_train_step(cfg, ocfg, accum_steps=args.accum),
            donate_argnums=(0, 1),
        ),
    )
    params, _, step = trainer.run(params)

    losses = [h["loss"] for h in trainer.history]
    n = max(len(losses) // 10, 1)
    for i in range(0, len(losses), n):
        window = losses[i: i + n]
        print(f"step {i:4d}..{min(i + n, len(losses)):4d}: "
              f"loss {sum(window) / len(window):.4f}")
    stragglers = [h for h in trainer.history if h["straggler"]]
    print(f"\nfinal loss {losses[-1]:.4f} (start {losses[0]:.4f}); "
          f"{len(stragglers)} straggler steps flagged; "
          f"checkpoints at {ckpt_dir}: steps {ckpt.available_steps()}")
    assert losses[-1] < losses[0], "loss must decrease"


if __name__ == "__main__":
    main()
