"""Training launcher.

CPU-scale entry point (examples/tests) and the mesh-configured production
path. ``--arch <id> --variant smoke`` trains a reduced config for a few
hundred steps on synthetic data; on a real TPU slice the same module drives
the production mesh with ``--mesh single|multi``.

Usage::

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        --variant smoke --steps 100 --grad-sync canary --data-parallel 1
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Dict, List, Optional

import jax

from repro.data import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import get_config
from repro.optim import AdamWConfig, cosine_with_warmup
from repro.parallel.context import ParallelContext, parallel_context
from repro.train import TrainConfig, Trainer, TrainerConfig


def main(argv: Optional[list] = None) -> List[Dict[str, float]]:
    """Train as the arguments say; returns the per-step history."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--variant", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--grad-sync", default="auto",
                    choices=["auto", "canary", "canary_fp", "ring",
                             "hierarchical"])
    ap.add_argument("--canary-blocks", type=int, default=16)
    ap.add_argument("--data-parallel", type=int, default=0,
                    help="0 = all local devices")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--replan-every", type=int, default=0)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--history-out", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch, args.variant)
    dp = args.data_parallel or max(1, len(jax.devices())
                                   // args.model_parallel)
    mesh = jax.make_mesh((dp, args.model_parallel), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    sched = cosine_with_warmup(args.lr, warmup_steps=max(1, args.steps // 20),
                               total_steps=args.steps)
    tc = TrainConfig(model=cfg,
                     optimizer=AdamWConfig(lr=args.lr, schedule=sched),
                     grad_sync=args.grad_sync,
                     canary_blocks=args.canary_blocks)
    data = DataConfig(vocab_size=cfg.vocab_size, global_batch=args.batch,
                      seq_len=args.seq)
    trainer_cfg = TrainerConfig(train=tc, data=data, steps=args.steps,
                                log_every=args.log_every,
                                checkpoint_dir=args.checkpoint_dir,
                                checkpoint_every=args.checkpoint_every,
                                replan_every=args.replan_every)
    ctx = ParallelContext(mesh=mesh, data_axes=("data",), model_axis="model")
    with parallel_context(ctx):
        trainer = Trainer(trainer_cfg, mesh=mesh)
        history = trainer.run()
    first, last = history[0]["loss"], history[-1]["loss"]
    print(f"loss: {first:.4f} -> {last:.4f} over {args.steps} steps "
          f"({args.grad_sync})")
    if args.history_out:
        os.makedirs(os.path.dirname(args.history_out) or ".", exist_ok=True)
        with open(args.history_out, "w") as f:
            json.dump(history, f)
    return history


if __name__ == "__main__":
    main()
