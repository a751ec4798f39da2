"""Training launcher: ``python -m repro.launch.train --arch smollm-135m ...``

Single-process driver for the host devices (the same Trainer the examples
use); on a real multi-host pod this module is what each host would run after
``jax.distributed.initialize()``.  Fault-tolerance wiring: auto-resume from
the newest checkpoint, async snapshots, SIGTERM-graceful exit, straggler
monitor, deterministic data resume.

XLA flags: latency-hiding scheduler + async collectives are what a real TPU
deployment sets; they are exported here (harmless on CPU).
"""

import os
os.environ.setdefault(
    "LIBTPU_INIT_ARGS",
    "--xla_tpu_enable_async_collective_fusion=true "
    "--xla_tpu_enable_latency_hiding_scheduler=true")

import argparse

import jax

from repro.configs import ARCH_NAMES, get_config, smoke_config
from repro.data import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import AdamWConfig
from repro.train import Trainer, TrainConfig


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", choices=ARCH_NAMES, default="smollm-135m")
    p.add_argument("--smoke", action="store_true",
                   help="use the reduced smoke config (CPU-trainable)")
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--seq-len", type=int, default=512)
    p.add_argument("--global-batch", type=int, default=8)
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--compress-grads", action="store_true")
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-every", type=int, default=100)
    args = p.parse_args()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if jax.default_backend() == "cpu" and not args.smoke \
            and cfg.param_count > 1e9:
        raise SystemExit(
            f"{cfg.name} has {cfg.param_count/1e9:.0f}B params - on this "
            "host run with --smoke (full configs are dry-run only here)")

    enable_compile_cache()
    trainer = Trainer(
        cfg,
        AdamWConfig(lr=args.lr, total_steps=args.steps,
                    warmup_steps=max(args.steps // 20, 1)),
        TrainConfig(steps=args.steps, microbatches=args.microbatches,
                    compress_grads=args.compress_grads,
                    checkpoint_dir=args.checkpoint_dir,
                    checkpoint_every=args.checkpoint_every),
        DataConfig(vocab=cfg.vocab, seq_len=args.seq_len,
                   global_batch=args.global_batch),
    )
    result = trainer.run()
    print(f"final loss: {result['history'][-1]['loss']:.4f}  "
          f"straggler events: {len(result['straggler_events'])}")


if __name__ == "__main__":
    main()
