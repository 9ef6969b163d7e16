"""Training launcher.

Runs an architecture with the fault-tolerant trainer.  Without ``--smoke``
the registered config trains on a ``("data", "model")`` mesh over every
device present (``launch.mesh.make_host_mesh``); ``--smoke`` trains the
reduced config on the default device, which is how it runs on a CPU.

  PYTHONPATH=src python -m repro.launch.train --arch mamba2-370m \
      --steps 5 --batch 4 --seq 2048
  PYTHONPATH=src JAX_PLATFORMS=cpu python -m repro.launch.train \
      --arch mamba2-370m --smoke --steps 60 --seq 128 --ckpt-dir ckpt
"""

from __future__ import annotations

import argparse

from repro.configs import get_config, smoke_config
from repro.data.pipeline import DataConfig, SyntheticPipeline
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.train.steps import make_train_bundle
from repro.train.trainer import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--steps-per-epoch", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4, help="global batch")
    ap.add_argument("--seq", type=int, default=None, help="default 2048; 128 with --smoke")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg, mesh = smoke_config(cfg), None
    else:
        mesh = make_host_mesh()
    bundle = make_train_bundle(cfg, mesh, microbatches=args.microbatches)
    pipe = SyntheticPipeline(
        DataConfig(
            cfg.vocab_size,
            seq_len=args.seq or (128 if args.smoke else 2048),
            global_batch=args.batch,
            seed=args.seed,
        )
    )
    trainer = Trainer(
        bundle,
        pipe,
        TrainerConfig(
            total_steps=args.steps,
            steps_per_epoch=args.steps_per_epoch,
            ckpt_every_steps=args.steps_per_epoch,
            ckpt_dir=args.ckpt_dir,
        ),
    )
    print(trainer.init_or_restore(args.seed))
    report = trainer.train()
    print("report:", report)


if __name__ == "__main__":
    main()
