"""Co-location executor demo: REAL JAX training jobs time-sharing one mesh.

This is the TPU-native analogue of the paper's GPU context-switch sharing:
two reduced-config LM jobs run interleaved, step by step, inside one
process.  The early-stage profiler measures each job's step time
solo and co-located — the measured inflation is what EaCO's observation
phase would feed into its history H.

  PYTHONPATH=src python examples/colocation_demo.py

Set ``REPRO_EXAMPLES_FAST=1`` (the CI examples gate) to shrink the runs
to a smoke-sized dry pass.
"""

import os
import sys
import pathlib

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

FAST = bool(int(os.environ.get("REPRO_EXAMPLES_FAST", "0")))

from repro.colocation.profiler import EarlyStageProfiler
from repro.colocation.stepper import ColocatedJob, TemporalStepper
from repro.configs import get_config, smoke_config
from repro.data.pipeline import DataConfig, SyntheticPipeline
from repro.train.steps import make_train_bundle


def make_job(arch: str, seed: int) -> ColocatedJob:
    cfg = smoke_config(get_config(arch))
    bundle = make_train_bundle(cfg)
    pipe = SyntheticPipeline(
        DataConfig(cfg.vocab_size, seq_len=128, global_batch=4, seed=seed)
    )
    return ColocatedJob(
        name=arch, bundle=bundle, pipeline=pipe,
        steps_per_epoch=2 if FAST else 8, target_epochs=1 if FAST else 2,
    )


def main() -> None:
    jobs = [make_job("minitron-8b", 0), make_job("mamba2-370m", 1)]
    # step times only: no FLOPs counts, so no duty cycle and no device peak
    profiler = EarlyStageProfiler(flops_per_step={})

    stepper = TemporalStepper(jobs)
    steps = 1 if FAST else 3
    print("— solo baselines (exclusive) —")
    for name, obs in profiler.profile_solo(stepper, steps=steps).items():
        print(f"  {name:14s} {obs.mean_step_s*1e3:8.1f} ms/step")

    print("— co-located (round-robin temporal sharing) —")
    for name, obs in profiler.observe(stepper, rounds=steps).items():
        infl = f"{obs.inflation_vs_solo:5.2f}x" if obs.inflation_vs_solo else "  n/a"
        print(f"  {name:14s} {obs.mean_step_s*1e3:8.1f} ms/step  inflation {infl}")

    print("— run both jobs to completion (checkpointing every epoch) —")
    report = stepper.run(max_rounds=8 if FAST else 64)
    for name, r in report.items():
        print(
            f"  {name:14s} steps={r['steps']:3d} loss {r['first_loss']:.3f} -> "
            f"{r['final_loss']:.3f}"
        )


if __name__ == "__main__":
    main()
