"""On-chip smoke run of the co-location main path.

Default (one TPU chip): two training jobs built by ``make_train_bundle``
share one chip through ``TemporalStepper``:

  * ``mamba2-370m`` exactly as registered, batch 4 x 2048;
  * ``h2o-danube-1.8b`` at its published widths with 4 of its 24 layers
    (the depth cut that lets its state sit beside the other job's in 16 GB
    of HBM), batch 1 x 4096: one full sliding window.

Each job is profiled alone through ``EarlyStageProfiler``, then the pair
runs co-located through an epoch boundary (both jobs checkpoint with
``AsyncCheckpointer`` under ``.smoke_ckpt/``), one job is evicted back to its
checkpoint and placed again, and its replayed steps must repeat their
losses.  A 512-token forward of ``mamba2-370m`` on the chip must match the
same parameters' loss on the host's CPU backend.

``--chips 4`` runs only the sharded path a four-chip host trains with: the
whole ``h2o-danube-1.8b`` (24 layers) through ``Trainer`` on a
``("data", "model")`` mesh over the four devices, its state split across
them, and the loss of one short sequence per data shard, through the mesh
model the Trainer steps, compared with the CPU backend.

  python3 chip_smoke.py
  python3 chip_smoke.py --chips 4

Any failed check raises, so the exit code is non-zero; the last line of a
passing run is ``{"ok": true, "device": {...}}``.  The run refuses to start
unless JAX's default device is a TPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import shutil
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402

from repro.colocation.profiler import EarlyStageProfiler  # noqa: E402
from repro.colocation.stepper import ColocatedJob, TemporalStepper  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import ShapeSpec  # noqa: E402
from repro.data.pipeline import DataConfig, SyntheticPipeline  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models.factory import build_model  # noqa: E402
from repro.roofline.analysis import model_flops_for_cell  # noqa: E402
from repro.train.steps import make_train_bundle  # noqa: E402
from repro.train.trainer import Trainer, TrainerConfig  # noqa: E402

CKPT_DIR = ROOT / ".smoke_ckpt"

# Loss of a near-uniform model at random init: ln(vocab).  The first step
# must land within this distance of it.
FIRST_LOSS_TOL = 1.0
# Chip vs CPU loss of the same bf16 forward: both backends round to bf16
# (8 significant bits) at different points, so allow one bf16 step at the
# loss's magnitude, 2**-8 of it.
CPU_LOSS_RTOL = 2.0**-8
# A replayed step after evict + restore starts from the checkpoint's exact
# bytes on the same data, so its loss repeats up to the chip's summation
# order.
REPLAY_ATOL = 1e-3

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, from its own
    monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration


@dataclasses.dataclass(frozen=True)
class JobSpec:
    name: str
    cfg: object
    batch: int
    seq: int


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def check_first_loss(name: str, loss: float, vocab: int) -> None:
    ref = math.log(vocab)
    check(
        abs(loss - ref) <= FIRST_LOSS_TOL,
        f"{name}: first-step loss {loss} is not within {FIRST_LOSS_TOL} of ln(vocab) = {ref}",
    )


def stats_of(device) -> dict:
    return device.memory_stats() or {}


def cpu_agreement(
    name: str, chip_model, cpu_model, params, tokens, labels, batch_sharding=None
) -> dict:
    """The loss of ``chip_model`` on the default devices against that of
    ``cpu_model`` on the host CPU backend, with the same parameters.  A
    model built for a mesh runs with its own sharding constraints on a batch
    placed by ``batch_sharding``."""
    on_dev = (tokens, labels)
    if batch_sharding is not None:
        on_dev = jax.device_put(on_dev, batch_sharding)
    on_chip = float(jax.jit(lambda p, t, l: chip_model.loss(p, t, l)[0])(params, *on_dev))
    cpu = jax.devices("cpu")[0]
    host = jax.device_put((jax.device_get(params), tokens, labels), cpu)
    on_cpu = float(jax.jit(lambda p, t, l: cpu_model.loss(p, t, l)[0])(*host))
    diff = abs(on_chip - on_cpu)
    tol = CPU_LOSS_RTOL * abs(on_cpu)
    check(
        math.isfinite(on_chip) and diff <= tol,
        f"{name}: chip loss {on_chip} vs CPU loss {on_cpu} differ by {diff} > {tol}",
    )
    return {"chip_loss": on_chip, "cpu_loss": on_cpu, "abs_diff": diff, "tol": tol}


def check_trainer(name: str, rep: dict, vocab: int) -> None:
    """A ``Trainer`` run passes with no loss spike rolled back (or trained
    past) and a first loss near ln(vocab)."""
    check(rep["rollbacks"] == 0, f"{name}: {rep['rollbacks']} loss spike(s) rolled back")
    check_first_loss(name, rep["first_loss"], vocab)


def default_jobs():
    danube = get_config("h2o-danube-1.8b")
    return [
        JobSpec("mamba2-370m", get_config("mamba2-370m"), 4, 2048),
        JobSpec(
            "h2o-danube-1.8b[4/24 layers]",
            dataclasses.replace(danube, num_layers=4),
            1,
            4096,
        ),
    ]


def colocation_phase(specs, *, solo_steps=3, co_rounds=3, cpu_seq=512) -> dict:
    """Profile each job alone, co-locate them through a checkpoint, evict
    and re-place the first, and check the first job's forward against the
    CPU backend.  Returns what was measured."""
    dev = jax.devices()[0]
    clock = CompileClock()
    shutil.rmtree(CKPT_DIR, ignore_errors=True)
    # warm-up + solo steps end at step solo_steps + 1; the next step, the
    # first co-located one, closes the epoch and checkpoints
    steps_per_epoch = solo_steps + 2
    profiler = EarlyStageProfiler(
        {
            s.name: model_flops_for_cell(s.cfg, ShapeSpec(s.name, s.seq, s.batch, "train"))
            for s in specs
        }
    )
    jobs, report = [], {}
    for i, s in enumerate(specs):
        before = stats_of(dev).get("bytes_in_use", 0)
        job = ColocatedJob(
            name=s.name,
            bundle=make_train_bundle(s.cfg),
            pipeline=SyntheticPipeline(DataConfig(s.cfg.vocab_size, s.seq, s.batch, seed=i)),
            steps_per_epoch=steps_per_epoch,
            target_epochs=1_000,
            ckpt_dir=str(CKPT_DIR / f"job{i}"),
        )
        alone = TemporalStepper([job], seed=i)
        jax.block_until_ready((job.params, job.opt_state))
        state_bytes = stats_of(dev).get("bytes_in_use", 0) - before
        c0, t0 = clock.seconds, time.perf_counter()
        alone.step_round()  # compiles
        first_step_s = time.perf_counter() - t0
        compile_s = clock.seconds - c0
        check_first_loss(s.name, job.losses[0], s.cfg.vocab_size)
        solo = profiler.profile_solo(alone, steps=solo_steps)[s.name]
        report[s.name] = {
            "state_bytes": state_bytes,
            "compile_s": compile_s,
            "first_step_s": first_step_s,
            "solo_median_step_s": solo.mean_step_s,
            "solo_duty_pct": solo.duty_cycle_pct,
            "first_loss": job.losses[0],
            "peak_bytes_in_use_after_solo": stats_of(dev).get("peak_bytes_in_use"),
            "resident_jobs_during_solo": [j.name for j in jobs] + [s.name],
        }
        jobs.append(job)

    co = TemporalStepper(jobs)
    observed = profiler.observe(co, rounds=co_rounds)
    for name, obs in observed.items():
        report[name].update(
            co_median_step_s=obs.mean_step_s,
            co_duty_pct=obs.duty_cycle_pct,
            inflation_vs_solo=obs.inflation_vs_solo,
        )

    # evict the first job to its epoch checkpoint, then place it again
    first = jobs[0]
    losses_before = list(first.losses)
    step_before = first.step
    evicted = co.evict(first.name)
    check(
        evicted.step == steps_per_epoch,
        f"evict restored step {evicted.step}, expected the epoch checkpoint at {steps_per_epoch}",
    )
    co.run(max_rounds=0)  # waits for the remaining jobs' checkpoints
    check([j.name for j in co.jobs] == [j.name for j in jobs[1:]], "evict left the wrong jobs")
    rounds = step_before - evicted.step
    others_before = [j.step for j in jobs[1:]]
    TemporalStepper(jobs[1:] + [first]).run(max_rounds=rounds)
    check(first.step == step_before, f"replay ended at step {first.step}, not {step_before}")
    check(
        [j.step for j in jobs[1:]] == [s + rounds for s in others_before],
        "the co-resident jobs did not step beside the restored one",
    )
    replayed = first.losses[len(losses_before):]
    original = losses_before[steps_per_epoch:]
    diffs = [abs(a - b) for a, b in zip(replayed, original)]
    check(
        len(replayed) == len(original) and max(diffs) <= REPLAY_ATOL,
        f"replayed losses {replayed} differ from the originals {original}",
    )
    report[first.name]["replay_max_abs_diff"] = max(diffs)
    ckpts = sorted(p.name for p in CKPT_DIR.rglob("step_*"))
    check(len(ckpts) >= len(jobs), f"expected an epoch checkpoint per job, found {ckpts}")
    report["pair"] = {
        "peak_bytes_in_use": stats_of(dev).get("peak_bytes_in_use"),
        "bytes_limit": stats_of(dev).get("bytes_limit"),
        "checkpoints": ckpts,
    }

    s0 = specs[0]
    tokens, labels = SyntheticPipeline(DataConfig(s0.cfg.vocab_size, cpu_seq, 1, seed=99)).batch_at(0)
    model = first.bundle.model
    report[s0.name]["cpu_check"] = cpu_agreement(s0.name, model, model, first.params, tokens, labels)
    for j in jobs:
        report[j.name]["losses"] = j.losses
    shutil.rmtree(CKPT_DIR, ignore_errors=True)
    return report


def sharded_phase(cfg, *, batch=4, seq=4096, steps=4, cpu_seq=512) -> dict:
    """Train ``cfg`` through ``Trainer`` on a mesh over every device and
    check that its state is split across them."""
    devices = jax.devices()
    mesh = make_host_mesh(devices)
    clock = CompileClock()
    bundle = make_train_bundle(cfg, mesh)
    trainer = Trainer(
        bundle,
        SyntheticPipeline(DataConfig(cfg.vocab_size, seq, batch, seed=0)),
        TrainerConfig(total_steps=steps, steps_per_epoch=steps, ckpt_every_steps=steps, log_every=1),
    )
    trainer.init_or_restore(0)
    # one short sequence per data shard, through the mesh model the Trainer
    # steps, against the mesh-less model on the CPU
    tokens, labels = SyntheticPipeline(
        DataConfig(cfg.vocab_size, cpu_seq, mesh.shape["data"], seed=99)
    ).batch_at(0)
    cpu_check = cpu_agreement(
        cfg.name,
        bundle.model,
        build_model(cfg),
        trainer.params,
        tokens,
        labels,
        bundle.batch_shardings["tokens"],
    )
    t0 = time.perf_counter()
    rep = trainer.train()  # raises on a non-finite loss
    train_s = time.perf_counter() - t0
    check_trainer(cfg.name, rep, cfg.vocab_size)
    losses = [h["loss"] for h in trainer.history]
    state = [0] * len(devices)
    for leaf in jax.tree.leaves((trainer.params, trainer.opt_state)):
        for shard in leaf.addressable_shards:
            state[devices.index(shard.device)] += shard.data.nbytes
    in_use = [stats_of(d).get("bytes_in_use", 0) for d in devices]
    for what, per_device in (("training state", state), ("bytes_in_use", in_use)):
        check(
            sum(per_device) > 0 and max(per_device) <= sum(per_device) / 2,
            f"{what} not split across devices: {per_device}",
        )
    return {
        "mesh": dict(mesh.shape),
        "params": cfg.param_count(),
        "batch": [batch, seq],
        "compile_s": clock.seconds,
        "train_s": train_s,
        "median_step_s": statistics.median(h["step_s"] for h in trainer.history[1:]),
        "losses": losses,
        "state_bytes_per_device": state,
        "bytes_in_use_per_device": in_use,
        "peak_bytes_in_use_per_device": [stats_of(d).get("peak_bytes_in_use") for d in devices],
        "cpu_check": cpu_check,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX's default device is {dev.platform!r}, not a TPU")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but {len(devices)} device(s) present")
    devices = devices[: args.chips]
    print(f"cache: {use_compile_cache()}", flush=True)
    print(f"device: {dev.device_kind} x {len(devices)}", flush=True)
    if args.chips == 1:
        report = colocation_phase(default_jobs())
    else:
        check(len(jax.devices()) == args.chips, "--chips 4 trains on every device present")
        report = sharded_phase(get_config("h2o-danube-1.8b"))
    for name, r in report.items():
        print(f"{name}: {json.dumps(r)}", flush=True)
    print(
        json.dumps(
            {
                "ok": True,
                "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)},
            }
        )
    )


if __name__ == "__main__":
    main()
