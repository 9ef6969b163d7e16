"""Temporal co-location executor: the TPU-native analogue of GPU
hardware context switching (DESIGN.md §2).

A TPU core runs one XLA program at a time — there is no driver-level
time-slicing — so EaCO's mechanism maps to *step-granular round-robin*:
several jobs' train steps interleave inside one JAX process on one mesh,
with every job's model/optimizer state co-resident in HBM (the analogue of
co-resident CUDA contexts).  The paper's observation that the GPU program
"interchanges between jobs at each training step" (§6.1) is exactly this
executor's schedule.

The stepper also implements the paper's epoch-boundary mechanics:
checkpoint at epoch ends, and ``evict`` (undo) returns a job's state to its
last epoch snapshot — the scheduler can re-place it on another mesh.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpoint import AsyncCheckpointer, latest_checkpoint, restore_checkpoint
from repro.data.pipeline import DataConfig, SyntheticPipeline
from repro.train.steps import TrainBundle


@dataclasses.dataclass
class AnalyticBundle:
    """Dry-run stand-in for a ``TrainBundle``: no device work, virtual time.

    The calibration bridge (``repro.bridge``) measures co-location inflation
    through the SAME ``TemporalStepper``/``EarlyStageProfiler`` path a real
    deployment uses, but in CI there are no accelerators and full-size
    configs cannot run at all.  An ``AnalyticBundle`` closes that gap: the
    stepper recognises it and, instead of executing a jitted step, advances
    a virtual clock by this model of the step time under contention:

        step_s(S) = solo_step_s * (1 + sum_{j in S, j != self}
                                       (switch_base + switch_per_mem * mem_j)
                                     + max(0, sum_duty(S) - 1))

    i.e. a per-co-resident context-switch cost that grows with the peer's
    HBM working set (bigger state => colder caches after every switch — the
    paper's §3 explanation for why VGG16 sets inflate more than AlexNet
    sets), plus a proportional slowdown once the summed compute duty cycle
    oversubscribes the device.  The model is intentionally *independent* of
    ``cluster.colocation.inflation_factor`` — it is the dry-run ground truth
    the differential tests compare that predictor model against.
    """

    name: str
    solo_step_s: float
    duty_cycle_pct: float  # compute duty cycle, percent (0, 100]
    mem_util_pct: float  # average HBM residency, percent
    flops_per_step: float = 0.0  # per-device, for MFU-style duty reporting
    switch_base: float = 0.018
    switch_per_mem: float = 0.0007  # per percentage point of peer mem
    loss0: float = 6.0  # synthetic loss curve: loss0 / (1 + 0.02 * step)

    def init_state(self, seed: int = 0):
        return (), ()  # truthy sentinels: nothing to initialise

    def step_seconds(self, co_bundles: List["AnalyticBundle"]) -> float:
        """Virtual step time when co-resident with ``co_bundles`` (which
        includes self, mirroring the profiler's signature convention)."""
        overhead = sum(
            self.switch_base + self.switch_per_mem * b.mem_util_pct
            for b in co_bundles
            if b is not self
        )
        demand = sum(b.duty_cycle_pct for b in co_bundles) / 100.0
        return self.solo_step_s * (1.0 + overhead + max(0.0, demand - 1.0))

    def loss_at(self, step: int) -> float:
        return self.loss0 / (1.0 + 0.02 * step)


@dataclasses.dataclass
class ColocatedJob:
    name: str
    bundle: TrainBundle
    pipeline: SyntheticPipeline
    steps_per_epoch: int
    target_epochs: int
    ckpt_dir: Optional[str] = None
    # runtime state
    params: Any = None
    opt_state: Any = None
    step: int = 0
    step_times: List[float] = dataclasses.field(default_factory=list)
    losses: List[float] = dataclasses.field(default_factory=list)
    done: bool = False

    @property
    def epoch(self) -> int:
        return self.step // self.steps_per_epoch

    def epochs_done(self) -> float:
        return self.step / self.steps_per_epoch


class TemporalStepper:
    """Round-robin step interleaving of co-located jobs on one mesh."""

    def __init__(self, jobs: List[ColocatedJob], seed: int = 0):
        self.jobs = list(jobs)  # evict() must not shrink the caller's list
        self._ckpt: Dict[str, AsyncCheckpointer] = {}
        for i, job in enumerate(jobs):
            if job.params is None:
                job.params, job.opt_state = job.bundle.init_state(seed + i)
            if job.ckpt_dir:
                self._ckpt[job.name] = AsyncCheckpointer(job.ckpt_dir)

    def _make_batch(self, job: ColocatedJob) -> Dict[str, jnp.ndarray]:
        tokens, labels = job.pipeline.batch_at(job.step)
        batch = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels)}
        cfg = job.bundle.cfg
        if cfg.frontend is not None:
            batch["frontend_embeds"] = jnp.zeros(
                (tokens.shape[0], cfg.frontend_positions, cfg.d_model), jnp.bfloat16
            )
        return batch

    def step_round(self) -> Dict[str, Dict[str, float]]:
        """One round-robin pass: one train step per live job (the context
        switch happens between steps, as on the paper's GPUs)."""
        metrics: Dict[str, Dict[str, float]] = {}
        for job in self.jobs:
            if job.done:
                continue
            if isinstance(job.bundle, AnalyticBundle):
                # dry-run: virtual step time under the live co-resident set
                live = [j.bundle for j in self.jobs if not j.done]
                dt = job.bundle.step_seconds(live)
                loss = job.bundle.loss_at(job.step)
            else:
                batch = self._make_batch(job)
                t0 = time.perf_counter()
                job.params, job.opt_state, m = jax.block_until_ready(
                    job.bundle.step_fn(job.params, job.opt_state, batch)
                )
                dt = time.perf_counter() - t0
                loss = float(m["loss"])
                if not math.isfinite(loss):
                    raise FloatingPointError(
                        f"{job.name}: non-finite loss {loss} at step {job.step + 1}"
                    )
            job.step += 1
            job.step_times.append(dt)
            job.losses.append(loss)
            metrics[job.name] = {"loss": loss, "step_s": dt, "step": job.step}
            if job.step % job.steps_per_epoch == 0:
                self._on_epoch(job)
            if job.epoch >= job.target_epochs:
                job.done = True
        return metrics

    def _on_epoch(self, job: ColocatedJob) -> None:
        """Epoch boundary: the paper's natural checkpoint (Alg. 1 line 12+)."""
        ck = self._ckpt.get(job.name)
        if ck is not None:
            ck.save(
                job.step,
                {"params": job.params, "opt": job.opt_state},
                {"epoch": job.epoch, "name": job.name},
            )

    def run(self, max_rounds: int = 10_000) -> Dict[str, Any]:
        rounds = 0
        while any(not j.done for j in self.jobs) and rounds < max_rounds:
            self.step_round()
            rounds += 1
        for ck in self._ckpt.values():
            ck.wait()
        return self.report()

    def evict(self, name: str) -> ColocatedJob:
        """EaCO undo: drop a job back to its last epoch checkpoint and free
        its share of the mesh."""
        idx = next(i for i, j in enumerate(self.jobs) if j.name == name)
        job = self.jobs.pop(idx)
        ck = self._ckpt.pop(name, None)
        if ck is not None:
            ck.wait()
            path = latest_checkpoint(job.ckpt_dir)
            if path is not None:
                state, meta = restore_checkpoint(
                    path, {"params": job.params, "opt": job.opt_state}
                )
                job.params, job.opt_state = state["params"], state["opt"]
                job.step = int(meta["step"])
        else:
            job.step = job.epoch * job.steps_per_epoch  # logical rollback
        return job

    def report(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        for job in self.jobs:
            times = job.step_times
            out[job.name] = {
                "steps": job.step,
                "epochs": job.epochs_done(),
                "mean_step_s": float(np.mean(times)) if times else 0.0,
                "p50_step_s": float(np.median(times)) if times else 0.0,
                "final_loss": job.losses[-1] if job.losses else None,
                "first_loss": job.losses[0] if job.losses else None,
            }
        return out
