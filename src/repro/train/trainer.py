"""Fault-tolerant training loop.

Wraps a :class:`TrainBundle` with:
  * epoch-boundary + every-N-step async checkpoints (the paper's undo /
    resume mechanism doubles as failure recovery),
  * automatic restart from the latest snapshot (restartable after process
    death; the data pipeline is counter-based so the step counter is the
    only cursor),
  * per-step-time EWMA straggler detection: a step slower than
    ``straggler_k`` x the EWMA raises a hook (re-placement in the cluster
    scheduler; exclusion from the DP group at the next epoch in a real
    multi-host run),
  * loss-spike detection with rollback (restore last snapshot, skip the
    offending data window); a non-finite loss with no snapshot to return
    to raises ``FloatingPointError`` instead of training on.
"""

from __future__ import annotations

import dataclasses
import math
import os
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpoint import (
    AsyncCheckpointer,
    latest_checkpoint,
    restore_checkpoint,
)
from repro.data.pipeline import SyntheticPipeline
from repro.train.steps import TrainBundle


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 100
    steps_per_epoch: int = 50
    ckpt_every_steps: int = 50
    ckpt_dir: Optional[str] = None
    keep_ckpts: int = 3
    straggler_k: float = 3.0
    ewma_alpha: float = 0.2
    loss_spike_factor: float = 3.0  # rollback if loss > factor x ewma
    log_every: int = 10


class Trainer:
    def __init__(
        self,
        bundle: TrainBundle,
        pipeline: SyntheticPipeline,
        cfg: TrainerConfig,
        on_straggler: Optional[Callable[[int, float, float], None]] = None,
    ):
        self.bundle = bundle
        self.pipeline = pipeline
        self.cfg = cfg
        self.on_straggler = on_straggler
        self.ckpt = AsyncCheckpointer(cfg.ckpt_dir, cfg.keep_ckpts) if cfg.ckpt_dir else None
        self.params = None
        self.opt_state = None
        self.step = 0
        self.history: List[Dict[str, float]] = []
        self._ewma_t: Optional[float] = None
        self._ewma_loss: Optional[float] = None
        self.straggler_events: List[int] = []
        self.rollbacks: int = 0

    # -- lifecycle ----------------------------------------------------------

    def init_or_restore(self, seed: int = 0) -> str:
        """Fresh init, or resume from the latest checkpoint if one exists."""
        self.params, self.opt_state = self.bundle.init_state(seed)
        if self.cfg.ckpt_dir:
            path = latest_checkpoint(self.cfg.ckpt_dir)
            if path is not None:
                state, meta = restore_checkpoint(
                    path,
                    {"params": self.params, "opt": self.opt_state},
                    shardings=(
                        {"params": self.bundle.param_shardings, "opt": self.bundle.opt_shardings}
                        if self.bundle.param_shardings is not None
                        else None
                    ),
                )
                self.params, self.opt_state = state["params"], state["opt"]
                self.step = int(meta["step"])
                return f"restored step {self.step} from {path}"
        return "fresh init"

    def _batch(self, step: int) -> Dict[str, jnp.ndarray]:
        tokens, labels = self.pipeline.batch_at(step)
        batch = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels)}
        cfg = self.bundle.cfg
        if cfg.frontend is not None:
            batch["frontend_embeds"] = jnp.zeros(
                (tokens.shape[0], cfg.frontend_positions, cfg.d_model), jnp.bfloat16
            )
        if self.bundle.batch_shardings:
            batch = {
                k: jax.device_put(v, self.bundle.batch_shardings[k])
                if k in self.bundle.batch_shardings
                else v
                for k, v in batch.items()
            }
        return batch

    # -- main loop ------------------------------------------------------------

    def train(self) -> Dict[str, Any]:
        assert self.params is not None, "call init_or_restore() first"
        c = self.cfg
        while self.step < c.total_steps:
            batch = self._batch(self.step)
            t0 = time.perf_counter()
            self.params, self.opt_state, metrics = jax.block_until_ready(
                self.bundle.step_fn(self.params, self.opt_state, batch)
            )
            dt = time.perf_counter() - t0
            loss = float(metrics["loss"])
            self.step += 1
            self._track(dt, loss)
            self.history.append({"step": self.step, "loss": loss, "step_s": dt})
            if self.step % c.log_every == 0:
                gn = float(metrics.get("grad_norm", 0.0))
                print(
                    f"step {self.step:5d} loss {loss:8.4f} gnorm {gn:7.3f} "
                    f"{dt*1e3:7.1f} ms/step",
                    flush=True,
                )
            if not math.isfinite(loss) or (
                self._ewma_loss and loss > c.loss_spike_factor * self._ewma_loss
            ):
                self._rollback(loss)
                continue
            if self.ckpt and (
                self.step % c.ckpt_every_steps == 0
                or self.step % c.steps_per_epoch == 0
            ):
                self.ckpt.save(
                    self.step,
                    {"params": self.params, "opt": self.opt_state},
                    {"epoch": self.step // c.steps_per_epoch},
                )
        if self.ckpt:
            self.ckpt.save(
                self.step,
                {"params": self.params, "opt": self.opt_state},
                {"epoch": self.step // c.steps_per_epoch},
            )
            self.ckpt.wait()
        return self.report()

    def _track(self, dt: float, loss: float) -> None:
        a = self.cfg.ewma_alpha
        if self.step <= 1:
            # the first step's wall time is dominated by XLA compilation;
            # seeding the EWMA with it would mask real stragglers for many
            # steps, so timing starts at step 2
            pass
        elif self._ewma_t is None:
            self._ewma_t = dt
        else:
            if dt > self.cfg.straggler_k * self._ewma_t:
                self.straggler_events.append(self.step)
                if self.on_straggler:
                    self.on_straggler(self.step, dt, self._ewma_t)
            self._ewma_t = (1 - a) * self._ewma_t + a * dt
        if math.isfinite(loss):
            self._ewma_loss = (
                loss if self._ewma_loss is None else (1 - a) * self._ewma_loss + a * loss
            )

    def _rollback(self, loss: float) -> None:
        """Loss spike / NaN: restore the last snapshot and skip ahead.

        Every detected spike counts in ``rollbacks``, also a finite one
        with no snapshot to return to, which trains on."""
        self.rollbacks += 1
        print(f"step {self.step}: loss spike {loss}, rollback", flush=True)
        path = latest_checkpoint(self.cfg.ckpt_dir) if self.cfg.ckpt_dir else None
        if path is None:
            if not math.isfinite(loss):
                raise FloatingPointError(
                    f"non-finite loss {loss} at step {self.step} and no "
                    "checkpoint to roll back to"
                )
            return
        if self.ckpt:
            self.ckpt.wait()
        state, meta = restore_checkpoint(
            path, {"params": self.params, "opt": self.opt_state}
        )
        self.params, self.opt_state = state["params"], state["opt"]
        # skip past the offending window (counter-based pipeline => pure jump)
        self.step = int(meta["step"]) + 1

    def report(self) -> Dict[str, Any]:
        losses = [h["loss"] for h in self.history]
        times = [h["step_s"] for h in self.history]
        return {
            "steps": self.step,
            "first_loss": losses[0] if losses else None,
            "final_loss": losses[-1] if losses else None,
            "min_loss": min(losses) if losses else None,
            "mean_step_s": float(np.mean(times)) if times else None,
            "straggler_events": len(self.straggler_events),
            "rollbacks": self.rollbacks,
        }
