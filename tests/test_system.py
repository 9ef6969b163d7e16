"""End-to-end behaviour tests for the paper's system.

Covers: trainer fault tolerance (restart, straggler detection), the
co-location executor (temporal sharing + evict/restore), the early-stage
profiler, spatial mesh splitting, and real learning on the smoke configs.
"""

import math
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# real multi-step training on CPU: this module compiles and runs trainers
# end to end (~1 min total), so the whole file lives in the nightly tier
pytestmark = pytest.mark.slow

from repro.colocation.profiler import EarlyStageProfiler
from repro.colocation.stepper import ColocatedJob, TemporalStepper
from repro.configs import get_config, smoke_config
from repro.data.pipeline import DataConfig, SyntheticPipeline
from repro.roofline import hw
from repro.train.steps import make_train_bundle
from repro.train.trainer import Trainer, TrainerConfig


def _job(arch, seed=0, ckpt_dir=None, steps_per_epoch=4, target_epochs=2):
    cfg = smoke_config(get_config(arch))
    bundle = make_train_bundle(cfg)
    pipe = SyntheticPipeline(
        DataConfig(cfg.vocab_size, seq_len=64, global_batch=2, seed=seed)
    )
    return ColocatedJob(
        name=arch,
        bundle=bundle,
        pipeline=pipe,
        steps_per_epoch=steps_per_epoch,
        target_epochs=target_epochs,
        ckpt_dir=ckpt_dir,
    )


def test_trainer_restart_resumes_exactly():
    cfg = smoke_config(get_config("mamba2-370m"))
    bundle = make_train_bundle(cfg)
    pipe = SyntheticPipeline(DataConfig(cfg.vocab_size, 64, 4, seed=0))
    with tempfile.TemporaryDirectory() as d:
        t1 = Trainer(
            bundle, pipe,
            TrainerConfig(total_steps=6, steps_per_epoch=3, ckpt_every_steps=3,
                          ckpt_dir=d, log_every=100),
        )
        t1.init_or_restore(0)
        t1.train()
        # a NEW trainer restores at step 6 and continues to 9
        t2 = Trainer(
            bundle, pipe,
            TrainerConfig(total_steps=9, steps_per_epoch=3, ckpt_every_steps=3,
                          ckpt_dir=d, log_every=100),
        )
        msg = t2.init_or_restore(0)
        assert "restored step 6" in msg
        t2.train()
        assert t2.step == 9


def test_trainer_straggler_detection():
    cfg = smoke_config(get_config("minitron-8b"))
    bundle = make_train_bundle(cfg)
    pipe = SyntheticPipeline(DataConfig(cfg.vocab_size, 64, 2, seed=0))
    events = []
    tr = Trainer(
        bundle,
        pipe,
        TrainerConfig(total_steps=6, steps_per_epoch=100, ckpt_every_steps=100,
                      log_every=100, straggler_k=2.5),
        on_straggler=lambda s, dt, ewma: events.append((s, dt, ewma)),
    )
    tr.init_or_restore(0)
    orig = bundle.step_fn
    calls = {"n": 0}

    def slow_step(*a, **k):
        import time as _t

        calls["n"] += 1
        if calls["n"] == 4:
            _t.sleep(1.0)  # injected stall
        return orig(*a, **k)

    tr.bundle.step_fn = slow_step
    tr.train()
    assert tr.straggler_events, "straggler must be detected"
    assert events, "straggler hook must fire"


def test_temporal_stepper_two_jobs_progress():
    jobs = [_job("minitron-8b", 0), _job("mamba2-370m", 1)]
    stepper = TemporalStepper(jobs)
    report = stepper.run(max_rounds=16)
    for name, r in report.items():
        assert r["steps"] == 8  # 4 steps/epoch x 2 epochs
        assert np.isfinite(r["final_loss"])


def test_stepper_evict_restores_epoch_checkpoint():
    with tempfile.TemporaryDirectory() as d:
        jobs = [_job("mamba2-370m", 0, ckpt_dir=d, steps_per_epoch=3, target_epochs=3)]
        stepper = TemporalStepper(jobs)
        for _ in range(4):  # epoch boundary at step 3, then 1 extra step
            stepper.step_round()
        job = stepper.evict("mamba2-370m")
        assert job.step == 3, "evict must roll back to the epoch checkpoint"


def test_early_stage_profiler_reports_inflation():
    jobs = [_job("minitron-8b", 0), _job("internvl2-2b", 1)]
    # a CPU has no published peak: divide by the modelled v5e one
    prof = EarlyStageProfiler(
        flops_per_step={j.name: 1e9 for j in jobs},
        peak_flops=hw.MODELLED_PEAK_FLOPS_BF16,
    )
    stepper = TemporalStepper(jobs)
    solo = prof.profile_solo(stepper, steps=2)
    obs = prof.observe(stepper, rounds=2)
    for name in solo:
        assert solo[name].mean_step_s > 0
        assert obs[name].inflation_vs_solo is not None
        assert 0 < obs[name].duty_cycle_pct <= 100.0


def test_spatial_mesh_split():
    from repro.colocation.spatial import split_mesh, submesh_for_job
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(jax.devices()[:1])  # (1, 1)
    subs = split_mesh(mesh, 1, axis="data")
    assert len(subs) == 1 and subs[0].axis_names == mesh.axis_names
    sub = submesh_for_job(mesh, 0, 1, axis="data")
    assert sub.devices.shape == mesh.devices.shape
    with pytest.raises(ValueError):
        split_mesh(mesh, 2, axis="data")


def test_train_loss_decreases():
    """The framework actually learns: 30 steps on structured synthetic data
    reduce the loss materially."""
    from repro.optim.schedules import constant

    cfg = smoke_config(get_config("h2o-danube-1.8b"))
    bundle = make_train_bundle(cfg, lr_schedule=constant(2e-3))
    pipe = SyntheticPipeline(DataConfig(cfg.vocab_size, 128, 8, seed=3))
    tr = Trainer(
        bundle, pipe,
        TrainerConfig(total_steps=30, steps_per_epoch=10, ckpt_every_steps=1000,
                      log_every=1000),
    )
    tr.init_or_restore(0)
    rep = tr.train()
    assert rep["final_loss"] < rep["first_loss"] - 0.3, rep


def test_microbatched_step_matches_unbatched():
    """Gradient accumulation must match the single-pass step numerically
    (same data, same update) within bf16 tolerance."""
    cfg = smoke_config(get_config("minitron-8b"))
    b1 = make_train_bundle(cfg, microbatches=1)
    b4 = make_train_bundle(cfg, microbatches=4)
    p1, o1 = b1.init_state(0)
    p4, o4 = b4.init_state(0)
    pipe = SyntheticPipeline(DataConfig(cfg.vocab_size, 32, 8, seed=0))
    tokens, labels = pipe.batch_at(0)
    batch = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels)}
    p1n, _, m1 = b1.step_fn(p1, o1, batch)
    p4n, _, m4 = b4.step_fn(p4, o4, batch)
    assert abs(float(m1["loss"]) - float(m4["loss"])) < 0.05
    for a, b in zip(jax.tree.leaves(p1n), jax.tree.leaves(p4n)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=0.05, rtol=0.1
        )
