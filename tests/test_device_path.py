"""The chip path's guard rails, checked on the CPU: device-keyed peaks, mesh
shapes from the devices present, where the compile cache goes, and failed
steps that stop the run instead of being counted and passed over."""

import math
import runpy
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.colocation.profiler import EarlyStageProfiler
from repro.colocation.stepper import ColocatedJob, TemporalStepper
from repro.configs import get_config, smoke_config
from repro.data.pipeline import DataConfig, SyntheticPipeline
from repro.launch import compile_cache
from repro.launch.mesh import host_mesh_shape, make_host_mesh
from repro.roofline import hw
from repro.train.steps import TrainBundle
from repro.train.trainer import Trainer, TrainerConfig


def test_peak_is_looked_up_by_device_kind():
    assert hw.peak_flops_bf16("TPU v5 lite") == 197e12
    with pytest.raises(ValueError, match="no published peak"):
        hw.peak_flops_bf16("cpu")


@pytest.mark.parametrize("n,shape", [(1, (1, 1)), (2, (2, 1)), (4, (2, 2)), (8, (4, 2))])
def test_host_mesh_shape(n, shape):
    assert host_mesh_shape(n) == shape


def test_host_mesh_spans_the_devices_present():
    mesh = make_host_mesh()
    assert mesh.axis_names == ("data", "model")
    assert mesh.size == len(jax.devices())


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("env", [None, "/elsewhere/jax-cache"])
def test_compile_cache_placement(env, monkeypatch, cache_dir_config):
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    before = jax.config.jax_compilation_cache_dir
    got = compile_cache.use_compile_cache()
    if env is None:
        assert got == str(compile_cache.CHECKOUT_CACHE_DIR)
        assert got.endswith("/.jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    else:  # JAX reads the variable itself; nothing else is set
        assert got == env
        assert jax.config.jax_compilation_cache_dir == before


def _nan_bundle(losses=None):
    """A TrainBundle whose steps return ``losses`` in turn (by default a
    non-finite one each step), compiled nothing."""
    cfg = smoke_config(get_config("h2o-danube-1.8b"))
    losses = iter(losses or [])

    def step_fn(params, opt_state, batch):
        loss = jnp.float32(next(losses, math.nan))
        return params, opt_state, {"loss": loss, "grad_norm": jnp.float32(0)}

    bundle = TrainBundle(cfg, None, None, step_fn, None, None, None, None, {})
    bundle.init_state = lambda seed=0: (jnp.zeros(()), jnp.zeros(()))
    pipe = SyntheticPipeline(DataConfig(cfg.vocab_size, 8, 1))
    return bundle, pipe


def test_stepper_raises_on_non_finite_loss():
    bundle, pipe = _nan_bundle()
    job = ColocatedJob("nan-job", bundle, pipe, steps_per_epoch=4, target_epochs=1)
    with pytest.raises(FloatingPointError, match="nan-job"):
        TemporalStepper([job]).step_round()


def test_trainer_raises_on_non_finite_loss_without_checkpoint():
    bundle, pipe = _nan_bundle()
    tr = Trainer(bundle, pipe, TrainerConfig(total_steps=3, log_every=100))
    tr.init_or_restore(0)
    with pytest.raises(FloatingPointError, match="no checkpoint"):
        tr.train()
    assert tr.rollbacks == 1


def _chip_smoke() -> dict:
    """``chip_smoke.py``'s functions, loaded without running its main."""
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    return runpy.run_path(str(path), run_name="chip_smoke")


def test_finite_spike_without_checkpoint_is_counted_and_fails_the_smoke():
    bundle, pipe = _nan_bundle([10.0, 10.0, 100.0, 10.0])
    tr = Trainer(bundle, pipe, TrainerConfig(total_steps=4, log_every=100))
    tr.init_or_restore(0)
    rep = tr.train()  # a finite spike with nothing to return to trains on
    assert rep["steps"] == 4 and rep["rollbacks"] == 1
    with pytest.raises(AssertionError, match="1 loss spike"):
        _chip_smoke()["check_trainer"]("spiky", rep, bundle.cfg.vocab_size)


def test_profiler_duty_uses_the_device_peak():
    bundle, pipe = _nan_bundle()
    job = ColocatedJob("j", bundle, pipe, steps_per_epoch=4, target_epochs=1)
    stepper = TemporalStepper([job])
    prof = EarlyStageProfiler.for_stepper(stepper)
    assert prof.peak_flops is None  # real bundles: the device's own peak
    assert prof._duty("j", 1.0) == 0.0  # no FLOPs count: nothing to look up
    prof.flops_per_step["j"] = 1e12
    with pytest.raises(ValueError, match="no published peak"):  # a CPU has none
        prof._duty("j", 1.0)
    modelled = EarlyStageProfiler({"j": 1e12}, hw.MODELLED_PEAK_FLOPS_BF16)
    assert modelled._duty("j", 1.0) == pytest.approx(100.0 * 1e12 / 197e12)
