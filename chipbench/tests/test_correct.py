"""``correct`` at smoke widths on the CPU: a sound run passes; the run with
the timed path broken underneath fails, for each fault a one-chip training
cell can have; and the control (the reference with float8 products in the
program's place) fails.

The limits here are set, as the chip's are, between the readings of sound
runs and those of the control and faults, but from readings at these
widths (``calibrate`` on the CPU): the chip's limits belong to the chip's
sizes."""

import io
import time

import jax
import pytest

from chipbench import harness
from chipbench.calibrate import calibrate
from chipbench.tests import tiny

# readings at these widths over seeds 5, 2**31 + 9 and 77 (largest program
# reading; smallest control reading):
#   danube: loss 1.7e-5 / 3.2e-4, grad 9.2e-4 / 4.4e-3, update 8.7e-4 / 7.9e-3
#   mamba:  loss 1.2e-4 / 1.4e-4, grad 2.7e-3 / 3.1e-2, update 1.1e-2 / 9.4e-3
LIMITS = {
    "h2o-danube-1.8b-l4": {"loss": 1e-4, "grad": 2e-3, "update": 3e-3},
    "mamba2-370m": {"loss": 1e-3, "grad": 1e-2, "update": 3e-2},
}
SEED = 2**31 + 9


def _broken(kind):
    """``make_train_bundle`` whose step is broken in the way ``kind`` says."""
    real = harness.make_train_bundle

    def make(*args, **kw):
        bundle = real(*args, **kw)
        step = bundle.step_fn
        if kind == "unchanged":
            loss = jax.jit(lambda p, t, l: bundle.model.loss(p, t, l)[0])

            def fn(p, o, batch):
                return p, o, {"loss": loss(p, batch["tokens"], batch["labels"])}

        else:  # half of the batch left out: rows, or the later positions of one row

            def fn(p, o, batch):
                t, l = batch["tokens"], batch["labels"]
                if t.shape[0] > 1:
                    t, l = t[: t.shape[0] // 2], l[: l.shape[0] // 2]
                else:
                    l = l.at[:, l.shape[1] // 2 :].set(-100)
                return step(p, o, {"tokens": t, "labels": l})

        bundle.step_fn = fn
        return bundle

    return make


def _run(jobs, monkeypatch, fault=None):
    monkeypatch.setattr(harness, "temp_bytes", lambda r: 0)
    if fault:
        monkeypatch.setattr(harness, "make_train_bundle", _broken(fault))
    cell = tiny.cell(jobs, limits=LIMITS)
    return harness.run_cell(cell, SEED, 0.5, False, jax.devices()[:1], time.perf_counter(), log=lambda m: None)


def test_sound_run_is_correct(monkeypatch):
    result = _run([("h2o-danube-1.8b-l4", 1), ("mamba2-370m", 4)], monkeypatch)
    assert result["correct"], result["compared"]
    assert list(result)[-1] == "compared"
    assert set(result["metrics"]) == {"tokens_per_s", "peak_hbm_bytes", "setup_s"}


@pytest.mark.parametrize(
    "jobs, fault",
    [
        ([("mamba2-370m", 4)], "unchanged"),
        ([("mamba2-370m", 4)], "half"),
        ([("h2o-danube-1.8b-l4", 1)], "unchanged"),
        ([("h2o-danube-1.8b-l4", 1)], "half"),
    ],
)
def test_broken_step_is_not_correct(jobs, fault, monkeypatch):
    result = _run(jobs, monkeypatch, fault)
    assert not result["correct"], result["compared"]


def test_control_is_not_correct():
    cell = tiny.cell([("h2o-danube-1.8b-l4", 1), ("mamba2-370m", 4)], limits=LIMITS)
    summary = calibrate(cell, [SEED], 1, jax.devices()[:1], io.StringIO(), lambda m: None)
    for job, numbers in summary.items():
        lim = LIMITS[job]
        assert all(numbers[k]["program"] <= lim[k] for k in lim), (job, numbers)
        assert any(numbers[k]["control"] > lim[k] for k in lim), (job, numbers)
        assert any(numbers[k]["half_batch"] > lim[k] for k in lim), (job, numbers)
