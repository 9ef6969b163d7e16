"""The four-chip path at smoke widths on four virtual CPU devices, in a
child process (JAX fixes its device count when it starts): the sharded job
steps through the stepper with its state split over the devices, and
``correct`` comes out true; with the exchange between the data shards left
out (each step takes the first shard's rows alone) it comes out false."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

CHILD = r"""
import json, sys, time
import jax
from chipbench import harness
from chipbench.tests import tiny
from chipbench.tests.test_correct import LIMITS, SEED, _broken

harness.temp_bytes = lambda r: 0
if sys.argv[1] == "exchange_left_out":
    harness.make_train_bundle = _broken("half")
cell = tiny.cell([("h2o-danube-1.8b-l4", 4)], limits=LIMITS, mesh={"data": 2, "model": 2}, chips=4)
res = harness.run_cell(cell, SEED, 0.5, False, jax.devices()[:4], time.perf_counter(), log=lambda m: None)
print(json.dumps({"correct": res["correct"], "compared": res["compared"], "count": res["device"]["count"]}))
"""


@pytest.mark.parametrize("fault", [None, "exchange_left_out"])
def test_four_devices(fault):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]),
    )
    out = subprocess.run(
        [sys.executable, "-c", CHILD, str(fault)], env=env, cwd=ROOT, capture_output=True, text=True, timeout=900
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["count"] == 4
    assert res["correct"] is (fault is None), res["compared"]
