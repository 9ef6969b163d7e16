"""The four-chip path at smoke widths on four virtual CPU devices, in a
child process (JAX fixes its device count when it starts): the sharded job
steps through the stepper with its state split over the devices, and
``correct`` comes out true; with the exchange between the data shards left
out (each step takes the first shard's rows alone) it comes out false.
Once with the 4-layer danube under a mesh given here, once with the
``mesh4-b4x4096`` mix as it stands (the 24-layer danube, cut to smoke
sizes, under the mix's own mesh), where the control, the reference with
float8 products put in the program's place and judged by the harness's
own comparison, must not come out correct either."""

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
mix, fault = sys.argv[1], sys.argv[2]
if fault == "exchange_left_out":
    harness.make_train_bundle = _broken("half")
if mix == "solo-b4x2048":
    cell = tiny.cell([("h2o-danube-1.8b-l4", 4)], limits=LIMITS, mesh={"data": 2, "model": 2}, chips=4)
else:
    limits = {"h2o-danube-1.8b": LIMITS["h2o-danube-1.8b-l4"]}
    cell = tiny.cell(traffic=mix, limits=limits, chips=4)
    assert cell.traffic["mesh"] == {"data": 2, "model": 2} and [(j.name, j.batch) for j in cell.jobs] == [("h2o-danube-1.8b", 4)]
if fault == "control":
    import io
    from chipbench.calibrate import calibrate
    out = io.StringIO()
    calibrate(cell, [SEED], 1, jax.devices()[:4], out, lambda m: None)
    got = json.loads(out.getvalue().splitlines()[0])[cell.jobs[0].name]
    print(json.dumps({"correct": got["control_correct"], "compared": got["control_compared"], "count": 4}))
else:
    res = harness.run_cell(cell, SEED, 0.5, False, jax.devices()[:4], time.perf_counter(), log=lambda m: None)
    print(json.dumps({"correct": res["correct"], "compared": res["compared"], "count": res["device"]["count"]}))
"""


def _child(mix, fault):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]),
    )
    out = subprocess.run(
        [sys.executable, "-c", CHILD, mix, str(fault)], env=env, cwd=ROOT, capture_output=True, text=True, timeout=900
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["count"] == 4
    assert res["correct"] is (fault is None), res["compared"]


@pytest.mark.parametrize("fault", [None, "exchange_left_out"])
def test_four_devices(fault):
    _child("solo-b4x2048", fault)


@pytest.mark.parametrize("fault", [None, "exchange_left_out", "control"])
def test_four_devices_mesh4_mix(fault):
    _child("mesh4-b4x4096", fault)
