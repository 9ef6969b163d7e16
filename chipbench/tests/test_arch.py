"""Architectures are found by name: a new one joins the benchmark through
new files alone (an architecture module, a configuration and its smoke
sizes), and its scopes join the ones the trace is reduced by."""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

from chipbench import arch, harness, spans
from chipbench.tests import tiny
from chipbench.tests.test_correct import LIMITS, SEED

HERE = Path(__file__).resolve().parent

TOY = '''
"""A stand-in architecture: the transformer's functions under another
name, with one layer kind of its own."""
from chipbench import arch

_t = arch.load("transformer")
check, specs, loss, forward_flops = _t.check, _t.specs, _t.loss, _t.forward_flops
SCOPES = dict(_t.SCOPES, toy_block=None)
'''


def _new_files(root, module=TOY):
    """Under ``root``: ``chipbench/arch/toy.py``, the configuration ``toy-1``
    (the 4-layer danube's file, architecture ``toy``) and its smoke sizes."""
    bench = root / "chipbench"
    for d in ("arch", "configs", "tests/tiny_sizes"):
        (bench / d).mkdir(parents=True)
    (bench / "arch" / "toy.py").write_text(module)
    base = "h2o-danube-1.8b-l4"
    cfg = json.loads((tiny.ROOT / "chipbench" / "configs" / f"{base}.json").read_text())
    cfg.update(name="toy-1", architecture="toy")
    (bench / "configs" / "toy-1.json").write_text(json.dumps(cfg))
    shutil.copy(tiny.ROOT / "chipbench" / "tests" / "tiny_sizes" / f"{base}.json", bench / "tests" / "tiny_sizes" / "toy-1.json")
    return base


def test_new_architecture_from_new_files(tmp_path, monkeypatch):
    base = _new_files(tmp_path)
    assert "toy_block" not in arch.scopes()
    monkeypatch.setattr(arch, "DIRS", [tmp_path / "chipbench" / "arch", *arch.DIRS])
    monkeypatch.setattr(harness, "temp_bytes", lambda r: 0)
    # the trace is reduced by the new layer kind with no other change
    assert arch.scopes()["toy_block"] is None
    op_name = "jit(train_step)/transpose(jvp(toy_block))/dot_general:"
    assert spans.scopes_of(op_name, arch.scopes()) == ("toy_block",)
    window = [spans.Span("chipbench.window", 0, 100)]
    reduced = spans.reduce(spans.Trace(window, {0: [spans.Op("dot", 10, 40, ("toy_block",))]}, {}), [0])
    assert reduced["under"]["toy_block"] == reduced["kinds_s"] == pytest.approx(30e-9)
    cell = tiny.cell([("toy-1", 1)], limits={"toy-1": LIMITS[base]}, root=tmp_path)
    assert cell.jobs[0].cfg["architecture"] == "toy"
    result = harness.run_cell(cell, SEED, 0.5, False, jax.devices()[:1], time.perf_counter(), log=lambda m: None)
    assert result["correct"], result["compared"]


def test_every_architecture_declares_what_the_harness_reads():
    for name in arch.names():
        module = arch.load(name)
        for fn in ("check", "specs", "loss", "forward_flops"):
            assert callable(getattr(module, fn)), (name, fn)
        assert all(outer is None or outer in module.SCOPES for outer in module.SCOPES.values()), name
    # each scope is held by the same outer scope in every architecture
    assert arch.scopes() and all(outer is None or outer in arch.scopes() for outer in arch.scopes().values())


@pytest.mark.parametrize("tests", ["test_spans.py", "test_trace.py"])
def test_span_and_reader_tests_hold_with_a_new_architecture(tests, tmp_path):
    # the existing tests of the reduction and the readers, run with the toy
    # architecture's module (and its scope of its own) found beside the
    # others: none of them may depend on which architectures exist
    _new_files(tmp_path)
    run = (
        "import sys, pytest; from chipbench import arch; "
        f"arch.DIRS.insert(0, {str(tmp_path / 'chipbench' / 'arch')!r}); "
        "assert 'toy_block' in arch.scopes(); "
        f"sys.exit(pytest.main(['-q', '-p', 'no:cacheprovider', {str(HERE / tests)!r}]))"
    )
    root = tiny.ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join([str(root), str(root / "src")]))
    out = subprocess.run([sys.executable, "-c", run], cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]


def test_a_scope_held_differently_by_two_architectures_is_refused(tmp_path, monkeypatch):
    _new_files(tmp_path, TOY.replace("toy_block=None", "toy_block=None, attention_core=None"))
    monkeypatch.setattr(arch, "DIRS", [tmp_path / "chipbench" / "arch", *arch.DIRS])
    with pytest.raises(ValueError, match="attention_core"):
        arch.scopes()


def test_an_unknown_architecture_is_refused():
    with pytest.raises(ValueError, match="no architecture module"):
        arch.load("no-such-architecture")
