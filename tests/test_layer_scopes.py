"""Layer-kind scopes in the compiled train step, and the co-location
stepper's profiler spans, checked on the CPU at smoke widths.

The scopes (``attention``, ``attention_core``, ``mlp``, ``ssm``,
``ssd_scan``, ``head``, ``optimizer``) only name operations in their
``op_name`` metadata; the spans (``repro.stepper.batch`` around batch
preparation, ``repro.stepper.step`` around the step call) carry each job's
name and step index.  A device trace of the benchmark reads both.
"""

from __future__ import annotations

import contextlib
import dataclasses
import re

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import source_info_util

from repro.colocation.stepper import ColocatedJob, TemporalStepper
from repro.configs import get_config, smoke_config
from repro.data.pipeline import DataConfig, SyntheticPipeline
from repro.train.steps import make_train_bundle

ARCHS = ["h2o-danube-1.8b", "mamba2-370m"]
LAYER_KINDS = {"attention", "mlp", "ssm", "head"}
BATCH, SEQ = 2, 128

_INSTR = re.compile(r"^\s*(?:ROOT )?%\S+ = \S+ ([\w-]+)\(")
_META = re.compile(r", metadata=\{[^}]*\}")


def _config(name):
    cfg = smoke_config(get_config(name))
    if cfg.sliding_window is not None:  # take the banded path: S > window + chunk
        cfg = dataclasses.replace(cfg, sliding_window=32)
    return cfg


def _step(name):
    """The tiny step's jitted function and abstract arguments."""
    bundle = make_train_bundle(_config(name), None, q_chunk=32)
    params = jax.eval_shape(bundle.model.init, jax.random.PRNGKey(0))
    opt = jax.eval_shape(bundle.optimizer.init, params)
    batch = {k: jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32) for k in ("tokens", "labels")}
    return bundle.step_fn, (params, opt, batch)


def _compiled_text(name) -> str:
    fn, args = _step(name)
    return fn.lower(*args).compile().as_text()


def _scopes(op_name: str):
    """The scope names on an ``op_name`` path, transforms unwrapped:
    ``transpose(jvp(head))`` is ``head``."""
    out = []
    for part in op_name.split("/"):
        while (m := re.fullmatch(r"(\w+)\((.*)\)", part)) and m.group(1) not in ("jit", "pjit"):
            part = m.group(2)
        out.append(part)
    return out


def _instructions(text):
    """(opcode, op_name) of every instruction with an op name."""
    for line in text.splitlines():
        m = _INSTR.match(line)
        op = re.search(r'metadata=\{[^}]*op_name="([^"]*)"', line)
        if m and op:
            yield m.group(1), op.group(1)


@pytest.fixture(scope="module", params=ARCHS)
def compiled(request):
    return request.param, _compiled_text(request.param)


def test_every_matmul_falls_under_a_layer_kind(compiled):
    name, text = compiled
    dots = [(op, set(_scopes(op_name))) for op, op_name in _instructions(text) if op in ("dot", "convolution")]
    assert dots
    bad = [op for op, scopes in dots if not scopes & LAYER_KINDS]
    assert not bad, bad
    # forward and transposed (backward) products both carry the scopes
    names = [op_name for op, op_name in _instructions(text) if op in ("dot", "convolution")]
    assert any("transpose(" in n for n in names) and any("/jvp(" in n for n in names)
    kinds = set().union(*(s & LAYER_KINDS for _, s in dots))
    assert kinds == ({"attention", "mlp", "head"} if name.startswith("h2o") else {"ssm", "head"})
    inner = {"attention_core"} if name.startswith("h2o") else {"ssd_scan"}
    assert set().union(*(s for _, s in dots)) >= inner


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _equations(sub)


def test_clip_and_adamw_fall_under_optimizer(compiled):
    name, text = compiled
    # in the compiled step: the clip's norm and AdamW's denominator
    sqrts = [op_name for op, op_name in _instructions(text) if op == "sqrt"]
    assert sqrts and all("optimizer" in _scopes(n) for n in sqrts), sqrts
    # in the traced step: every operation that the clip or AdamW adds
    fn, args = _step(name)
    ours = [
        eqn
        for eqn in _equations(jax.make_jaxpr(fn)(*args).jaxpr)
        if (frame := source_info_util.user_frame(eqn.source_info.traceback))
        and frame.file_name.endswith("optim/adamw.py")
    ]
    assert len(ours) > 10
    assert all("optimizer" in _scopes(str(e.source_info.name_stack)) for e in ours)


@contextlib.contextmanager
def _no_scope(name):
    yield


def _program(text: str) -> str:
    """The computations without metadata or the stack-frame tables, each
    instruction renamed by its first appearance (the lowering numbers
    some names differently when scopes are on)."""
    lines = text.splitlines()
    first = next(i for i, line in enumerate(lines) if line.startswith(("%", "ENTRY")))
    names = {}
    return re.sub(
        r"%[\w.-]+",
        lambda m: names.setdefault(m.group(0), f"%v{len(names)}"),
        _META.sub("", "\n".join(lines[first:])),
    )


@pytest.mark.parametrize("name", ARCHS)
def test_scopes_change_only_metadata(name, monkeypatch):
    scoped = _compiled_text(name)
    monkeypatch.setattr(jax, "named_scope", _no_scope)
    plain = _compiled_text(name)
    assert not any({"mlp", "ssm", "optimizer"} & set(_scopes(n)) for _, n in _instructions(plain))
    assert any({"mlp", "ssm"} & set(_scopes(n)) for _, n in _instructions(scoped))
    assert len(_program(plain)) > 10_000
    assert _program(plain) == _program(scoped)


# ---------------------------------------------------------------------------
# The stepper's spans
# ---------------------------------------------------------------------------


def _jobs(seed=0):
    out = []
    for i, name in enumerate(ARCHS):
        cfg = _config(name)
        bundle = make_train_bundle(cfg, None, q_chunk=32)
        pipe = SyntheticPipeline(DataConfig(cfg.vocab_size, SEQ // 2, BATCH, seed=seed + i))
        out.append(ColocatedJob(name, bundle, pipe, steps_per_epoch=100, target_epochs=1))
    return out


def test_stepper_spans_in_a_profiler_trace(tmp_path):
    from jax.profiler import ProfileData

    stepper = TemporalStepper(_jobs())
    stepper.step_round()  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(2):
        stepper.step_round()
    jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    stats = dict(e.stats)
                    spans.append((e.start_ns, e.name, stats["job"], int(stats["step"])))
    got = [s[1:] for s in sorted(spans)]
    want = [
        (kind, job, step)
        for step in (1, 2)
        for job in ARCHS
        for kind in ("repro.stepper.batch", "repro.stepper.step")
    ]
    assert got == want


def test_step_round_bookkeeping_without_a_trace():
    jobs = _jobs(seed=3)
    for j in jobs:
        j.params, j.opt_state = j.bundle.init_state(7)
    # the same steps taken by hand on copies of the same state
    want = {}
    for j in jobs:
        params, opt = jax.tree.map(jnp.copy, (j.params, j.opt_state))
        losses = []
        for step in range(2):
            tokens, labels = j.pipeline.batch_at(step)
            batch = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels)}
            params, opt, m = j.bundle.step_fn(params, opt, batch)
            losses.append(float(m["loss"]))
        want[j.name] = losses
    stepper = TemporalStepper(jobs)
    rounds = [stepper.step_round() for _ in range(2)]
    for j in jobs:
        assert j.losses == want[j.name]
        assert j.step == 2 and len(j.step_times) == 2
        assert all(t > 0 for t in j.step_times)
        assert [r[j.name]["step"] for r in rounds] == [1, 2]
        assert [r[j.name]["step_s"] for r in rounds] == j.step_times
        assert np.allclose([r[j.name]["loss"] for r in rounds], want[j.name])
