"""The reduction by the program's own names (``spans.py``) on hand-made
intervals, on the benchmark's earlier chip trace (which has none of them),
and on a trace of the co-location stepper recorded on a TPU v5e by
``record_stepper_trace.py`` (``data/stepper_trace.xplane.pb``)."""

import shutil
from pathlib import Path

import pytest

from chipbench import harness, spans, trace
from chipbench.spans import Op, Span, Trace

DATA = Path(__file__).resolve().parent / "data"

CORE = "jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/attention/attention_core/while/body/closed_call/bqhd,bkhd->bhqk/dot_general:"


@pytest.mark.parametrize(
    "op_name,want",
    [
        (CORE, ("attention", "attention_core")),
        ("jit(train_step)/jvp(head)/while/body/closed_call/bsd,dv->bsv/dot_general", ("head",)),
        ("jit(train_step)/transpose(jvp(head))/while/body/closed_call/bsd,dv->bsv/dot_general:", ("head",)),
        ("jit(train_step)/jvp()/while/body/closed_call/ssm/ssd_scan/closed_call/while/body/mul:", ("ssm", "ssd_scan")),
        ("jit(train_step)/optimizer/sqrt:", ("optimizer",)),
        ("jit(train_step)/jvp()/while/body/closed_call/add:", ()),
        ("jit(attention)/dot_general:", ()),  # a function's name, not a scope
        ("", ()),
    ],
)
def test_scopes_of_an_op_name(op_name, want):
    assert spans.scopes_of(op_name) == want


def _op(name, s, e, *scopes):
    return Op(name, s, e, tuple(scopes))


def test_self_time_of_nested_operations():
    # a loop 0..100 holding a product 10..30 and a fusion 40..60 that holds
    # a copy 45..50; then an operation 100..120 that starts as the loop ends
    ops = [
        _op("while", 0, 100),
        _op("dot", 10, 30, "mlp"),
        _op("fusion", 40, 60, "attention"),
        _op("copy", 45, 50, "attention", "attention_core"),
        _op("after", 100, 120, "head"),
    ]
    got = {op.name: t for op, t in spans.self_times(ops, 0, 200)}
    assert got == {"while": 60, "dot": 20, "fusion": 15, "copy": 5, "after": 20}
    # clipped to a window 5..110 first: the loop keeps 95 - 40, the last 10
    got = {op.name: t for op, t in spans.self_times(ops, 5, 110)}
    assert got == {"while": 55, "dot": 20, "fusion": 15, "copy": 5, "after": 10}


def _window(lo, hi, *program):
    return [Span("chipbench.window", lo, hi)] + list(program)


def test_self_time_goes_to_the_innermost_scope_or_unscoped():
    tr = Trace(
        spans=_window(0, 100),
        ops={
            0: [
                _op("while", 0, 100),
                _op("dot", 10, 30, "mlp"),
                _op("fusion", 40, 60, "attention"),
                _op("copy", 45, 50, "attention", "attention_core"),
                _op("adam", 70, 80, "optimizer"),
            ]
        },
        modules={},
    )
    got = spans.reduce(tr, [0])
    ns = 1e-9
    assert got["by_scope"] == {
        "unscoped": pytest.approx(50 * ns),
        "mlp": pytest.approx(20 * ns),
        "attention": pytest.approx(15 * ns),
        "attention/attention_core": pytest.approx(5 * ns),
        "optimizer": pytest.approx(10 * ns),
    }
    # attention counts its core; the core alone is the innermost part
    assert got["under"]["attention"] == pytest.approx(20 * ns)
    assert got["under"]["attention_core"] == pytest.approx(5 * ns)
    assert got["under"]["ssm"] == 0
    assert got["kinds_s"] == pytest.approx(50 * ns)
    assert got["busy_s"] == pytest.approx(100 * ns)
    assert got["top_unscoped"] == [["while", pytest.approx(50 * ns)]]
    assert got["has_scopes"] and not got["has_spans"]


def test_idle_is_split_exactly_by_program_span():
    # window 0..100; batch 10..20, step 20..60 (program run 22..58), batch
    # 62..70, step 70..92 (program run 72..90): the bounds -2..2 give no shift
    program = [
        Span("repro.stepper.batch", 10, 20, "a", 0),
        Span("repro.stepper.step", 20, 60, "a", 0),
        Span("repro.stepper.batch", 62, 70, "b", 0),
        Span("repro.stepper.step", 70, 92, "b", 0),
    ]
    tr = Trace(
        spans=_window(0, 100, *program),
        ops={0: [_op("f", 22, 58, "ssm"), _op("g", 72, 90, "mlp")]},
        modules={0: [(22, 58), (72, 90)]},
    )
    got = spans.reduce(tr, [0])
    assert got["clock"] == {"steps_matched": 2, "lower_ns": -2, "upper_ns": 2, "offset_ns": 0, "consistent": True}
    # idle 0..22, 58..72, 90..100 = 46: in a batch 10..20 and 62..70 (18),
    # in a step 20..22, 58..60, 70..72 and 90..92 (8), the rest outside (20)
    ns = 1e-9
    assert got["idle_s"] == {
        "total": pytest.approx(46 * ns),
        "batch": pytest.approx(18 * ns),
        "step": pytest.approx(8 * ns),
        "outside": pytest.approx(20 * ns),
    }
    # the gaps, longest first, each named by the innermost span at its middle
    assert got["idle_gaps"] == [
        ("repro.stepper.batch", pytest.approx(22 * ns)),
        ("repro.stepper.batch", pytest.approx(14 * ns)),
        ("chipbench.window", pytest.approx(10 * ns)),
    ]
    assert got["has_spans"]


MS = 1_000_000


def _skewed(skew, late_end=0):
    """Three 250 ms steps with 5 ms between them, each run on a device whose
    clock is ``skew`` ahead: it starts 0.1, 0.3 and 0.2 ms into its span and
    ends 0.05, 0.02 and 0.08 ms before the span does (the last ``late_end``
    later)."""
    steps, runs = [], []
    for i, (start, end) in enumerate([(0.1, 0.05), (0.3, 0.02), (0.2, 0.08 - late_end)]):
        t = i * 255 * MS
        steps.append(Span("repro.stepper.step", t, t + 250 * MS, "a", i))
        runs.append((t + start * MS + skew, t + (250 - end) * MS + skew))
    ops = [_op("r", s, e, "ssm") for s, e in runs]
    return Trace(_window(-MS, 800 * MS, *steps), {0: ops}, {0: runs})


def test_a_known_skew_is_recovered_within_its_bounds():
    got = spans.reduce(_skewed(1 * MS), [0])["clock"]
    # lower: 1 - 0.02 ms (the second step ends nearest its span's end);
    # upper: 1 + 0.1 ms (the first starts nearest its span's start)
    assert got["steps_matched"] == 3 and got["consistent"]
    assert got["lower_ns"] == pytest.approx(0.98 * MS)
    assert got["upper_ns"] == pytest.approx(1.1 * MS)
    assert got["lower_ns"] <= 1 * MS <= got["upper_ns"]
    assert got["offset_ns"] == pytest.approx(1.04 * MS)


def test_conflicting_bounds_apply_no_offset():
    # the third run ends 0.3 ms after its span: lower 1.22 ms > upper 1.1 ms
    got = spans.reduce(_skewed(1 * MS, late_end=0.3), [0])
    assert got["clock"]["consistent"] is False
    assert got["clock"]["lower_ns"] == pytest.approx(1.22 * MS)
    assert got["clock"]["offset_ns"] == 0.0
    # with no offset, idle is split on the host's clock as it stands
    assert got["idle_s"]["total"] == pytest.approx(
        got["idle_s"]["batch"] + got["idle_s"]["step"] + got["idle_s"]["outside"]
    )


def _record(tmp_path, fixture, monkeypatch, chips=1, tokens=(4096,)):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(DATA / fixture, d / "t.xplane.pb")
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    spans._reduce_file.cache_clear()
    return {
        "trace": {"busy_s": 1.0, "window_s": 1.0},
        "chips": chips,
        "window_s": 1.0,
        "jobs": [{"steps": 1, "tokens_per_step": t} for t in tokens],
    }


NEW = [
    "attention.ns_per_token",
    "attention_core.ns_per_token",
    "mlp.ns_per_token",
    "ssm.ns_per_token",
    "ssd_scan.ns_per_token",
    "head.ns_per_token",
    "optimizer.ns_per_token",
    "executor.idle_in_batch_share",
    "executor.idle_in_step_share",
]


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_names_reads_nothing(metric, tmp_path, monkeypatch):
    # the earlier chip trace: a product under no scope, no program span
    record = _record(tmp_path, "tiny_trace.xplane.pb", monkeypatch)
    assert harness.metric_reader(metric)(record) is None
    # and an untraced run reads nothing either
    assert harness.metric_reader(metric)({**record, "trace": None}) is None


def test_recorded_stepper_trace():
    # recorded on one TPU v5e: a one-layer GQA job and a one-layer Mamba-2
    # job at smoke widths, three rounds of the stepper in the window
    tr = spans.collect(str(DATA / "stepper_trace.xplane.pb"))
    program = sorted((sp for sp in tr.spans if sp.name.startswith("repro.")), key=lambda sp: sp.start)
    assert [(sp.name, sp.job, sp.step) for sp in program] == [
        (kind, job, step)
        for step in (1, 2, 3)
        for job in ("h2o-danube-1.8b", "mamba2-370m")
        for kind in ("repro.stepper.batch", "repro.stepper.step")
    ]
    got = spans.reduce(tr, [0])
    # each step span against its program run (start - span start; end -
    # span end), ns: -679,439 / -2,437,911; -643,319 / -2,268,273;
    # -778,779 / -1,922,290; -613,310 / -2,205,198; -809,541 / -2,123,131;
    # -836,021 / -2,093,685: the device's clock is 0.84 to 1.92 ms behind
    assert got["clock"] == {
        "steps_matched": 6,
        "lower_ns": -1_922_290,
        "upper_ns": -836_021,
        "offset_ns": -1_379_155.5,
        "consistent": True,
    }
    # window 40,863,678 .. 58,021,127 (17,157,449 ns), busy 283,818 ns, all
    # of it inside the runs. Moved by the offset, the first batch span ends
    # before the window and the first step span starts before it (it keeps
    # 1,391,464.5 ns); the other batch spans hold 770,100 + 845,180 +
    # 771,630 + 1,167,980 + 746,410 ns of idle time, and the step spans
    # 1,391,464.5 + 1,691,510 + 1,192,120 + 1,658,640 + 1,362,630 +
    # 1,324,330 less the busy time
    ns = 1e-9
    assert got["window_s"] == pytest.approx(17_157_449 * ns)
    assert got["busy_s"] == pytest.approx(283_818 * ns)
    assert got["idle_s"] == {
        "total": pytest.approx(16_873_631 * ns),
        "batch": pytest.approx(4_301_300 * ns),
        "step": pytest.approx(8_336_876.5 * ns),
        "outside": pytest.approx(4_235_454.5 * ns),
    }
    # the same busy and idle time as the benchmark's own reduction
    s0, o0 = trace.collect(str(DATA / "stepper_trace.xplane.pb"))
    assert trace.reduce(s0, o0, [0])["busy_s"] == pytest.approx(got["busy_s"])
    # every scope of the two jobs is there; the tiny widths leave much of
    # the time to the embedding and its gradient, outside every scope
    assert all(got["under"][s] > 0 for s in spans.SCOPES)
    assert sum(got["by_scope"].values()) == pytest.approx(got["busy_s"])
    assert got["top_unscoped"][0][0] == "%fusion.2 jit(train_step)/transpose(jvp())/scatter-add:"


def test_readers_on_the_recorded_stepper_trace(tmp_path, monkeypatch):
    record = _record(tmp_path, "stepper_trace.xplane.pb", monkeypatch, tokens=(128, 128))
    read = {m: harness.metric_reader(m)(record) for m in NEW}
    # 256 tokens trained in the window
    assert read["ssm.ns_per_token"] == pytest.approx(117_542 / 256)
    assert read["ssd_scan.ns_per_token"] == pytest.approx(82_764 / 256)
    assert read["attention.ns_per_token"] == pytest.approx(47_536 / 256)
    assert read["executor.idle_in_batch_share"] == pytest.approx(100 * 4_301_300 / 17_157_449)
    assert read["executor.idle_in_step_share"] == pytest.approx(100 * 8_336_876.5 / 17_157_449)
