"""The reduction by the program's own names (``spans.py``) on hand-made
intervals, on the benchmark's earlier chip trace (which has none of them),
and on a trace of the co-location stepper recorded on a TPU v5e by
``record_stepper_trace.py`` (``data/stepper_trace.xplane.pb``)."""

import shutil
from pathlib import Path

import pytest

from chipbench import arch, harness, spans, trace
from chipbench.spans import Op, Span, Trace
from chipbench.tests import tiny

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
    assert spans.scopes_of(op_name, arch.scopes()) == want


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


def test_steps_pair_only_with_runs_of_the_step_program():
    # the device also runs a program with no scoped operation (0.5..0.6 ms
    # after each step's run): it is left out, and the skew is found as
    # without it
    tr = _skewed(1 * MS)
    other = [(e + MS // 2, e + MS * 6 // 10) for _, e in tr.modules[0]]
    tr.modules[0] = sorted(tr.modules[0] + other)
    tr.ops[0] += [_op("copy", s, e) for s, e in other]
    assert spans.step_runs(tr.modules[0], tr.ops[0]) == sorted(set(tr.modules[0]) - set(other))
    got = spans.reduce(tr, [0])["clock"]
    assert got["steps_matched"] == 3 and got["offset_ns"] == pytest.approx(1.04 * MS)


def test_idle_by_span_reads_nothing_where_no_step_pairs(monkeypatch):
    # one step span and two runs of the step's program: the spans cannot be
    # put on the device's clock, so the split by span is not read
    tr = _skewed(1 * MS)
    tr.spans = [sp for sp in tr.spans if sp.step != 1]
    reduced = spans.reduce(tr, [0])
    assert reduced["has_spans"] and reduced["clock"]["steps_matched"] == 0
    monkeypatch.setattr(spans, "from_record", lambda r: reduced)
    for part in ("batch", "step"):
        assert spans.idle_share({"trace": {}}, part) is None


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
    # every scope of the two jobs' architectures is there; the tiny widths
    # leave much of the time to the embedding and its gradient, outside
    # every scope
    jobs = {sp.job for sp in program}
    assert all(got["under"][s] > 0 for job in jobs for s in arch.of(tiny.config(job)).SCOPES)
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


@pytest.mark.parametrize(
    "op_name,want",
    [
        ("%all-gather-start.3 = (bf16[2560,6912]{1,0}, bf16[5120,6912]{1,0}) all-gather-start(bf16[2560,6912]{1,0} %p.1)", True),
        ("%all-gather-done.3 = bf16[5120,6912]{1,0} all-gather-done((bf16[2560,6912]{1,0}, bf16[5120,6912]{1,0}) %all-gather-start.3)", True),
        ("%all-reduce.7 = f32[] all-reduce(f32[] %x), replica_groups={{0,1,2,3}}", True),
        ("all-reduce-done.2", True),
        ("%reduce-scatter.1 = f32[1280,6912]{1,0} reduce-scatter(f32[2560,6912]{1,0} %g)", True),
        ("%all-to-all.4 = bf16[4,64]{1,0} all-to-all(bf16[4,64]{1,0} %t)", True),
        ("%collective-permute-done.9 = bf16[8]{0} collective-permute-done(bf16[8]{0} %c)", True),
        ("%all-gather-fusion.2 = bf16[5120,80]{1,0} fusion(bf16[2560,80]{1,0} %w), kind=kOutput", True),
        ("%fusion.12 = bf16[4096,2560]{1,0} fusion(bf16[4096,6912]{1,0} %all-gather-done.3), kind=kOutput", False),
        ("%copy-start.62 = (s32[2,64]{1,0}, s32[2,64]{1,0}, u32[]) copy-start(s32[2,64]{1,0} %t)", False),
        ("%reduce-window.1 = f32[8,256]{1,0} reduce-window(f32[8,256]{1,0} %a, f32[] %z)", False),
        ("%reduce.3 = f32[] reduce(f32[8]{0} %a, f32[] %z), to_apply=%add", False),
        ("%scatter-add_fusion.1 = bf16[32000,2560]{1,0} fusion(%g), kind=kLoop", False),
    ],
)
def test_collective_operations_by_name(op_name, want):
    # by the instruction's own name: an operand that is a collective's
    # result does not make a fusion one
    assert spans.is_collective(op_name) is want


def test_collective_self_time_by_hand():
    # a layer loop 0..100 holding an all-gather started 5..8 and waited on
    # 20..30, a product 30..60 under mlp, and a reduce-scatter fusion 60..75
    # under mlp; after the loop an all-reduce 100..110 inside the optimizer
    ops = [
        _op("%while.1 = while()", 0, 100),
        _op("%all-gather-start.1 = all-gather-start()", 5, 8, "mlp"),
        _op("%all-gather-done.1 = all-gather-done()", 20, 30, "mlp"),
        _op("%fusion.4 = fusion(%all-gather-done.1)", 30, 60, "mlp"),
        _op("%reduce-scatter-fusion.1 = fusion()", 60, 75, "mlp"),
        _op("%all-reduce.2 = all-reduce()", 100, 110, "optimizer"),
    ]
    tr = Trace(spans=_window(0, 120), ops={0: ops, 1: ops[:-1]}, modules={})
    got = spans.reduce(tr, [0, 1])
    ns = 1e-9
    # device 0: 3 + 10 + 15 + 10 = 38; device 1: 28; averaged over the two
    assert got["collectives_s"] == pytest.approx(33 * ns)
    assert got["top_collectives"] == [
        ["%reduce-scatter-fusion.1", pytest.approx(15 * ns)],
        ["%all-gather-done.1", pytest.approx(10 * ns)],
        ["%all-reduce.2", pytest.approx(5 * ns)],
        ["%all-gather-start.1", pytest.approx(3 * ns)],
    ]
    # the collectives also count under the scopes that hold them
    assert got["under"]["mlp"] == pytest.approx(58 * ns)
    assert got["under"]["optimizer"] == pytest.approx(5 * ns)


def test_collectives_reader(tmp_path, monkeypatch):
    # recorded on one chip: no collective, so nothing to read
    record = _record(tmp_path, "stepper_trace.xplane.pb", monkeypatch, tokens=(128, 128))
    assert harness.metric_reader("collectives.ns_per_token")(record) is None
    # on four devices, 38 ns of collectives on each over 4 + 4 tokens
    ops = [_op("%all-gather-done.1 = all-gather-done()", 10, 48, "attention"), _op("%fusion.1 = fusion()", 48, 90)]
    reduced = spans.reduce(Trace(_window(0, 100), {d: ops for d in range(4)}, {}), [0, 1, 2, 3])
    monkeypatch.setattr(spans, "from_record", lambda r: reduced)
    record = {"trace": {"busy_s": 1.0, "window_s": 1.0}, "chips": 4, "jobs": [{"steps": 2, "tokens_per_step": 4}]}
    assert harness.metric_reader("collectives.ns_per_token")(record) == pytest.approx(38 / 8)
