"""Reduce the traced window by the program's own names: the device's self
time under each layer-kind scope, and each idle interval of the device
split by the program's host spans, on the device's clock.

The program names two things in the profiler's trace.  Its train step
carries ``jax.named_scope``s, one for each kind of layer and some inside
those (each architecture's ``SCOPES``, ``chipbench/arch``; this module
reduces by their union, ``arch.scopes()``), which the device's
operations keep in their ``tf_op`` metadata, through scans, remat and the
backward pass.  Its co-location stepper wraps batch
preparation in a ``repro.stepper.batch`` host span and the step call,
through ``block_until_ready``, in ``repro.stepper.step``; each carries the
job's name and the step index.

``trace.py`` reads the same ``.xplane.pb`` for busy and idle time; this
module adds, without changing any of those numbers:

- the host-device clock offset, bounded by the steps: a step's program
  cannot start on the device before the host entered its step span, nor end
  after the host left it;
- each device operation's self time (its duration less what operations
  nested inside it on the same line cover), put down to the scopes on its
  op name, and that of the collective operations (the exchange between
  chips, named by XLA after the collective) apart;
- each idle interval of the window split exactly by its overlap with the
  program's spans, moved onto the device's clock by the offset.

The per-layer readers under ``metrics/`` call :func:`from_record` with the
harness's record of a traced run; it finds the trace where ``run.py`` has
the profiler write it, reduces it once, and logs the reduction as one line
on standard error.  A trace of a program without these names (no
``repro.`` span, no scope) reduces to ``None`` for what it lacks.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import importlib.util
import json
import re
import sys
from pathlib import Path
from typing import Collection, Dict, FrozenSet, List, Optional, Sequence, Tuple

from chipbench import arch, trace

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".chipbench_trace"  # where run.py has the profiler write

UNSCOPED = "unscoped"
PROGRAM_PREFIX = "repro."
BATCH_SPAN = "repro.stepper.batch"
STEP_SPAN = "repro.stepper.step"
MODULES_LINE = "XLA Modules"
TOP = 10
# an operation XLA names after a collective: its synchronous form, the
# -start and -done halves of its asynchronous one, or a fusion around it
COLLECTIVE = re.compile(r"all-gather|reduce-scatter|all-reduce|all-to-all|collective-permute")


def is_collective(op_name: str) -> bool:
    """Whether a device operation (``%name = ...`` or its bare name) is part
    of an exchange between chips."""
    return bool(COLLECTIVE.search(op_name.split(" = ")[0]))

Interval = Tuple[float, float]


@dataclasses.dataclass
class Span:
    name: str
    start: float  # ns, host clock
    end: float
    job: Optional[str] = None
    step: Optional[int] = None


@dataclasses.dataclass
class Op:
    name: str
    start: float  # ns, device clock
    end: float
    scopes: Tuple[str, ...]  # the scopes on its op name, outermost first
    tf_op: str = ""


@dataclasses.dataclass
class Trace:
    spans: List[Span]  # host spans of the benchmark and of the program
    ops: Dict[int, List[Op]]  # each device's XLA Ops line
    modules: Dict[int, List[Interval]]  # each device's XLA Modules line


# ---------------------------------------------------------------------------
# Reading the trace
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=1)
def _xplane_pb2():
    """The XSpace message classes, loaded from TensorFlow's copy of
    ``xplane.proto`` without importing TensorFlow; ``None`` if absent."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or spec.origin is None:
        return None
    path = Path(spec.origin).parent / "tsl" / "profiler" / "protobuf" / "xplane_pb2.py"
    if not path.is_file():
        return None
    mod_spec = importlib.util.spec_from_file_location("chipbench_xplane_pb2", path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module


def scopes_of(op_name: str, known: Collection[str]) -> Tuple[str, ...]:
    """The scopes on an op name, outermost first, with the transforms that
    wrap a scope taken off: ``transpose(jvp(head))`` is ``head``.  A
    ``jit(...)`` component is a function's name, never a scope.  ``known``
    are the scope names (``arch.scopes()``)."""
    op_name = op_name.rsplit(":", 1)[0] if ":" in op_name else op_name
    out = []
    for part in op_name.split("/"):
        while (m := re.fullmatch(r"(\w+)\((.*)\)", part)) and m.group(1) not in ("jit", "pjit"):
            part = m.group(2)
        if part in known:
            out.append(part)
    return tuple(out)


def _stat_value(stat, names):
    kind = stat.WhichOneof("value")
    if kind == "ref_value":
        return names[stat.ref_value]
    return getattr(stat, kind) if kind else None


def collect(path: str) -> Optional[Trace]:
    """The trace's host spans (``chipbench.`` and ``repro.``), each TPU's
    operations with their scopes, and each TPU's program executions, in
    whole ns as ``trace.collect`` reads them.  ``None`` where the trace
    cannot be read without TensorFlow's proto."""
    pb2 = _xplane_pb2()
    if pb2 is None:
        return None
    space = pb2.XSpace()
    space.ParseFromString(Path(path).read_bytes())
    spans, ops, modules = [], {}, {}
    known = arch.scopes()
    for plane in space.planes:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        meta = plane.event_metadata
        kinds = {}  # metadata id -> (name, scopes, tf_op)
        m = trace.DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    out = ops.setdefault(dev, [])
                    for e in line.events:
                        if e.metadata_id not in kinds:
                            md = meta[e.metadata_id]
                            tf_op = next(
                                (_stat_value(s, names) for s in md.stats if names.get(s.metadata_id) == "tf_op"), ""
                            )
                            kinds[e.metadata_id] = (md.name, scopes_of(tf_op, known), tf_op)
                        name, scopes, tf_op = kinds[e.metadata_id]
                        s = line.timestamp_ns + e.offset_ps // 1000
                        out.append(Op(name, s, s + e.duration_ps // 1000, scopes, tf_op))
                elif line.name == MODULES_LINE:
                    modules[dev] = [
                        (line.timestamp_ns + e.offset_ps // 1000, line.timestamp_ns + e.offset_ps // 1000 + e.duration_ps // 1000)
                        for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = meta[e.metadata_id].name
                    if name.startswith((trace.SPAN_PREFIX, PROGRAM_PREFIX)):
                        stats = {names.get(s.metadata_id): _stat_value(s, names) for s in e.stats}
                        s = line.timestamp_ns + e.offset_ps // 1000
                        step = stats.get("step")
                        spans.append(
                            Span(name, s, s + e.duration_ps // 1000, stats.get("job"), None if step is None else int(step))
                        )
    return Trace(spans, ops, modules)


# ---------------------------------------------------------------------------
# Reductions
# ---------------------------------------------------------------------------


def _length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in trace.union(intervals))


def _overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of the intersection of two interval sets."""
    a, b = trace.union(a), trace.union(b)
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def self_times(ops: Sequence[Op], lo: float, hi: float) -> List[Tuple[Op, float]]:
    """Each operation inside [lo, hi] with its self time (ns): its clipped
    duration less the part that operations nested inside it cover."""
    clipped = []
    for op in ops:
        c = trace._clip(op.start, op.end, lo, hi)
        if c:
            clipped.append((c[0], c[1], op))
    clipped.sort(key=lambda x: (x[0], -x[1]))
    out = []
    stack: List[list] = []  # [start, end, op, [children]]

    def close(entry):
        s, e, op, children = entry
        out.append((op, (e - s) - _length(children)))

    for s, e, op in clipped:
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3].append((s, min(e, stack[-1][1])))
        stack.append([s, e, op, []])
    while stack:
        close(stack.pop())
    return out


def step_runs(runs: Sequence[Interval], ops: Sequence[Op]) -> List[Interval]:
    """The program runs on a device that hold an operation under a layer
    scope: the train step's, without the runs of any other program the
    device ran beside it."""
    starts = sorted(op.start for op in ops if op.scopes)
    out = []
    for s, e in runs:
        i = bisect.bisect_left(starts, s)
        if i < len(starts) and starts[i] <= e:
            out.append((s, e))
    return out


def clock_offset(steps: Sequence[Span], runs: Dict[int, List[Interval]]) -> dict:
    """Bounds on the device's clock less the host's (ns).

    Each step call runs one program, so on each device the k-th run of a
    step's program (``step_runs``) belongs to the k-th step span: it cannot start before the host entered
    the span, nor end after the host left it.  ``offset_ns`` is the middle
    of the tightest bounds; it is 0 where a device's runs do not pair off
    with the steps, or where the bounds conflict."""
    out = {"steps_matched": 0, "lower_ns": None, "upper_ns": None, "offset_ns": 0.0, "consistent": None}
    steps = sorted(steps, key=lambda sp: sp.start)
    if not steps or any(len(r) != len(steps) for r in runs.values()):
        return out
    pairs = [(sp, run) for r in runs.values() for sp, run in zip(steps, sorted(r))]
    lower = max(e - sp.end for sp, (_, e) in pairs)
    upper = min(s - sp.start for sp, (s, _) in pairs)
    out.update(steps_matched=len(pairs), lower_ns=lower, upper_ns=upper, consistent=lower <= upper)
    if lower <= upper:
        out["offset_ns"] = (lower + upper) / 2
    return out


def reduce(tr: Trace, devices: Sequence[int]) -> dict:
    """Self time by scope and idle time by program span, each averaged over
    ``devices``, inside the benchmark's window span.

    ``by_scope`` gives each set of scopes on an op name (joined by ``/``,
    ``unscoped`` for none) its self time in seconds, and ``under`` each
    scope the self time of every operation it holds; ``collectives_s`` is
    the self time of the collective operations; ``idle_s`` splits the
    idle time of the window, which is ``trace.reduce``'s window less its
    busy time, into ``batch``, ``step`` and ``outside`` the program's spans.
    """
    windows = [(sp.start, sp.end) for sp in tr.spans if sp.name == trace.WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {trace.WINDOW_SPAN} span, found {len(windows)}")
    lo, hi = windows[0]
    n = len(devices)
    program = [sp for sp in tr.spans if sp.name.startswith(PROGRAM_PREFIX)]
    steps = [sp for sp in program if sp.name == STEP_SPAN]
    clock = clock_offset(steps, {d: step_runs(tr.modules.get(d, []), tr.ops.get(d, [])) for d in devices})
    shift = clock["offset_ns"]
    moved = {
        name: [(sp.start + shift, sp.end + shift) for sp in program if sp.name == name]
        for name in (BATCH_SPAN, STEP_SPAN)
    }

    known = arch.scopes()
    kinds = {s for s, outer in known.items() if outer is None}
    by_scope: Dict[FrozenSet[str], float] = collections.Counter()
    unscoped_ops = collections.Counter()
    collectives = collections.Counter()
    idle = collections.Counter()
    gaps = []
    for d in devices:
        timed = self_times(tr.ops.get(d, []), lo, hi)
        for op, t in timed:
            by_scope[frozenset(op.scopes)] += t / n
            if not op.scopes:
                unscoped_ops[(op.name.split(" = ")[0], op.tf_op)] += t / n
            if is_collective(op.name):
                collectives[op.name.split(" = ")[0]] += t / n
        busy = trace.union([trace._clip(op.start, op.end, lo, hi) for op, _ in timed])
        free, t = [], lo
        for s, e in busy + [(hi, hi)]:
            if s > t:
                free.append((t, s))
            t = max(t, e)
        if d == devices[0]:
            gaps = free
        idle["total"] += _length(free) / n
        idle["batch"] += _overlap(free, moved[BATCH_SPAN]) / n
        idle["step"] += _overlap(free, moved[STEP_SPAN]) / n
    idle["outside"] = idle["total"] - idle["batch"] - idle["step"]
    on_device = [(sp.name, sp.start + shift, sp.end + shift) for sp in tr.spans]
    labelled = sorted(((trace.label(on_device, (s + e) / 2), (e - s) * 1e-9) for s, e in gaps), key=lambda g: -g[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(by_scope.values()) * 1e-9,
        "has_scopes": any(v for k, v in by_scope.items() if k),
        "has_spans": bool(steps),
        "clock": clock,
        "by_scope": {"/".join(sorted(k)) or UNSCOPED: v * 1e-9 for k, v in by_scope.items()},
        "under": {s: sum(v for k, v in by_scope.items() if s in k) * 1e-9 for s in known},
        "kinds_s": sum(v for k, v in by_scope.items() if k & kinds) * 1e-9,
        "collectives_s": sum(collectives.values()) * 1e-9,
        "top_collectives": [[name, v * 1e-9] for name, v in collectives.most_common(TOP)],
        "top_unscoped": [[f"{name} {tf_op}".strip(), v * 1e-9] for (name, tf_op), v in unscoped_ops.most_common(TOP)],
        "idle_s": {k: v * 1e-9 for k, v in idle.items()},
        "idle_gaps": labelled[:TOP],
    }


# ---------------------------------------------------------------------------
# From the harness's record of a traced run
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=2)
def _reduce_file(path: str, mtime_ns: int, devices: Tuple[int, ...]) -> Optional[dict]:
    tr = collect(path)
    if tr is None:
        return None
    out = reduce(tr, devices)
    detail = {k: v for k, v in out.items() if k not in ("has_scopes", "has_spans")}
    print("detail.program: " + json.dumps(detail), file=sys.stderr, flush=True)
    return out


def from_record(record: dict) -> Optional[dict]:
    """The reduction of the trace a traced run left in ``TRACE_DIR``;
    ``None`` for an untraced run or a trace this module cannot read."""
    if not record.get("trace"):
        return None
    try:
        path = trace.find_xplane(str(TRACE_DIR))
    except FileNotFoundError:
        return None
    devices = tuple(range(record["chips"]))
    return _reduce_file(path, Path(path).stat().st_mtime_ns, devices)


def tokens(record: dict) -> int:
    return sum(j["steps"] * j["tokens_per_step"] for j in record["jobs"])


def ns_per_token(record: dict, scope: str) -> Optional[float]:
    """Device self time under ``scope`` in the window per token trained in
    it; ``None`` where the program names no scope."""
    r = from_record(record)
    if r is None or not r["has_scopes"] or not tokens(record):
        return None
    return r["under"][scope] * 1e9 / tokens(record)


def collective_ns_per_token(record: dict) -> Optional[float]:
    """Device self time of the collective operations in the window per
    token trained in it; ``None`` where the trace holds none."""
    r = from_record(record)
    if r is None or not r["top_collectives"] or not tokens(record):
        return None
    return r["collectives_s"] * 1e9 / tokens(record)


def idle_share(record: dict, part: str) -> Optional[float]:
    """Share of the window (%) in which the device idled while the host was
    in the program's ``part`` spans; ``None`` where it has none, or where
    no step pairs with a run of its program, so that the spans cannot be
    put on the device's clock."""
    r = from_record(record)
    if r is None or not r["has_spans"] or not r["clock"]["steps_matched"]:
        return None
    return 100.0 * r["idle_s"][part] / r["window_s"]
