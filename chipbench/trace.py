"""Reduce a profiler trace of the measured window to busy and idle time,
the device operations that took the most time, and the longest idle gaps,
each gap labelled with the benchmark's own host span that encloses it.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Device
operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane; the benchmark's spans are host events whose
names start with ``chipbench.``; the window is the ``chipbench.window``
span.
"""

from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
TOP = 10

Interval = Tuple[float, float]  # start, end in ns


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def collect(path: str):
    """(spans, ops): the benchmark's host spans as [(name, start, end)] and
    each device's operations as {index: [(name, start, end)]}, in ns."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans, ops = [], {}
    seen = []
    for plane in pd.planes:
        seen.append(f"{plane.name}: {[line.name for line in plane.lines][:12]}")
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[int(m.group(1))] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    if not ops:
        raise ValueError(f"no {OPS_LINE!r} line on a TPU plane in {path}; planes: {seen}")
    return spans, ops


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _clip(s: float, e: float, lo: float, hi: float):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def label(spans, t: float) -> str:
    """The innermost benchmark span that holds time ``t``."""
    inside = [(e - s, n) for n, s, e in spans if s <= t <= e]
    return min(inside)[1] if inside else "outside the benchmark's spans"


def reduce(spans, ops: Dict[int, list], devices: Sequence[int]) -> dict:
    """Busy and idle time of ``devices`` within the window span.

    ``busy_s`` is the union of each device's operation intervals inside the
    window, averaged over the devices.  ``device_ops`` sums each operation
    name's time inside the window over the devices, divided by their number.
    ``idle_gaps`` are the first device's longest gaps between operations
    inside the window, each named by the innermost benchmark span at its
    middle; ``idle_by_span`` adds up all of that device's idle time so.
    """
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(windows)}")
    lo, hi = windows[0]
    missing = [d for d in devices if not ops.get(d)]
    if missing:
        raise ValueError(f"no device operations traced on device(s) {missing}")
    busy, per_op = [], collections.Counter()
    for d in devices:
        clipped = []
        for name, s, e in ops[d]:
            c = _clip(s, e, lo, hi)
            if c:
                clipped.append(c)
                per_op[name] += (c[1] - c[0]) / len(devices)
        busy.append(union(clipped))
    gaps, t = [], lo
    for s, e in busy[0] + [(hi, hi)]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    named = [(label(spans, (s + e) / 2), (e - s) * 1e-9) for s, e in gaps]
    by_span = collections.Counter()
    for name, sec in named:
        by_span[name] += sec
    n = len(devices)
    return {
        "busy_s": sum(e - s for b in busy for s, e in b) * 1e-9 / n,
        "window_s": (hi - lo) * 1e-9,
        "device_ops": [[k, v * 1e-9] for k, v in per_op.most_common(TOP)],
        "idle_gaps": sorted(named, key=lambda g: -g[1])[:TOP],
        "idle_by_span": dict(by_span),
    }


def reduce_dir(log_dir: str, devices: Sequence[int]) -> dict:
    spans, ops = collect(find_xplane(log_dir))
    return reduce(spans, ops, devices)
