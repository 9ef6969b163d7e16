"""Shared helpers for the benchmark suite."""

from __future__ import annotations

import hashlib
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

ARTIFACTS = os.path.join(os.path.dirname(__file__), "artifacts")
REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

# bump when the shape of the BENCH_*.json payloads changes incompatibly
#   v2: run context (n_jobs / fleet / queue_window / ...) lives only in
#       ``meta`` — v1 duplicated it at the top level of the payload; read
#       it through ``bench_context`` to stay compatible with both
BENCH_SCHEMA_VERSION = 2

# the runner (benchmarks/run.py) exports a single wall-clock timestamp so
# every BENCH file of one sweep carries the same stamp; direct module
# invocation leaves it unset and the artifacts stay fully deterministic
TIMESTAMP_ENV = "REPRO_BENCH_TIMESTAMP"


def artifact_path(*parts: str) -> str:
    path = os.path.join(ARTIFACTS, *parts)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    return path


def save_json(name: str, payload: Any) -> str:
    path = artifact_path(name)
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=str)
    return path


def timed_us(fn: Callable, *args, iters: int = 5, warmup: int = 2) -> float:
    for _ in range(warmup):
        fn(*args)
    t0 = time.perf_counter()
    for _ in range(iters):
        fn(*args)
    return (time.perf_counter() - t0) / iters * 1e6


def pct(new: float, ref: float) -> float:
    return (new / ref - 1.0) * 100.0


def trace_signature(trace: Sequence[Tuple[Any, float, float]]) -> str:
    """Deterministic content signature of a generated trace: sha256 over
    every job's (family, reference width, arrival, deadline), truncated to
    16 hex chars.  Two BENCH files with equal signatures replayed exactly
    the same workload, whatever config produced it."""
    h = hashlib.sha256()
    for profile, arrival, deadline in trace:
        h.update(
            f"{profile.name}|{profile.n_gpus}|{arrival!r}|{deadline!r}\n".encode()
        )
    return h.hexdigest()[:16]


def bench_meta(
    trace: Optional[Sequence[Tuple[Any, float, float]]] = None,
    fleet: Optional[Dict[str, Any]] = None,
    **extra: Any,
) -> Dict[str, Any]:
    """The shared metadata block every BENCH_*.json carries: schema
    version, trace signature + job count, fleet shape, and the sweep
    timestamp when the runner exported one (absent on direct invocation,
    keeping artifacts deterministic)."""
    meta: Dict[str, Any] = {"schema_version": BENCH_SCHEMA_VERSION}
    if trace is not None:
        meta["trace_signature"] = trace_signature(trace)
        meta["n_jobs"] = len(trace)
    if fleet is not None:
        meta["fleet"] = fleet
    ts = os.environ.get(TIMESTAMP_ENV)
    if ts:
        meta["timestamp"] = ts
    meta.update(extra)
    return meta


def bench_context(bench: Dict[str, Any], key: str, default: Any = None) -> Any:
    """Read a run-context field (``n_jobs``, ``fleet``, ``queue_window``,
    ...) from a BENCH payload, wherever its schema version put it: ``meta``
    first (v2 emits context only there), then the payload top level (v1
    duplicated it).  Lets the regression gate compare v1 baselines against
    v2 artifacts."""
    meta = bench.get("meta")
    if isinstance(meta, dict) and key in meta:
        return meta[key]
    if key in bench:
        return bench[key]
    # v1 scale/dvfs also nested n_jobs under the trace block
    trace = bench.get("trace")
    if isinstance(trace, dict) and key in trace:
        return trace[key]
    return default


def write_bench(name: str, payload: Dict[str, Any], meta: Dict[str, Any]) -> str:
    """Write the repo-root ``BENCH_<name>.json`` trajectory file with the
    shared ``meta`` block stamped in; returns the path."""
    out = {"meta": meta, **payload}
    path = os.path.join(REPO_ROOT, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1, default=str)
        f.write("\n")
    return path


# metric keys the --check regression gate compares (higher = worse)
_REGRESSION_KEYS = ("total_energy_kwh", "energy_kwh", "avg_jct_h", "avg_jtt_h")


def _walk_metrics(payload: Any, path: str = "") -> Dict[str, float]:
    out: Dict[str, float] = {}
    if isinstance(payload, dict):
        for k, v in payload.items():
            p = f"{path}.{k}" if path else str(k)
            if k in _REGRESSION_KEYS and isinstance(v, (int, float)):
                out[p] = float(v)
            else:
                out.update(_walk_metrics(v, p))
    return out


def check_regression(
    baseline: Dict[str, Any], current: Dict[str, Any], tolerance: float = 0.10
) -> List[str]:
    """Compare two BENCH payloads; returns human-readable failures for
    every energy/JCT metric that regressed (grew) by more than
    ``tolerance`` relative to the committed baseline.  Metrics present in
    only one payload are ignored — adding a scheduler or cap level must
    not fail the gate."""
    old = _walk_metrics(baseline)
    new = _walk_metrics(current)
    failures = []
    for key in sorted(old.keys() & new.keys()):
        ref = old[key]
        if ref <= 0:
            continue
        ratio = new[key] / ref
        if ratio > 1.0 + tolerance:
            failures.append(
                f"{key}: {new[key]:.4g} vs baseline {ref:.4g} "
                f"({(ratio - 1) * 100:+.1f}% > +{tolerance * 100:.0f}%)"
            )
    return failures


class Row:
    """CSV row in the repo's ``name,us_per_call,derived`` convention."""

    def __init__(self, name: str, us_per_call: float, derived: str):
        self.name, self.us, self.derived = name, us_per_call, derived

    def __str__(self) -> str:
        return f"{self.name},{self.us:.2f},{self.derived}"
