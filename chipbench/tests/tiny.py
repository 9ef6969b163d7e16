"""Cells at smoke widths, built from the real configuration and traffic
files with every size cut down, for CPU tests.  A configuration's smoke
sizes are ``tiny_sizes/<name>.json``, laid over its file."""

from __future__ import annotations

import json
from pathlib import Path

from chipbench import harness

ROOT = Path(__file__).resolve().parents[2]


def config(name: str, root: Path = ROOT) -> dict:
    """Configuration ``name`` of the benchmark under ``root``, at its smoke
    sizes."""
    bench = root / "chipbench"
    cfg = json.loads((bench / "configs" / f"{name}.json").read_text())
    cfg.update(json.loads((bench / "tests" / "tiny_sizes" / f"{name}.json").read_text()))
    return cfg


def cell(jobs=None, seq=64, limits=None, mesh=None, chips=1, traffic="solo-b4x2048", root: Path = ROOT) -> harness.Cell:
    """A cell of ``jobs`` [(config name, batch)] (default: the mix's own
    jobs and batches) at sequence ``seq``, under the rest of mix
    ``traffic``; ``mesh``, where given, in place of the mix's."""
    mix = json.loads((ROOT / "chipbench" / "traffic" / f"{traffic}.json").read_text())
    if jobs is None:
        jobs = [(j["config"], j["batch"]) for j in mix["jobs"]]
    specs = [
        harness.JobSpec(name=n, cfg=config(n, root), batch=b, seq=seq, index=i)
        for i, (n, b) in enumerate(jobs)
    ]
    if mesh is not None:
        mix["mesh"] = mesh
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    return harness.Cell(
        name="tiny",
        chips=chips,
        jobs=specs,
        traffic=mix,
        end_to_end=spec["end_to_end"],
        per_layer=spec["per_layer"],
        limits=limits or {},
    )
