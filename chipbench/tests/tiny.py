"""Cells at smoke widths, built from the real configuration and traffic
files with every size cut down, for CPU tests."""

from __future__ import annotations

import copy
import json
from pathlib import Path

from chipbench import harness

BENCH = Path(__file__).resolve().parents[1]

TINY = {
    "h2o-danube-1.8b-l4": {
        "hidden_size": 64,
        "intermediate_size": 128,
        "num_attention_heads": 4,
        "num_key_value_heads": 2,
        "head_dim": 16,
        "num_hidden_layers": 2,
        "vocab_size": 503,
        "sliding_window": 32,
        "program": {
            "registry": "h2o-danube-1.8b",
            "replace": {
                "num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
                "head_dim": 16, "d_ff": 128, "vocab_size": 503, "sliding_window": 32,
            },
        },
    },
    "mamba2-370m": {
        "d_model": 64,
        "n_layer": 2,
        "vocab_size": 503,
        "ssm_cfg": {"d_state": 16, "d_conv": 4, "expand": 2, "headdim": 16, "ngroups": 1, "chunk_size": 32},
        "program": {
            "registry": "mamba2-370m",
            "replace": {"num_layers": 2, "d_model": 64, "vocab_size": 503,
                        "ssm": {"d_state": 16, "head_dim": 16, "chunk": 32}},
        },
    },
}


def config(name: str) -> dict:
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    cfg.update(copy.deepcopy(TINY[name]))
    return cfg


def cell(jobs, seq=64, limits=None, mesh=None, chips=1) -> harness.Cell:
    """A cell of ``jobs`` [(config name, batch)] at sequence ``seq``."""
    specs = [
        harness.JobSpec(name=n, cfg=config(n), batch=b, seq=seq, index=i)
        for i, (n, b) in enumerate(jobs)
    ]
    traffic = json.loads((BENCH / "traffic" / "solo-b4x2048.json").read_text())
    traffic["mesh"] = mesh
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    return harness.Cell(
        name="tiny",
        chips=chips,
        jobs=specs,
        traffic=traffic,
        end_to_end=spec["end_to_end"],
        per_layer=spec["per_layer"],
        limits=limits or {},
    )
