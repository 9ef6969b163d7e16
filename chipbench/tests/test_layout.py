"""BENCHMARK.json against the shape the harness relies on: every name it
gives has its file, and every cell loads."""

import json
import re
from pathlib import Path

import pytest

from chipbench import harness

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_names_units_and_files():
    assert SPEC["command"] == ["python3", "chipbench/run.py"] and SPEC["paths"] == ["chipbench"]
    configs = {c["name"] for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert cfg["source"] == c["source"]
    used = {w["config"] for w in SPEC["workloads"]}
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert (ROOT / "chipbench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert (ROOT / "chipbench" / "limits" / f"{w['name']}.json").is_file()
        traffic = json.loads((ROOT / "chipbench" / "traffic" / f"{w['traffic']}.json").read_text())
        assert traffic["jobs"][0]["config"] == w["config"]
        used |= {j["config"] for j in traffic["jobs"]}
    assert used == configs
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").is_file()


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_loads_and_has_limits(cell):
    c = harness.load_cell(cell)
    for job in c.jobs:
        assert c.limits[job.name] and set(c.limits[job.name]) <= {"loss", "grad", "update"}
    assert {m["name"] for m in c.end_to_end} == {"tokens_per_s", "peak_hbm_bytes", "setup_s"}
