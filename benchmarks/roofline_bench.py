"""Roofline table from the dry-run artifacts (§Roofline deliverable).

Reads benchmarks/artifacts/dryrun/*.json (produced by
``repro.launch.dryrun``) and prints, per (arch x shape x mesh):
the three roofline terms, the dominant bottleneck, MODEL_FLOPS /
HLO_FLOPs, and per-device memory vs HBM.
"""

from __future__ import annotations

import time
from typing import List

from benchmarks.common import Row, load_dryrun_records, save_json
from repro.configs import SHAPES, get_config
from repro.roofline import hw
from repro.roofline.analysis import analytic_hbm_bytes


def run() -> List[Row]:
    rows: List[Row] = []
    t0 = time.perf_counter()
    records = load_dryrun_records()
    table = []
    for r in records:
        tag = f"+{r['tag']}" if r.get("tag") else ""  # §Perf variants
        cell = f"{r['arch']}/{r['shape']}/{r['mesh']}{tag}"
        if r["status"] == "skipped":
            table.append({"cell": cell, "status": "skipped", "reason": r["reason"]})
            continue
        if r["status"] == "error":
            table.append({"cell": cell, "status": "error", "error": r.get("error", "?")})
            continue
        m = r["memory"]
        entry = {
            "cell": cell,
            "status": "ok",
            "mem_gib": round(m["per_device_bytes"] / 2**30, 2),
            "fits_hbm": m["fits_hbm"],
        }
        if "roofline" in r:
            rf = r["roofline"]
            cfg = get_config(r["arch"])
            shape = SHAPES[r["shape"]]
            chips = 512 if r["mesh"] == "multi" else 256
            mem_an = analytic_hbm_bytes(
                cfg, shape, chips, m.get("microbatches", 8)
            ) / hw.HBM_BW
            terms = {
                "compute": rf["compute_s"],
                "memory": mem_an,
                "collective": rf["collective_s"],
            }
            bottleneck = max(terms, key=terms.get)
            entry.update(
                compute_s=rf["compute_s"],
                memory_s_hlo=rf["memory_s"],  # mandated cost_analysis bytes
                memory_s=mem_an,  # fusion-aware analytic estimate
                collective_s=rf["collective_s"],
                bottleneck=bottleneck,
                useful_ratio=round(rf["useful_ratio"], 3),
                collective_counts=rf["collective_counts"],
                roofline_frac=round(
                    max(rf["model_flops_per_device"] / hw.MODELLED_PEAK_FLOPS_BF16, 1e-12)
                    / max(max(terms.values()), 1e-12),
                    4,
                ),
            )
        table.append(entry)
    save_json("roofline_table.json", table)
    us = (time.perf_counter() - t0) * 1e6 / max(len(records), 1)
    for e in table:
        if e["status"] != "ok" or "bottleneck" not in e:
            continue
        rows.append(
            Row(
                f"roofline/{e['cell']}",
                us,
                f"c={e['compute_s']*1e3:.1f}ms m={e['memory_s']*1e3:.1f}ms "
                f"x={e['collective_s']*1e3:.1f}ms (hlo_m={e['memory_s_hlo']*1e3:.0f}ms) "
                f"{e['bottleneck']}-bound roofline_frac={e['roofline_frac']} "
                f"useful={e['useful_ratio']} mem={e['mem_gib']}GiB fits={e['fits_hbm']}",
            )
        )
    n_ok = sum(1 for e in table if e["status"] == "ok")
    n_skip = sum(1 for e in table if e["status"] == "skipped")
    n_err = sum(1 for e in table if e["status"] == "error")
    rows.append(
        Row("roofline/summary", us, f"cells ok={n_ok} skipped={n_skip} error={n_err}")
    )
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
