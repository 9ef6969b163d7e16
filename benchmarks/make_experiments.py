"""Render EXPERIMENTS.md sections from the benchmark/dry-run artifacts.

Usage:  PYTHONPATH=src:. python benchmarks/make_experiments.py > EXPERIMENTS.tables.md
The tables are pasted/refreshed into EXPERIMENTS.md §Dry-run / §Roofline.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List

from benchmarks.common import ARTIFACTS, load_dryrun_records
from repro.configs import ASSIGNED, SHAPES, get_config
from repro.roofline import hw
from repro.roofline.analysis import analytic_hbm_bytes

SHAPE_ORDER = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


def fmt_bytes(b: float) -> str:
    return f"{b/2**30:.2f}"


def dryrun_table(mesh: str) -> List[str]:
    recs = {
        (r["arch"], r["shape"]): r
        for r in load_dryrun_records()
        if r["mesh"] == mesh and not r.get("tag")
    }
    out = [
        f"| arch | shape | status | mem/dev GiB | fits 16G | compile s | collectives |",
        f"|---|---|---|---|---|---|---|",
    ]
    for arch in ASSIGNED:
        for shape in SHAPE_ORDER:
            r = recs.get((arch, shape))
            if r is None:
                out.append(f"| {arch} | {shape} | (pending) | | | | |")
                continue
            if r["status"] == "skipped":
                out.append(f"| {arch} | {shape} | SKIP (full attention) | — | — | — | — |")
                continue
            if r["status"] == "error":
                out.append(f"| {arch} | {shape} | ERROR {r['error'][:40]} | | | | |")
                continue
            m = r["memory"]
            colls = ""
            if "roofline" in r:
                cc = r["roofline"]["collective_counts"]
                colls = " ".join(f"{k.split('-')[-1][:4]}:{v}" for k, v in sorted(cc.items()))
            out.append(
                f"| {arch} | {shape} | ok | {fmt_bytes(m['per_device_bytes'])} | "
                f"{'yes' if m['fits_hbm'] else 'NO'} | {m['compile_s']} | {colls} |"
            )
    return out


def roofline_table() -> List[str]:
    recs = {
        (r["arch"], r["shape"]): r
        for r in load_dryrun_records()
        if r["mesh"] == "single" and not r.get("tag")
    }
    out = [
        "| arch | shape | compute s | memory s (analytic) | memory s (HLO) | "
        "collective s | bottleneck | MODEL/HLO flops | roofline frac |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for arch in ASSIGNED:
        cfg = get_config(arch)
        for shape_name in SHAPE_ORDER:
            r = recs.get((arch, shape_name))
            if r is None or r["status"] != "ok":
                continue
            shape = SHAPES[shape_name]
            mem_an = analytic_hbm_bytes(cfg, shape, 256, r["memory"].get("microbatches", 8)) / hw.HBM_BW
            if "roofline" in r:
                rf = r["roofline"]
                terms = {"compute": rf["compute_s"], "memory": mem_an, "collective": rf["collective_s"]}
                bn = max(terms, key=terms.get)
                ideal = rf["model_flops_per_device"] / hw.MODELLED_PEAK_FLOPS_BF16
                frac = ideal / max(max(terms.values()), 1e-12)
                out.append(
                    f"| {arch} | {shape_name} | {rf['compute_s']:.3f} | {mem_an:.3f} | "
                    f"{rf['memory_s']:.1f} | {rf['collective_s']:.3f} | {bn} | "
                    f"{rf['useful_ratio']:.2f} | {frac:.3f} |"
                )
            else:
                # analytic-only cells (SSD prefill policy)
                from repro.roofline.analysis import model_flops_for_cell

                mf = model_flops_for_cell(cfg, shape) / 256
                comp = mf / hw.MODELLED_PEAK_FLOPS_BF16 / 0.4  # assume useful ratio ~0.4
                out.append(
                    f"| {arch} | {shape_name} | ~{comp:.3f}* | {mem_an:.3f} | n/a | n/a | "
                    f"{'memory' if mem_an > comp else 'compute'}* | n/a | "
                    f"{(mf/hw.MODELLED_PEAK_FLOPS_BF16)/max(mem_an, comp):.3f}* |"
                )
    out.append("")
    out.append("`*` analytic-only cells (unrolled SSD-prefill HLO impractical to compile on the CPU container; see dryrun policy note).")
    return out


def perf_table() -> List[str]:
    recs = [r for r in load_dryrun_records() if r.get("tag")]
    out = [
        "| cell | variant | mem/dev GiB | fits | compute s | collective s | notes |",
        "|---|---|---|---|---|---|---|",
    ]
    for r in recs:
        if r["status"] != "ok":
            out.append(f"| {r['arch']}/{r['shape']} | {r['tag']} | ERROR | | | | {r.get('error','')[:60]} |")
            continue
        m = r["memory"]
        rf = r.get("roofline", {})
        out.append(
            f"| {r['arch']}/{r['shape']} | {r['tag']} | {fmt_bytes(m['per_device_bytes'])} | "
            f"{'yes' if m['fits_hbm'] else 'NO'} | {rf.get('compute_s', float('nan')):.3f} | "
            f"{rf.get('collective_s', float('nan')):.3f} | "
            f"colls={rf.get('collective_counts','')} |"
        )
    return out


def main() -> None:
    print("## Generated tables\n")
    print("### Dry-run (single-pod 16x16 = 256 chips)\n")
    print("\n".join(dryrun_table("single")))
    print("\n### Dry-run (multi-pod 2x16x16 = 512 chips)\n")
    print("\n".join(dryrun_table("multi")))
    print("\n### Roofline (single-pod)\n")
    print("\n".join(roofline_table()))
    print("\n### Perf variants\n")
    print("\n".join(perf_table()))


if __name__ == "__main__":
    main()
