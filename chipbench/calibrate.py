"""Readings that the limits of ``correct`` are set from, for one cell, in
one process (the step programs compile once for all seeds).

  python3 chipbench/calibrate.py --workload <name> --seeds 11,12,... \
      --control-seeds 3 --out chiprun_out/calibrate.<name>.jsonl

For every seed the program takes its first steps exactly as in a run and
is compared with the float32 reference.  For the first ``--control-seeds``
seeds two more readings are taken against the same reference: the control
(the reference with every matrix product rounded to float8) and the fault
of half of each batch left out.  A step that returns its state unchanged
reads 1 on ``update`` by construction and is not run.  Each seed writes one
JSON line; the last line of standard output sums them up: per job and
number, the largest program reading and the smallest control and fault
readings, and the limits those readings give (``limits``); with
``--write-limits`` they become the cell's ``limits/<name>.json``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
from chipbench import tpu_devices, use_compile_cache  # noqa: E402

use_compile_cache()


def calibrate(cell, seeds, control_seeds, devices, out, log):
    from chipbench import harness
    from chipbench.reference import train as R
    from repro.colocation.stepper import TemporalStepper

    program = harness.Program(cell, devices)
    summary = {}

    def note(job, kind, gaps):
        for k, v in gaps.items():
            s = summary.setdefault(job, {}).setdefault(k, {})
            s[kind] = max(s.get(kind, v), v) if kind == "program" else min(s.get(kind, v), v)

    for n, seed in enumerate(seeds):
        t0 = time.perf_counter()
        running = program.start(seed)
        program.first_steps(TemporalStepper([r.job for r in running]), running)
        harness.free(running)
        line = {"seed": seed}
        for r in running:
            cfg = r.spec.cfg
            full = harness.reference_batches(r.spec, cell.traffic, seed)
            ref = R.readings(cfg, r.key, full, "float32", devices)
            got = {"program": R.gaps(r.readings, ref), "losses": r.readings["losses"],
                   "reference_losses": ref["losses"]}
            note(r.spec.name, "program", got["program"])
            if n < control_seeds:
                ctl = R.readings(cfg, r.key, full, "fp8", devices)
                half = harness.reference_batches(r.spec, cell.traffic, seed, half=True)
                flt = R.readings(cfg, r.key, half, "float32", devices)
                got["control"] = R.gaps(ctl, ref)
                got["half_batch"] = R.gaps(flt, ref)
                note(r.spec.name, "control", got["control"])
                note(r.spec.name, "half_batch", got["half_batch"])
            line[r.spec.name] = got
        line["seconds"] = time.perf_counter() - t0
        out.write(json.dumps(line) + "\n")
        out.flush()
        log(json.dumps(line))
    return summary


# a reading sets the upper end of a number's limit where it is this many
# times the number's lower reading (the largest of the program's) or more
UPPER_FACTOR = {"control": 3.0, "half_batch": 10.0, "unchanged": 3.0}
# what a step that returns its state unchanged reads: no first moment, so
# every leaf's first gradient reads 0, and no change
UNCHANGED = {"grad": 1.0, "update": 1.0}


def limits(summary: dict) -> dict:
    """Each number's limit between its lower reading L and the smallest
    reading U that sets an upper end: L^(1/3) U^(2/3), so that it lies
    nearer U than L by ratio, with two significant digits.  A number with
    no U is left out, and so not compared."""
    out = {}
    for job, numbers in summary.items():
        for k, s in numbers.items():
            readings = dict(s, unchanged=UNCHANGED.get(k))
            lower = s["program"]
            uppers = [
                v for kind, v in readings.items()
                if kind in UPPER_FACTOR and v is not None and v >= UPPER_FACTOR[kind] * lower
            ]
            if uppers:
                limit = lower ** (1 / 3) * min(uppers) ** (2 / 3)
                out.setdefault(job, {})[k] = float(f"{limit:.2g}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", required=True)
    ap.add_argument("--write-limits", action="store_true")
    args = ap.parse_args()

    devices = tpu_devices()
    from chipbench import harness

    cell = harness.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "w") as out:
        summary = calibrate(
            cell, seeds, args.control_seeds, devices[: cell.chips], out,
            lambda m: print(m, file=sys.stderr, flush=True),
        )
    proposed = limits(summary)
    if args.write_limits:
        (ROOT / "chipbench" / "limits" / f"{cell.name}.json").write_text(json.dumps(proposed, indent=2) + "\n")
    print(json.dumps({"workload": cell.name, "seeds": len(seeds), "summary": summary, "limits": proposed}))


if __name__ == "__main__":
    main()
