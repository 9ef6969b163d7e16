"""Run one cell of the benchmark once.

  python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Refuses to run unless JAX's default device is a TPU and the cell's chips are
present.  Set-up makes each job's weights from the seed, compiles (or loads
from the compile cache in ``<checkout>/.jax_cache``) and takes the first
steps; then the window runs for ``--seconds``; then the first steps are
checked against the float32 reference.  The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
end-to-end metrics, or with ``--trace 1`` the per-layer ones), ``device``
and, last, ``compared``: each number checked beside its limit, which are
also the last lines of standard error.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".chipbench_trace"
sys.path.insert(0, str(ROOT))
from chipbench import tpu_devices, use_compile_cache  # noqa: E402

use_compile_cache()


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    devices = tpu_devices()
    from chipbench import harness

    cell = harness.load_cell(args.workload)
    if len(devices) < cell.chips:
        sys.exit(f"chipbench: {cell.name} needs {cell.chips} chips, {len(devices)} present")
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    try:
        result = harness.run_cell(
            cell,
            args.seed,
            args.seconds,
            bool(args.trace),
            devices[: cell.chips],
            T_START,
            trace_dir=TRACE_DIR,
            log=log,
        )
    finally:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    for name, c in result["compared"].items():
        log(f"compared {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
