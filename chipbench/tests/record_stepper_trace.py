"""Record the small chip trace of the co-location stepper that
``test_spans.py`` reads.

  python3 chipbench/tests/record_stepper_trace.py <out.xplane.pb>

On a TPU: two tiny jobs (a one-layer GQA transformer and a one-layer
Mamba-2, smoke widths) through ``TemporalStepper`` for three rounds, each
round in a ``chipbench.round`` span, all inside a ``chipbench.window``
span, as the harness runs its window.  The trace keeps the planes and lines
that ``spans.collect`` reads: each TPU's ``XLA Ops`` and ``XLA Modules``
lines and the host's ``chipbench.`` and ``repro.`` spans.  Prints every
program span, program run and idle interval of the window, so that the
test's numbers can be worked out by hand.
"""

import dataclasses
import sys
import tempfile
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))
from chipbench import spans, trace  # noqa: E402
from repro.colocation.stepper import ColocatedJob, TemporalStepper  # noqa: E402
from repro.configs import get_config, smoke_config  # noqa: E402
from repro.data.pipeline import DataConfig, SyntheticPipeline  # noqa: E402
from repro.train.steps import make_train_bundle  # noqa: E402

JOBS = ("h2o-danube-1.8b", "mamba2-370m")
KEEP_LINES = (trace.OPS_LINE, spans.MODULES_LINE)


def jobs():
    out = []
    for i, name in enumerate(JOBS):
        cfg = dataclasses.replace(smoke_config(get_config(name)), num_layers=1)
        bundle = make_train_bundle(cfg, None, q_chunk=32)
        pipe = SyntheticPipeline(DataConfig(cfg.vocab_size, 64, 2, seed=i))
        out.append(ColocatedJob(name, bundle, pipe, steps_per_epoch=100, target_epochs=1))
    return out


def trim(src: str, dst: str) -> None:
    """Keep only what ``spans.collect`` and ``trace.collect`` read: the TPU
    planes' two lines with each operation's ``tf_op``, and the host's spans."""
    pb2 = spans._xplane_pb2()
    space = pb2.XSpace()
    space.ParseFromString(Path(src).read_bytes())
    planes = []
    for plane in space.planes:
        device = trace.DEVICE_PLANE.match(plane.name)
        if not device and not plane.name.startswith("/host:"):
            continue
        keep = []
        for line in plane.lines:
            if device:
                wanted = line.name in KEEP_LINES
            else:
                events = [
                    e
                    for e in line.events
                    if plane.event_metadata[e.metadata_id].name.startswith((trace.SPAN_PREFIX, spans.PROGRAM_PREFIX))
                ]
                del line.events[:]
                line.events.extend(events)
                wanted = bool(events)
            if wanted:
                keep.append(line)
        del plane.lines[:]
        plane.lines.extend(keep)
        used = {e.metadata_id for line in plane.lines for e in line.events}
        for k in [k for k in plane.event_metadata if k not in used]:
            del plane.event_metadata[k]
        tf_op = {k for k, v in plane.stat_metadata.items() if v.name == "tf_op"}
        for md in plane.event_metadata.values():
            stats = [st for st in md.stats if st.metadata_id in tf_op]
            del md.stats[:]
            md.stats.extend(stats)
        planes.append(plane)
    del space.planes[:]
    space.planes.extend(planes)
    Path(dst).write_bytes(space.SerializeToString())


def main() -> None:
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_stepper_trace: needs a TPU")
    stepper = TemporalStepper(jobs())
    stepper.step_round()  # compile outside the trace
    with tempfile.TemporaryDirectory() as d:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=options)
        with jax.profiler.TraceAnnotation("chipbench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("chipbench.round"):
                    stepper.step_round()
        jax.profiler.stop_trace()
        trim(trace.find_xplane(d), sys.argv[1])
    tr = spans.collect(sys.argv[1])
    for sp in sorted(tr.spans, key=lambda s: s.start):
        print("span", sp.name, sp.job, sp.step, sp.start, sp.end)
    for dev, runs in tr.modules.items():
        for s, e in runs:
            print("run", dev, s, e)
    lo, hi = next((s.start, s.end) for s in tr.spans if s.name == trace.WINDOW_SPAN)
    busy = trace.union([c for op in tr.ops[0] if (c := trace._clip(op.start, op.end, lo, hi))])
    t = lo
    for s, e in busy + [(hi, hi)]:
        if s > t:
            print("idle", t, s)
        t = max(t, e)
    print("ops", len(tr.ops[0]))


if __name__ == "__main__":
    main()
