"""Record the small chip trace that ``test_trace.py`` reads.

  python3 chipbench/tests/record_trace.py <out.xplane.pb>

On a TPU: three rounds of one matrix product, each followed by a 5 ms
sleep inside the round, then a 10 ms pause in a span of its own, all inside
a ``chipbench.window`` span.  Prints every device operation and benchmark
span of the trace, so that the test's numbers can be worked out by hand.
"""

import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, str(__import__("pathlib").Path(__file__).resolve().parents[2]))
from chipbench import trace  # noqa: E402


def main() -> None:
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: needs a TPU")
    f = jax.jit(lambda x: (x @ x).astype(jnp.bfloat16))
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=options)
        with jax.profiler.TraceAnnotation("chipbench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("chipbench.round"):
                    f(x).block_until_ready()
                    time.sleep(0.005)
            with jax.profiler.TraceAnnotation("chipbench.pause"):
                time.sleep(0.01)
        jax.profiler.stop_trace()
        path = trace.find_xplane(d)
        shutil.copy(path, sys.argv[1])
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(sys.argv[1]).planes:
        print("plane", plane.name, [line.name for line in plane.lines])
    spans, ops = trace.collect(sys.argv[1])
    for s in spans:
        print("span", *s)
    for dev, evs in ops.items():
        for e in evs:
            print("op", dev, *e)


if __name__ == "__main__":
    main()
