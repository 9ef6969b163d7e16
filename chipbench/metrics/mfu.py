"""Model FLOP/s utilization of the whole training step: FLOPs the jobs'
steps require (chipbench/flops.py, no recomputation) x steps in the window,
over window x chips x the chip's published bf16 peak, in percent."""


def read(record):
    if not record["peak_flops_per_s"]:
        return None
    done = sum(j["steps"] * j["flops_per_step"] for j in record["jobs"])
    return 100.0 * done / (record["window_s"] * record["chips"] * record["peak_flops_per_s"])
