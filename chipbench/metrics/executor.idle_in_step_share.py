"""Share of the traced window in which the device idled while the host was
inside ``repro.stepper.step`` (the step call through ``block_until_ready``:
dispatch, launch, any compile), on the device's clock, in percent (averaged
over the cell's chips; chipbench/spans.py)."""

from chipbench import spans


def read(record):
    return spans.idle_share(record, "step")
