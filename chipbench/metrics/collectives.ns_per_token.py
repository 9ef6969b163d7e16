"""Device self time of the collective operations (all-gather,
reduce-scatter, all-reduce, all-to-all, collective-permute, their -start
and -done halves and the fusions XLA names after them): the time the core
spends in the exchange between chips or waiting on it; in the traced
window, per token trained in the window, in ns/token, averaged over the
cell's chips (chipbench/spans.py)."""

from chipbench import spans


def read(record):
    return spans.collective_ns_per_token(record)
