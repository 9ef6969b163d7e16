"""Device self time under the ``attention`` scope, its ``attention_core``
included: the GQA and MLA layers, projections and core; in the traced
window, per token trained in the window, in ns/token, averaged over the
cell's chips (chipbench/spans.py)."""

from chipbench import spans


def read(record):
    return spans.ns_per_token(record, "attention")
