"""Device self time under the ``attention_core`` scope: scores, mask, softmax
and the value product, the part a flash-attention kernel would replace; in
the traced window, per token trained in the window, in ns/token, averaged
over the cell's chips (chipbench/spans.py)."""

from chipbench import spans


def read(record):
    return spans.ns_per_token(record, "attention_core")
