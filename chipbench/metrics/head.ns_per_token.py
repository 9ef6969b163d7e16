"""Device self time under the ``head`` scope: the final projection and the
chunked cross-entropy; in the traced window, per token trained in the
window, in ns/token, averaged over the cell's chips (chipbench/spans.py)."""

from chipbench import spans


def read(record):
    return spans.ns_per_token(record, "head")
