"""Device self time under the ``ssd_scan`` scope: the chunked SSD scan, the
part an SSD-scan kernel would replace; in the traced window, per token
trained in the window, in ns/token, averaged over the cell's chips
(chipbench/spans.py)."""

from chipbench import spans


def read(record):
    return spans.ns_per_token(record, "ssd_scan")
