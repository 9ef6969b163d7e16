"""Device self time under the ``mlp`` scope: the SwiGLU channel mixer; in the
traced window, per token trained in the window, in ns/token, averaged over
the cell's chips (chipbench/spans.py)."""

from chipbench import spans


def read(record):
    return spans.ns_per_token(record, "mlp")
