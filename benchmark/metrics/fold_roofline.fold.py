"""Kernels: ``fold_fused`` (ops/pallas_kernels.py) as a share of its HBM
roofline. One call folds one chip's resident replica block."""

from readers import roofline_pct
from roofline import fold_fused_bytes

# The Pallas call, named by its enclosing jit in the HLO the trace shows.
KERNEL = r"^%_fold_fused_jit[.0-9]* = .*tpu_custom_call"


def read(c):
    k = c["res"]["counters"]
    one_call = fold_fused_bytes(k["rows_per_device"], k["elems"], k["actors"])
    return roofline_pct(c, KERNEL, one_call, per_call=True)
