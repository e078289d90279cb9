"""Kernels: ``wire_pack`` (ops/wire_kernels.py) as a share of its HBM
roofline. The bytes are those of the live cohorts the window's pushes
shipped (``push_cohorts``, each ``elems`` δ lanes of ``actors`` clocks),
not of the ``dispatch_lanes``-wide dispatches the plane pads them to: a
change that stops the padding shows as a higher share."""

from readers import roofline_pct
from roofline import wire_pack_bytes

# The Pallas call has no stable name in the trace (it shows as ``%body``):
# it is found by its outputs, the packed words, three verdict planes and
# the 1x8 stats row.
KERNEL = (r"^%\S+ = \(u32\[\d+,\d+\]\{[^}]*\}(, s32\[\d+,1\]\{[^}]*\}){3}, "
          r"s32\[1,8\]\{[^}]*\}\) custom-call\(")


def read(c):
    cfg = c["config"]
    cohorts = c["res"]["counters"]["push_cohorts"]
    if not cohorts:
        return None
    return roofline_pct(c, KERNEL, wire_pack_bytes(cohorts * cfg["elems"], cfg["actors"]))
