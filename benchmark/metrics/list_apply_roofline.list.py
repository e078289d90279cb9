"""Kernels: the list apply as a share of its HBM roofline: the bytes the
window's rounds must move (``roofline_list``), over the device time of
the apply program, found by its name ``jit__list_apply_chunks`` on the
trace's "XLA Modules" line (the reset fill between replays is another
program and is not counted), over the chip's HBM bandwidth."""

from roofline_list import apply_bytes
from trace_modules import module_time

PROGRAM = r"^jit__list_apply_chunks\b"


def read(c):
    path, peaks = c["res"].get("xplane"), c["peaks"]
    if path is None or peaks is None:
        return None
    hit = module_time(path, PROGRAM)
    if hit is None or hit[0] <= 0:
        return None
    k = c["res"]["counters"]
    nbytes = apply_bytes(k["lanes_live"], k["chunk_reads"], k["chunk_width"])
    return 100.0 * nbytes / hit[0] / peaks["hbm_bytes_per_s"]
