"""Kernels: the share of the device's busy time in the window that the
map fold's Pallas call took (``_map_chunk_fold`` in ops/pallas_kernels.py,
named by its own jit in the HLO the trace shows). The rest is the chunk
decode, the parked-remove replay, the payload pass and the writers' step:
this says whether the kernel or the XLA work around it sets the pace."""

import trace_reduce

KERNEL = r"^%_map_chunk_fold[.0-9]* = .*tpu_custom_call"


def read(c):
    red = c["trace"]
    if red is None or red["busy_s"] <= 0:
        return None
    hit = trace_reduce.op_time(red, KERNEL)
    if hit is None:
        return None
    return 100.0 * hit[0] / red["devices"] / red["busy_s"]
