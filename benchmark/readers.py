"""Arithmetic the per-layer readers share. Each reader returns None
where it finds nothing to read: no trace (a CPU run), or no kernel of
its name in the trace."""

from __future__ import annotations

import numpy as np

import trace_reduce


def idle_pct(c):
    red = c["trace"]
    if red is None or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def roofline_pct(c, pattern: str, nbytes: int, per_call: bool = False):
    """Bytes the calls in the trace needed (``nbytes`` in all, or each
    call's where ``per_call``), over their device time, as a share of the
    chip's HBM bandwidth."""
    red = c["trace"]
    if red is None or c["peaks"] is None:
        return None
    hit = trace_reduce.op_time(red, pattern)
    if hit is None or hit[0] <= 0:
        return None
    secs, calls = hit
    total = nbytes * calls if per_call else nbytes
    return 100.0 * total / secs / c["peaks"]["hbm_bytes_per_s"]


def p99_ms(values):
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), 99)) * 1e3
