"""Device time of whole programs, by name, from a profiler trace. Each
chip's "XLA Modules" line holds one event per program run, named
``jit_<function>(<fingerprint>)``; ``trace_reduce`` reads the ops of the
"XLA Ops" line instead. A reader whose work is one named program, made of
whatever ops the compiler chose, reads its time here."""

from __future__ import annotations

import re
from typing import Optional, Tuple

import trace_reduce

MODULE_LINES = ("XLA Modules",)


def module_time(path: str, pattern: str) -> Optional[Tuple[float, int]]:
    """Device seconds and runs, summed over chips, of the programs whose
    name matches ``pattern``, clipped to the ``bench_window``; None when
    the trace holds no window or no such run."""
    from jax.profiler import ProfileData

    rx = re.compile(pattern)
    window, runs = None, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            for line in plane.lines:
                if line.name in MODULE_LINES:
                    runs += [(e.start_ns, e.end_ns) for e in line.events
                             if rx.search(e.name)]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == trace_reduce.WINDOW:
                        window = (e.start_ns, e.end_ns)
    if window is None:
        return None
    lo, hi = window
    inside = [min(b, hi) - max(a, lo) for a, b in runs if min(b, hi) > max(a, lo)]
    if not inside:
        return None
    return sum(inside) * 1e-9, len(inside)
