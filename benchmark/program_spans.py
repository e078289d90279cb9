"""What the readers of program spans share: the per-name aggregate of
``crdt_tpu.telemetry.span`` (``span_stats``). The program records its
spans only while a profiler session runs, and a run profiles exactly
its measured window with ``--trace 1``, so in a traced run the
aggregate covers the window and nothing else. Each helper returns None
where the span never fired, or where the program keeps no aggregate."""

from __future__ import annotations

import numpy as np


def stats(name: str):
    """The aggregate of span ``name``, or None."""
    from crdt_tpu import telemetry

    read = getattr(telemetry, "span_stats", None)
    if read is None:
        return None
    st = read().get(name)
    return st if st and st["n"] else None


def median_ms(name: str):
    """Median milliseconds of one call of span ``name``."""
    st = stats(name)
    return None if st is None else float(np.median(st["durations_s"])) * 1e3


def median_less_ms(name: str, child: str):
    """Median milliseconds, call by call, of span ``name`` less its one
    child span ``child``: the two duration rings pair up where both
    spans fired equally often."""
    a, b = stats(name), stats(child)
    if a is None or b is None or a["n"] != b["n"]:
        return None
    d = np.asarray(a["durations_s"]) - np.asarray(b["durations_s"])
    return float(np.median(d)) * 1e3
