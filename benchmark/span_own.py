"""A program span's own time: the median milliseconds, call by call, of
span ``name`` less all of its ``children``. Each child fires once in each
call of ``name``, so the duration rings pair up entry by entry; where any
child fired another number of times, or never, there is nothing to pair
and the helper returns None."""

from __future__ import annotations

import numpy as np

from program_spans import stats


def median_own_ms(name: str, *children: str):
    top = stats(name)
    kids = [stats(c) for c in children]
    if top is None or any(k is None or k["n"] != top["n"] for k in kids):
        return None
    own = np.asarray(top["durations_s"], np.float64)
    for k in kids:
        own = own - np.asarray(k["durations_s"], np.float64)
    return float(np.median(own)) * 1e3
