"""Fan-out push: median milliseconds of one ``FanoutPlane.push`` in the
benchmark's span around it, over the window."""

import numpy as np


def read(c):
    s = c["res"]["samples"].get("push_s", [])
    return float(np.median(s)) * 1e3 if s else None
