"""Shared example bootstrap: honor a JAX_PLATFORMS=cpu request robustly.

Thin wrapper over :mod:`crdt_tpu.utils.cpu_pin` (the one copy of the
pin-CPU recipe) that only acts when the caller asked for CPU via the
environment.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pin_platform():
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        return
    from crdt_tpu.utils.cpu_pin import pin_cpu

    pin_cpu()
