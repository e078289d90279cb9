"""Pin JAX onto the host CPU backend for tests, examples and tools.

Must be called before anything queries devices (``jax.devices()``,
first jit execution); importing jax or crdt_tpu beforehand is fine —
backend initialization is lazy.
"""

from __future__ import annotations

import os
import re


def pin_cpu(virtual_devices: int | None = None) -> None:
    """Force the CPU backend, optionally with N virtual devices.

    ``virtual_devices`` sets ``--xla_force_host_platform_device_count``
    in XLA_FLAGS, REPLACING any count inherited from the environment or a
    parent process (multihost worker processes want their own per-process
    count, and the test suite pins exactly 8 — run with
    ``virtual_devices=None`` to keep a caller-supplied XLA_FLAGS count).
    """
    if virtual_devices:
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+",
            "",
            os.environ.get("XLA_FLAGS", ""),
        ).strip()
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={virtual_devices}"
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"  # for any subprocesses

    import jax

    jax.config.update("jax_platforms", "cpu")
