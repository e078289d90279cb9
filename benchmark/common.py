"""What every driver shares: the device, the compile cache and its
counter, the measured window and host spans."""

from __future__ import annotations

import contextlib
import glob
import os
import time

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# A fixed path inside the checkout: the path is part of the cache key.
CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


def use_checkout_cache() -> None:
    """Point JAX's persistent cache at the checkout, whatever the
    environment says, so two checkouts never share one; cache every
    program, so a second run compiles nothing."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"


class Compiles:
    """Backend compile seconds and persistent-cache hits/misses, from
    JAX's own monitoring events (copied from ``chip_smoke.py``)."""

    def __init__(self):
        import jax

        self.compile_s, self.hits, self.misses, self.n = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.n += 1

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snap(self) -> dict:
        return {"compile_s": self.compile_s, "compiles": self.n,
                "cache_hits": self.hits, "cache_misses": self.misses}


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest chip (buffers the runtime counts,
    not a program's temporaries)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


class Spans:
    """Host spans the benchmark places around its calls into each layer:
    total seconds and count per name, and, in a traced run, a profiler
    annotation on the device trace's clock."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.total: dict = {}
        self.count: dict = {}
        self.samples: dict = {}

    @contextlib.contextmanager
    def span(self, name: str, keep: bool = False):
        ann = contextlib.nullcontext()
        if self.traced:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
        t0 = time.perf_counter()
        with ann:
            yield
        dt = time.perf_counter() - t0
        self.total[name] = self.total.get(name, 0.0) + dt
        self.count[name] = self.count.get(name, 0) + 1
        if keep:
            self.samples.setdefault(name, []).append(dt)

    def as_dict(self) -> dict:
        return {k: {"s": self.total[k], "n": self.count[k]} for k in self.total}


class Window:
    """The measured window: host-clock bounds and, with ``--trace 1``, a
    profiler trace of exactly the window, under a ``bench_window``
    annotation."""

    def __init__(self, traced: bool, trace_dir: str):
        self.traced, self.trace_dir = traced, trace_dir
        self.t0 = self.t1 = None
        self._ann = None

    def __enter__(self):
        if self.traced:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation("bench_window")
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        if self.traced:
            import jax

            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def xplane(self):
        hits = sorted(glob.glob(os.path.join(
            self.trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        return hits[-1] if hits else None
