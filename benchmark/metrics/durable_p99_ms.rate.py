"""Serve WAL: p99 of submit → durable over the sampled ops of the window,
from the ``Tracer``'s raw stamps (``durable`` is stamped when the
group-committed slab's dispatch finishes)."""

from readers import p99_ms


def read(c):
    return p99_ms(c["res"]["samples"].get("durable_s", []))
