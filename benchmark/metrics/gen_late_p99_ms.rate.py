"""Load generator: p99 of (submit time - due time) over the window's ops.
A late generator is a stalled loop, not a fast server."""

from readers import p99_ms


def read(c):
    return p99_ms(c["res"]["samples"].get("gen_late_s", []))
