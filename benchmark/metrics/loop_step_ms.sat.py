"""Serve loop: milliseconds per ``ServeLoop.step`` in the benchmark's span
around it, over the window."""


def read(c):
    span = c["spans"].get("span.step")
    if not span or span["n"] <= 0:
        return None
    return span["s"] / span["n"] * 1e3
