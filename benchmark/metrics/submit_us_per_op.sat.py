"""Serve ingest: host microseconds per op in the benchmark's span around
its ``IngestQueue.add``/``rm`` calls, over the window."""


def read(c):
    span = c["spans"].get("span.submit")
    n = c["res"]["counters"].get("submitted", 0)
    if not span or n <= 0:
        return None
    return span["s"] / n * 1e6
