"""Serve WAL: median milliseconds of one ``serve.wal_commit`` program span
(a slab's WAL append and its group-commit fsync) over the window."""

from program_spans import median_ms


def read(c):
    return median_ms("serve.wal_commit")
