"""Fan-out push: median milliseconds of one ``fanout.snapshot`` program
span (the batched gather of the pushed tenants' rows, its fetch to the
host and the per-tenant slices) over the window."""

from program_spans import median_ms


def read(c):
    return median_ms("fanout.snapshot")
