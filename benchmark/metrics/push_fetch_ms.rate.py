"""Fan-out push: median milliseconds of one ``fanout.wire_fetch`` program
span (waiting for a dispatch's wire and copying it to the host) over the
window."""

from program_spans import median_ms


def read(c):
    return median_ms("fanout.wire_fetch")
