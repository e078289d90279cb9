"""Fan-out push: median milliseconds of one ``fanout.stack`` program span
(the host stack of a dispatch's ``dispatch_lanes`` base rows and their
copy to the device) over the window."""

from program_spans import median_ms


def read(c):
    return median_ms("fanout.stack")
