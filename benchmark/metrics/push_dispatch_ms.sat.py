"""Fan-out push: median milliseconds, call by call, of one ``fanout.dispatch``
program span less its ``fanout.stack``, ``fanout.wire_fetch`` and
``fanout.deliver`` spans: the ``mesh_fanout_push`` call's own host path and
the dispatch's lane bookkeeping, during which the chip waits."""

from span_own import median_own_ms


def read(c):
    return median_own_ms("fanout.dispatch", "fanout.stack", "fanout.wire_fetch",
                         "fanout.deliver")
