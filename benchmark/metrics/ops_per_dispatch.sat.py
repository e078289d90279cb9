"""Serve apply: ops applied per device dispatch, from the ``FlushReport``s
of the dispatches the window finished (``ops_applied`` / ``dispatches``)."""


def read(c):
    k = c["res"]["counters"]
    if k.get("dispatches", 0) <= 0:
        return None
    return k["ops_acked"] / k["dispatches"]
