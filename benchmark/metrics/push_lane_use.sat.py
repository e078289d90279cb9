"""Fan-out push: the share of dispatch lanes that carry a live cohort, from
the ``fanout.dispatch`` program span's attribute sums over the window:
100 x sum of ``live`` / sum of ``lanes``."""

from program_spans import stats


def read(c):
    st = stats("fanout.dispatch")
    if st is None or not st["attrs"].get("lanes"):
        return None
    return 100.0 * st["attrs"].get("live", 0) / st["attrs"]["lanes"]
