"""Serve loop: milliseconds of one ``ServeLoop.step`` outside its
``serve.finish`` span (assembly, WAL commit, persist drain and issue: the
step's host work), from the program spans' totals over the window."""

from program_spans import stats


def read(c):
    step, finish = stats("serve.step"), stats("serve.finish")
    if step is None:
        return None
    host = step["total_s"] - (finish["total_s"] if finish else 0.0)
    return host / step["n"] * 1e3
