"""List apply entry: median milliseconds, call by call, of
``BatchedList.apply_chunks`` (the ``list.apply`` program span) outside its
``list.apply.wait`` span: the chunk-id checks, counters, upload and
dispatch around one round's apply."""

from program_spans import median_less_ms


def read(c):
    return median_less_ms("list.apply", "list.apply.wait")
