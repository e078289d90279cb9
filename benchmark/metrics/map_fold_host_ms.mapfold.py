"""Anti-entropy entry: median milliseconds, call by call, of
``mesh_fold_map`` (the ``ae.map_fold`` program span) outside its
``ae.map_fold.wait`` span: the entry's host work around one fold."""

from program_spans import median_less_ms


def read(c):
    return median_less_ms("ae.map_fold", "ae.map_fold.wait")
